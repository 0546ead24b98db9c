"""The full per-frame 2D->3D transformation pipeline (§3.1 workflow).

Two entry points, both jit-compatible and batchable over streams:

* :func:`anchor_step` — "Preparation": ingest cloud 3D detections for an
  anchor frame, project them to 2D to (re)seed the tracker, and refresh the
  fleet-average object size.
* :func:`transform_step` — "Transformation": run tracking-based association
  on the current 2D detections, project the point cloud into the masks,
  filter each cluster (Algorithm 1), RANSAC the visible surface and estimate
  3D boxes (Eqs. 1-2), then write results back onto the tracks for the next
  frame.

The 2D detector itself (instance segmentation) is *not* called here — its
outputs (boxes + instance-id label image) are inputs, so oracle detectors,
the YOLO-lite JAX net, or recorded outputs can all drive the same pipeline.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import association, box_estimation, boxes as box_ops
from repro.core import filtration, projection, ransac, tracking


# Names of the device step's stages (``jax.named_scope``): each lands in the
# ``op_name`` metadata of the stage's HLO instructions, so a profiler trace
# of the compiled step can be read per stage. An anchor frame's ingest runs
# under the same names as a transformed frame's matching work.
STAGE_ASSOCIATE = "associate"
STAGE_PROJECT = "project"
STAGE_CLUSTERS = "clusters"
STAGE_FILTRATION = "filtration"
STAGE_RANSAC = "ransac"
STAGE_BOXES = "boxes"


class TransformParams(NamedTuple):
    filtration: filtration.FiltrationParams = filtration.FiltrationParams()
    ransac: ransac.RansacParams = ransac.RansacParams()
    boxest: box_estimation.BoxEstParams = box_estimation.BoxEstParams()
    tracker: tracking.TrackerParams = tracking.TrackerParams()
    iou_assoc: float = 0.3        # association criterion (paper: 0.3)
    pts_per_obj: int = 256        # cluster buffer size
    use_tba: bool = True          # tracking-based association on/off (Table 4)
    # Ops backend for the hot ops (point projection, IoU, RANSAC scoring):
    # "ref" / "pallas" / "auto" (per-op from the autotune table) / ""
    # (defer to MOBY_BACKEND env, else platform default). A plain string
    # keeps the NamedTuple hashable for static jit args.
    backend: str = ""


def resolve_backend_params(params: TransformParams,
                           backend: str | None = None) -> TransformParams:
    """Apply an optional backend override, then pin the deferred "" to its
    resolved value ("ref" / "pallas", or "auto" when MOBY_BACKEND=auto
    asks for per-op autotuned resolution). Pinning matters because
    TransformParams is a static jit cache key: a later MOBY_BACKEND change
    must not be masked by a cache hit on an unresolved "". Engines call
    this once at construction.
    """
    from repro import ops
    if backend is not None:
        params = params._replace(backend=backend)
    return params._replace(backend=ops.resolve_backend(params.backend))


class MobyState(NamedTuple):
    tracks: tracking.TrackState
    avg_size: jnp.ndarray        # (3,) running average object size (l, w, h)
    key: jax.Array


def init_state(max_tracks: int, key: jax.Array,
               avg_size=(4.0, 1.7, 1.6)) -> MobyState:
    """Default avg size ~ KITTI car mean (l, w, h)."""
    return MobyState(tracks=tracking.init_tracks(max_tracks),
                     avg_size=jnp.asarray(avg_size, jnp.float32), key=key)


class FrameOutput(NamedTuple):
    boxes3d: jnp.ndarray         # (D, 7)
    valid: jnp.ndarray           # (D,)
    det_to_track: jnp.ndarray    # (D,)
    track_boxes2d: jnp.ndarray   # (T, 4) predicted boxes (diagnostics)


def _associate(tracks: tracking.TrackState, boxes2d: jnp.ndarray,
               valid: jnp.ndarray, params: TransformParams):
    """Kalman predict, match the predicted boxes to ``boxes2d`` (IoU +
    auction) and update/spawn tracks. Returns (tracks, det_to_track,
    predicted 2D boxes)."""
    tracks, pred2d = tracking.predict(tracks)
    t2d, d2t, _ = association.associate(pred2d, tracks.active, boxes2d,
                                        valid, params.iou_assoc,
                                        params.backend)
    tracks = tracking.update(tracks, t2d, boxes2d, params.tracker)
    tracks, d2t = tracking.spawn(tracks, boxes2d, valid, d2t)
    return tracks, d2t, pred2d


def _cloud_boxes2d(boxes3d: jnp.ndarray,
                   calib: projection.Calibration) -> jnp.ndarray:
    return jax.vmap(lambda b: box_ops.project_box3d_to_2d(
        b, calib.tr, calib.p))(boxes3d)


def _anchor_tail(state: MobyState, tracks: tracking.TrackState,
                 d2t: jnp.ndarray, pred2d: jnp.ndarray, boxes3d: jnp.ndarray,
                 valid: jnp.ndarray) -> tuple[MobyState, FrameOutput]:
    """An associated anchor frame: write the cloud boxes onto the tracks
    and refresh the fleet-average object size."""
    with jax.named_scope(STAGE_BOXES):
        tracks = tracking.set_box3d(tracks, d2t, boxes3d, valid)
        # Refresh fleet-average size from the (trusted) anchor results.
        n = jnp.maximum(jnp.sum(valid), 1)
        mean_size = jnp.sum(jnp.where(valid[:, None], boxes3d[:, 3:6], 0.0),
                            axis=0) / n
        avg_size = jnp.where(jnp.sum(valid) > 0, mean_size, state.avg_size)
    out = FrameOutput(boxes3d=boxes3d, valid=valid, det_to_track=d2t,
                      track_boxes2d=pred2d)
    return MobyState(tracks=tracks, avg_size=avg_size, key=state.key), out


def _transform_tail(state: MobyState, tracks: tracking.TrackState,
                    d2t: jnp.ndarray, pred2d: jnp.ndarray,
                    points: jnp.ndarray, det_valid: jnp.ndarray,
                    label_img: jnp.ndarray, calib: projection.Calibration,
                    params: TransformParams) -> tuple[MobyState, FrameOutput]:
    """An associated non-anchor frame: projection, clusters, filtration,
    RANSAC and 3D boxes, written back onto the tracks."""
    d = det_valid.shape[0]
    key, sub = jax.random.split(state.key)

    # --- point projection (§3.3) ------------------------------------------
    # Fused project + visibility + flat-index + label gather (ops backend).
    with jax.named_scope(STAGE_PROJECT):
        labels = projection.project_and_label(points, label_img, calib,
                                              params.backend)
    with jax.named_scope(STAGE_CLUSTERS):
        clusters, cvalid, _ = projection.build_clusters(points, labels, d,
                                                        params.pts_per_obj)

    # --- point filtration (Algorithm 1) ------------------------------------
    # Associated objects carry a center prior from the previous 3D box.
    with jax.named_scope(STAGE_FILTRATION):
        t_idx0 = jnp.clip(d2t, 0, state.tracks.x.shape[0] - 1)
        prior_ok = (d2t >= 0) & tracks.has_box3d[t_idx0]
        prior_centers = tracks.box3d[t_idx0][:, :3]
        keep = filtration.filter_clusters(clusters, cvalid, params.filtration,
                                          prior_centers, prior_ok)

    # --- RANSAC surface fitting --------------------------------------------
    with jax.named_scope(STAGE_RANSAC):
        fit = ransac.ransac_planes(sub, clusters, keep, params.ransac,
                                   backend=params.backend)

    # --- 3D box estimation (Eqs. 1-2, Fig. 10), written back for the next
    # frame ------------------------------------------------------------------
    with jax.named_scope(STAGE_BOXES):
        t_idx = jnp.clip(d2t, 0, state.tracks.x.shape[0] - 1)
        associated = (d2t >= 0) & tracks.has_box3d[t_idx]
        prev_boxes = tracks.box3d[t_idx]
        boxes3d, ok = box_estimation.estimate_boxes(
            clusters, fit.inliers, keep, fit.normal, fit.ok, associated,
            prev_boxes, state.avg_size, params.boxest)
        valid = ok & det_valid
        if params.use_tba:
            tracks = tracking.set_box3d(tracks, d2t, boxes3d, valid)

    out = FrameOutput(boxes3d=boxes3d, valid=valid, det_to_track=d2t,
                      track_boxes2d=pred2d)
    return MobyState(tracks=tracks, avg_size=state.avg_size, key=key), out


def anchor_step(state: MobyState, boxes3d: jnp.ndarray, valid: jnp.ndarray,
                calib: projection.Calibration,
                params: TransformParams = TransformParams()) -> tuple[MobyState, FrameOutput]:
    """Ingest cloud 3D detections at an anchor frame (steps 1-2 in Fig. 4)."""
    with jax.named_scope(STAGE_ASSOCIATE):
        tracks, d2t, pred2d = _associate(
            state.tracks, _cloud_boxes2d(boxes3d, calib), valid, params)
    return _anchor_tail(state, tracks, d2t, pred2d, boxes3d, valid)


def transform_step(state: MobyState, points: jnp.ndarray,
                   det_boxes2d: jnp.ndarray, det_valid: jnp.ndarray,
                   label_img: jnp.ndarray, calib: projection.Calibration,
                   params: TransformParams = TransformParams()) -> tuple[MobyState, FrameOutput]:
    """Transform one non-anchor frame (steps 3-4 in Fig. 4).

    Args:
      state: Moby per-stream state.
      points: (N, 3) LiDAR points.
      det_boxes2d: (D, 4) 2D detections [x1,y1,x2,y2].
      det_valid: (D,) mask.
      label_img: (H, W) int32 instance-id image; id i+1 = detection slot i.
      calib: sensor calibration.
    """
    # --- tracking-based association (§3.2) --------------------------------
    with jax.named_scope(STAGE_ASSOCIATE):
        if params.use_tba:
            tracks, d2t, pred2d = _associate(state.tracks, det_boxes2d,
                                             det_valid, params)
        else:
            # Ablation (Table 4, TRS-only): no association — every
            # detection is treated as a new object.
            tracks, pred2d = tracking.predict(state.tracks)
            d2t = jnp.full((det_boxes2d.shape[0],), -1, jnp.int32)
    return _transform_tail(state, tracks, d2t, pred2d, points, det_valid,
                           label_img, calib, params)


def fused_step(state: MobyState, points: jnp.ndarray,
               det_boxes2d: jnp.ndarray, det_valid: jnp.ndarray,
               label_img: jnp.ndarray, cloud_boxes3d: jnp.ndarray,
               cloud_valid: jnp.ndarray, is_anchor: jnp.ndarray,
               calib: projection.Calibration,
               params: TransformParams = TransformParams()
               ) -> tuple[MobyState, FrameOutput]:
    """One frame with its treatment resolved **on device**.

    A traced ``is_anchor`` flag selects between :func:`anchor_step` (ingest
    the cloud 3D result) and :func:`transform_step` (2D->3D
    transformation), so no host-side ``bool()`` sync is needed to branch.
    Batched engines ``vmap`` this over streams — each stream takes its own
    branch — and ``lax.scan`` can wrap it for device-resident multi-frame
    runs (repro.fleet).

    Under ``vmap`` the batched ``lax.cond`` lowers to a select that runs
    both branches for every stream, so the association — both branches
    run one, on different boxes — is taken out of it: each stream's own
    boxes (the projected cloud boxes or its 2D detections) are selected
    first and associated once, and the cond covers only the tails. Without
    tracking-based association the transform branch associates nothing,
    and the whole steps stay inside the cond.
    """
    if not params.use_tba:
        def _anchor(op):
            st, _pts, _b2, _v2, _li, b3, v3 = op
            return anchor_step(st, b3, v3, calib, params)

        def _transform(op):
            st, pts, b2, v2, li, _b3, _v3 = op
            return transform_step(st, pts, b2, v2, li, calib, params)

        return jax.lax.cond(is_anchor, _anchor, _transform,
                            (state, points, det_boxes2d, det_valid,
                             label_img, cloud_boxes3d, cloud_valid))

    with jax.named_scope(STAGE_ASSOCIATE):
        boxes2d = jnp.where(is_anchor, _cloud_boxes2d(cloud_boxes3d, calib),
                            det_boxes2d)
        valid = jnp.where(is_anchor, cloud_valid, det_valid)
        tracks, d2t, pred2d = _associate(state.tracks, boxes2d, valid, params)

    def _anchor(op):
        st, tr, d2, p2, _pts, _v2, _li, b3, v3 = op
        return _anchor_tail(st, tr, d2, p2, b3, v3)

    def _transform(op):
        st, tr, d2, p2, pts, v2, li, _b3, _v3 = op
        return _transform_tail(st, tr, d2, p2, pts, v2, li, calib, params)

    return jax.lax.cond(is_anchor, _anchor, _transform,
                        (state, tracks, d2t, pred2d, points, det_valid,
                         label_img, cloud_boxes3d, cloud_valid))
