"""Kalman-filter tracking in the 2D image plane (§3.2).

SORT-style [Bewley et al., ICIP'16] constant-velocity Kalman filter over the
observation ``z = [u, v, s, r]`` (box center, scale=area, aspect ratio) with
state ``x = [u, v, s, r, du, dv, ds]``. All tracks live in fixed slots with
an active mask, so predict/update vmap over the slot dimension and the whole
tracker is jit-compatible.

Each track also carries the object's latest 3D box (size + heading), which
is what the 2D->3D transformation consumes as its per-object prior.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# f32 matmuls at full precision: a TPU's default is one bf16 pass.
_HI = jax.lax.Precision.HIGHEST

STATE_DIM = 7
OBS_DIM = 4


class TrackerParams(NamedTuple):
    max_age: int = 3          # frames a track survives without a match
    min_area: float = 1.0


class TrackState(NamedTuple):
    x: jnp.ndarray            # (T, 7) Kalman mean
    p: jnp.ndarray            # (T, 7, 7) Kalman covariance
    active: jnp.ndarray       # (T,) bool
    age: jnp.ndarray          # (T,) frames since last match
    hits: jnp.ndarray         # (T,) total matches
    track_id: jnp.ndarray     # (T,) stable id (-1 if free)
    box3d: jnp.ndarray        # (T, 7) latest 3D box for this object
    has_box3d: jnp.ndarray    # (T,) bool
    next_id: jnp.ndarray      # scalar


def _fh_matrices(dtype=jnp.float32):
    f = jnp.eye(STATE_DIM, dtype=dtype)
    f = f.at[0, 4].set(1.0).at[1, 5].set(1.0).at[2, 6].set(1.0)
    h = jnp.zeros((OBS_DIM, STATE_DIM), dtype=dtype).at[
        jnp.arange(4), jnp.arange(4)].set(1.0)
    return f, h


def _qr_matrices(dtype=jnp.float32):
    q = jnp.diag(jnp.array([1, 1, 1, 1, 0.01, 0.01, 0.0001], dtype=dtype))
    r = jnp.diag(jnp.array([1, 1, 10, 10], dtype=dtype))
    return q, r


def bbox_to_z(box: jnp.ndarray) -> jnp.ndarray:
    """[x1,y1,x2,y2] -> [u, v, s, r]."""
    w = jnp.maximum(box[..., 2] - box[..., 0], 1e-3)
    h = jnp.maximum(box[..., 3] - box[..., 1], 1e-3)
    u = box[..., 0] + w / 2
    v = box[..., 1] + h / 2
    return jnp.stack([u, v, w * h, w / h], axis=-1)


def z_to_bbox(z: jnp.ndarray) -> jnp.ndarray:
    """[u, v, s, r] -> [x1,y1,x2,y2]."""
    s = jnp.maximum(z[..., 2], 1e-3)
    r = jnp.maximum(z[..., 3], 1e-3)
    w = jnp.sqrt(s * r)
    h = s / w
    return jnp.stack([z[..., 0] - w / 2, z[..., 1] - h / 2,
                      z[..., 0] + w / 2, z[..., 1] + h / 2], axis=-1)


def init_tracks(max_tracks: int, dtype=jnp.float32) -> TrackState:
    return TrackState(
        x=jnp.zeros((max_tracks, STATE_DIM), dtype),
        p=jnp.tile(jnp.eye(STATE_DIM, dtype=dtype)[None] * 10.0, (max_tracks, 1, 1)),
        active=jnp.zeros((max_tracks,), bool),
        age=jnp.zeros((max_tracks,), jnp.int32),
        hits=jnp.zeros((max_tracks,), jnp.int32),
        track_id=jnp.full((max_tracks,), -1, jnp.int32),
        box3d=jnp.zeros((max_tracks, 7), dtype),
        has_box3d=jnp.zeros((max_tracks,), bool),
        next_id=jnp.int32(0),
    )


def predict(state: TrackState) -> tuple[TrackState, jnp.ndarray]:
    """Kalman predict for all active slots. Returns predicted 2D boxes (T, 4)."""
    f, _ = _fh_matrices(state.x.dtype)
    q, _ = _qr_matrices(state.x.dtype)
    x = jnp.matmul(state.x, f.T, precision=_HI)
    # Clamp scale velocity so area stays positive (SORT convention).
    neg = (x[:, 2] + x[:, 6]) <= 0
    x = x.at[:, 6].set(jnp.where(neg, 0.0, x[:, 6]))
    p = jnp.einsum('ij,tjk,lk->til', f, state.p, f, precision=_HI) + q[None]
    x = jnp.where(state.active[:, None], x, state.x)
    p = jnp.where(state.active[:, None, None], p, state.p)
    boxes = z_to_bbox(x[:, :4])
    return state._replace(x=x, p=p), boxes


def update(state: TrackState, track_to_det: jnp.ndarray, det_boxes: jnp.ndarray,
           params: TrackerParams = TrackerParams()) -> TrackState:
    """Kalman update with matched detections; age unmatched; kill stale.

    Args:
      track_to_det: (T,) detection index per track, -1 if unmatched.
      det_boxes: (D, 4) detections.
    """
    _, h = _fh_matrices(state.x.dtype)
    _, r = _qr_matrices(state.x.dtype)
    matched = (track_to_det >= 0) & state.active
    det_idx = jnp.clip(track_to_det, 0, det_boxes.shape[0] - 1)
    z = bbox_to_z(det_boxes[det_idx])  # (T, 4)

    def kupdate(x, p, zi):
        hp = jnp.matmul(h, p, precision=_HI)
        y = zi - jnp.matmul(h, x, precision=_HI)
        s = jnp.matmul(hp, h.T, precision=_HI) + r
        k = jnp.linalg.solve(s, hp).T  # (7, 4)
        x2 = x + jnp.matmul(k, y, precision=_HI)
        p2 = jnp.matmul(jnp.eye(STATE_DIM, dtype=x.dtype)
                        - jnp.matmul(k, h, precision=_HI), p, precision=_HI)
        return x2, p2

    x2, p2 = jax.vmap(kupdate)(state.x, state.p, z)
    x = jnp.where(matched[:, None], x2, state.x)
    p = jnp.where(matched[:, None, None], p2, state.p)
    age = jnp.where(matched, 0, state.age + 1)
    hits = jnp.where(matched, state.hits + 1, state.hits)
    active = state.active & (age <= params.max_age)
    return state._replace(x=x, p=p, age=age, hits=hits, active=active)


def spawn(state: TrackState, det_boxes: jnp.ndarray, det_valid: jnp.ndarray,
          det_to_track: jnp.ndarray) -> tuple[TrackState, jnp.ndarray]:
    """Start new tracks for unmatched detections in free slots.

    Returns (state, det_to_track) where newly spawned detections now point at
    their new track slot.
    """
    t = state.x.shape[0]
    d = det_boxes.shape[0]
    free = ~state.active                        # (T,)
    need = det_valid & (det_to_track < 0)       # (D,)
    # Rank free slots and needy detections; pair them by rank.
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1        # rank among free
    need_rank = jnp.cumsum(need.astype(jnp.int32)) - 1        # rank among needy
    n_free = jnp.sum(free)
    # For each track slot: which detection (by rank) lands here?
    # slot with free_rank k takes the detection with need_rank k.
    det_rank_for_slot = jnp.where(free, free_rank, -1)        # (T,)
    # Build rank -> det index map.
    rank_to_det = jnp.full((t,), -1, jnp.int32)
    capped_rank = jnp.clip(need_rank, 0, t - 1)
    rank_to_det = rank_to_det.at[jnp.where(need, capped_rank, t - 1)].max(
        jnp.where(need & (need_rank < t), jnp.arange(d, dtype=jnp.int32), -1))
    take = jnp.where(det_rank_for_slot >= 0,
                     rank_to_det[jnp.clip(det_rank_for_slot, 0, t - 1)], -1)
    spawning = (take >= 0) & free & (det_rank_for_slot < jnp.sum(need))
    z = bbox_to_z(det_boxes[jnp.clip(take, 0, d - 1)])
    x_new = jnp.zeros_like(state.x).at[:, :4].set(z)
    p_new = jnp.tile(jnp.eye(STATE_DIM, dtype=state.x.dtype)[None] * 10.0,
                     (t, 1, 1))
    ids_new = state.next_id + jnp.cumsum(spawning.astype(jnp.int32)) - 1
    x = jnp.where(spawning[:, None], x_new, state.x)
    p = jnp.where(spawning[:, None, None], p_new, state.p)
    active = state.active | spawning
    age = jnp.where(spawning, 0, state.age)
    hits = jnp.where(spawning, 1, state.hits)
    track_id = jnp.where(spawning, ids_new, state.track_id)
    has_box3d = jnp.where(spawning, False, state.has_box3d)
    next_id = state.next_id + jnp.sum(spawning)
    # Update det_to_track for spawned detections.
    onehot = (take[:, None] == jnp.arange(d)[None, :]) & spawning[:, None]
    new_map = jnp.where(jnp.any(onehot, axis=0), jnp.argmax(onehot, axis=0),
                        det_to_track).astype(jnp.int32)
    state = state._replace(x=x, p=p, active=active, age=age, hits=hits,
                           track_id=track_id, has_box3d=has_box3d,
                           next_id=next_id)
    return state, new_map


def set_box3d(state: TrackState, det_to_track: jnp.ndarray,
              boxes3d: jnp.ndarray, boxes_ok: jnp.ndarray) -> TrackState:
    """Write per-detection 3D boxes back onto their tracks."""
    t = state.x.shape[0]
    d = det_to_track.shape[0]
    onehot = (det_to_track[:, None] == jnp.arange(t)[None, :]) & \
        boxes_ok[:, None] & (det_to_track >= 0)[:, None]      # (D, T)
    has = jnp.any(onehot, axis=0)
    src = jnp.argmax(onehot, axis=0)                          # (T,)
    new_boxes = boxes3d[src]
    box3d = jnp.where(has[:, None], new_boxes, state.box3d)
    has_box3d = state.has_box3d | has
    return state._replace(box3d=box3d, has_box3d=has_box3d)
