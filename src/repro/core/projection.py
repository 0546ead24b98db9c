"""Point projection (§3.3): transfer 2D semantics onto the point cloud.

The LiDAR frame is projected into the camera image with the fixed extrinsic
``Tr`` (LiDAR -> camera) and projective ``P`` (camera -> pixel) calibration
matrices (time-invariant, provided by the sensor rig as in KITTI). Each
in-image point is labeled with the instance id of the segmentation mask it
lands in ("squeezing the stacked masks along the channel dimension" — we
keep the equivalent flattened instance-id image). Labeled points are then
compacted into fixed-size per-object cluster buffers.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import ops


@jax.tree_util.register_pytree_node_class
class Calibration(NamedTuple):
    tr: jnp.ndarray  # (3, 4) LiDAR -> camera rigid transform
    p: jnp.ndarray   # (3, 4) camera projection matrix
    height: int      # label image height
    width: int       # label image width

    # The image dims are *structural* (they size the Pallas grid and the
    # flat gather index), so flatten them as static aux data — a jitted
    # step receiving a Calibration argument traces tr/p but keeps
    # height/width as Python ints.
    def tree_flatten(self):
        return (self.tr, self.p), (self.height, self.width)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])


def project_points(points: jnp.ndarray, calib: Calibration,
                   backend: str | None = None):
    """Project LiDAR points to pixel coordinates.

    Args:
      points: (N, 3) LiDAR-frame points.
      calib: calibration.
      backend: ops backend ("ref" / "pallas" / None = resolve).

    Returns:
      uv: (N, 2) float pixel coordinates.
      depth: (N,) camera-frame depth.
      visible: (N,) bool — in front of the camera and inside the image.
    """
    uv, depth, visible, _ = ops.point_proj(points, calib.tr, calib.p,
                                           calib.height, calib.width,
                                           backend=backend)
    return uv, depth, visible


def project_and_label(points: jnp.ndarray, label_img: jnp.ndarray,
                      calib: Calibration,
                      backend: str | None = None) -> jnp.ndarray:
    """Fused projection + visibility + flat-index + label gather.

    The transformation hot path: one registered op computes the composed
    calibration matmul, perspective divide, bounds test, and the flat
    ``v*W+u`` gather index (the Pallas backend fuses all of it per point
    tile); the instance-id gather itself stays an XLA gather.

    Returns (N,) int32 instance labels (0 = background / invisible).
    """
    _, _, visible, flat = ops.point_proj(points, calib.tr, calib.p,
                                         calib.height, calib.width,
                                         backend=backend)
    return ops.label_points(flat, visible, label_img)


def label_points(uv: jnp.ndarray, visible: jnp.ndarray,
                 label_img: jnp.ndarray) -> jnp.ndarray:
    """Nearest-pixel instance lookup. label_img: (H, W) int32, 0=background.

    Returns (N,) int32 labels, 0 for background/invisible points.
    """
    h, w = label_img.shape
    ui = jnp.clip(jnp.round(uv[:, 0]).astype(jnp.int32), 0, w - 1)
    vi = jnp.clip(jnp.round(uv[:, 1]).astype(jnp.int32), 0, h - 1)
    lab = label_img[vi, ui]
    return jnp.where(visible, lab, 0)


def masks_to_label_image(masks: jnp.ndarray) -> jnp.ndarray:
    """Squeeze stacked instance masks (O, H, W) bool into an id image (H, W).

    Later (higher-index) masks win overlaps, matching a front-to-back
    compositing where the 2D model outputs are ordered by confidence.
    """
    o = masks.shape[0]
    ids = jnp.arange(1, o + 1, dtype=jnp.int32)[:, None, None]
    stacked = jnp.where(masks, ids, 0)
    return jnp.max(stacked, axis=0).astype(jnp.int32)


def build_clusters(points: jnp.ndarray, labels: jnp.ndarray, max_obj: int,
                   pts_per_obj: int):
    """Compact labeled points into fixed per-object buffers.

    Slot ``j`` holds the points labeled ``j + 1``, in index order: its
    first ``min(count, P)`` rows are the lowest-indexed members, and every
    row beyond ``valid`` is zero. Labels outside ``1..O`` belong to no slot.

    Args:
      points: (N, 3).
      labels: (N,) int32 instance ids (0 = background).
      max_obj: O, number of object slots.
      pts_per_obj: P, buffer size per object.

    Returns:
      clusters: (O, min(P, N), 3) point buffers (zeros beyond valid).
      valid: (O, min(P, N)) bool masks.
      counts: (O,) number of points per object (possibly > P before capping).
    """
    return _build_clusters(points, labels, max_obj, pts_per_obj,
                           _packed_key_fits(labels.shape[0], max_obj))


def _packed_key_fits(n: int, max_obj: int) -> bool:
    """Whether slot key and point index share one non-negative int32."""
    return (max_obj + 1) << (n - 1).bit_length() < 2 ** 31


def _build_clusters(points, labels, max_obj, pts_per_obj, packed: bool):
    # One sort per stream by slot key puts each slot's members in one
    # contiguous run, in index order; the background key O + 1 sorts last.
    # Packed, the index in the low bits breaks ties, so one operand sorts
    # with no stability flag.
    n = labels.shape[0]
    ib = (n - 1).bit_length()
    key = jnp.where((labels >= 1) & (labels <= max_obj), labels,
                    max_obj + 1).astype(jnp.int32)
    iota = jnp.arange(n, dtype=jnp.int32)
    if packed:
        order = jax.lax.sort((key << ib) | iota, is_stable=False)
        order = order & ((1 << ib) - 1)
    else:
        _, order = jax.lax.sort((key, iota), num_keys=1, is_stable=True)

    obj_ids = jnp.arange(1, max_obj + 1, dtype=jnp.int32)
    counts = jnp.sum(labels[None, :] == obj_ids[:, None], axis=1)
    starts = jnp.cumsum(counts) - counts
    k = jnp.arange(min(pts_per_obj, n), dtype=jnp.int32)
    idx = order[jnp.clip(starts[:, None] + k, 0, n - 1)]
    valid = k < counts[:, None]
    clusters = jnp.where(valid[..., None], points[idx], 0.0)
    return clusters, valid, counts
