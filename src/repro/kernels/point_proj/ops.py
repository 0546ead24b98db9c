"""Public wrapper: compose calibration, pad, run kernel, label lookup."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.point_proj.point_proj import TILE_N, point_proj_pallas
from repro.ops import registry


def compose_calibration(tr: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    """(3,4) lidar->cam and (3,4) cam->pixel into one (3,4) lidar->pixel."""
    tr44 = jnp.concatenate(
        [tr, jnp.array([[0.0, 0.0, 0.0, 1.0]], tr.dtype)], axis=0)
    return jnp.matmul(p, tr44, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("height", "width", "interpret"))
def point_proj(points: jnp.ndarray, tr: jnp.ndarray, p: jnp.ndarray,
               height: int, width: int, interpret: bool | None = None):
    """(N,3) -> (uv (N,2), depth (N,), visible (N,) bool, flat (N,) int32)."""
    if interpret is None:  # platform default: compiled on a TPU
        interpret = registry.default_interpret()
    n = points.shape[0]
    pad = (-n) % TILE_N
    pts_t = jnp.pad(points.astype(jnp.float32), ((0, pad), (0, 0))).T
    mat = compose_calibration(tr.astype(jnp.float32), p.astype(jnp.float32))
    geo, idx = point_proj_pallas(pts_t, mat, height, width, interpret)
    return (geo[:2, :n].T, geo[2, :n], idx[0, :n].astype(bool), idx[1, :n])


def label_points(flat: jnp.ndarray, visible: jnp.ndarray,
                 label_img: jnp.ndarray) -> jnp.ndarray:
    """Gather instance ids at projected pixels (outside the kernel; XLA
    gather). label_img: (H, W) int32."""
    lab = jnp.take(label_img.reshape(-1), flat, axis=0)
    return jnp.where(visible, lab, 0)
