"""Pure-jnp oracle for fused point projection."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def point_proj_ref(points: jnp.ndarray, tr: jnp.ndarray, p: jnp.ndarray,
                   height: int, width: int):
    """Project LiDAR points into pixel space.

    Args:
      points: (N, 3).
      tr: (3, 4) LiDAR->camera; p: (3, 4) camera->pixel.

    Returns:
      uv: (N, 2) float32 pixel coords, depth: (N,), visible: (N,) bool,
      flat_idx: (N,) int32 clamped v*W+u index for the label-image gather.
    """
    n = points.shape[0]
    hom = jnp.concatenate([points, jnp.ones((n, 1), points.dtype)], axis=-1)
    cam = jnp.matmul(hom, tr.T, precision=jax.lax.Precision.HIGHEST)
    camh = jnp.concatenate([cam, jnp.ones((n, 1), points.dtype)], axis=-1)
    pix = jnp.matmul(camh, p.T, precision=jax.lax.Precision.HIGHEST)
    depth = pix[:, 2]
    w = jnp.where(jnp.abs(depth) < 1e-6, 1e-6, depth)
    uv = pix[:, :2] / w[:, None]
    visible = (depth > 0.1) & (uv[:, 0] >= 0) & (uv[:, 0] < width) \
        & (uv[:, 1] >= 0) & (uv[:, 1] < height)
    ui = jnp.clip(jnp.round(uv[:, 0]).astype(jnp.int32), 0, width - 1)
    vi = jnp.clip(jnp.round(uv[:, 1]).astype(jnp.int32), 0, height - 1)
    flat = vi * width + ui
    return uv, depth, visible, flat
