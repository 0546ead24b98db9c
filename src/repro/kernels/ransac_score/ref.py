"""Pure-jnp oracle for RANSAC plane-hypothesis inlier counting."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ransac_score_ref(points: jnp.ndarray, valid: jnp.ndarray,
                     normals: jnp.ndarray, offsets: jnp.ndarray,
                     thresh: float) -> jnp.ndarray:
    """Count inliers per (object, hypothesis).

    Args:
      points:  (O, P, 3) cluster point buffers.
      valid:   (O, P) bool.
      normals: (O, K, 3) plane normals.
      offsets: (O, K) plane offsets d (plane: n.x + d = 0).
      thresh:  inlier distance threshold.

    Returns:
      (O, K) int32 inlier counts.
    """
    dist = jnp.abs(jnp.einsum("opc,okc->opk", points, normals,
                              precision=jax.lax.Precision.HIGHEST)
                   + offsets[:, None, :])
    inl = (dist < thresh) & valid[:, :, None]
    return jnp.sum(inl, axis=1).astype(jnp.int32)
