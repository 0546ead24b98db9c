"""Public wrapper: pad to tiles, run the kernel, slice back."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.iou2d.iou2d import TILE_M, TILE_N, iou2d_pallas
from repro.ops import registry


@functools.partial(jax.jit, static_argnames=("interpret",))
def iou2d(a: jnp.ndarray, b: jnp.ndarray, interpret: bool | None = None
          ) -> jnp.ndarray:
    """(N,4) x (M,4) -> (N,M) IoU. Padded boxes are degenerate -> IoU 0."""
    if interpret is None:  # platform default: compiled on a TPU
        interpret = registry.default_interpret()
    n, m = a.shape[0], b.shape[0]
    pn = (-n) % TILE_N
    pm = (-m) % TILE_M
    ap = jnp.pad(a.astype(jnp.float32), ((0, pn), (0, 0)))
    bp = jnp.pad(b.astype(jnp.float32), ((0, pm), (0, 0)))
    out = iou2d_pallas(ap, bp, interpret=interpret)
    return out[:n, :m]
