"""Public wrapper for pillar scatter-max."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pillar_scatter.pillar_scatter import (TILE_G, TILE_N,
                                                         pillar_scatter_pallas)
from repro.ops import registry


@functools.partial(jax.jit, static_argnames=("n_pillars", "interpret"))
def pillar_scatter(feats: jnp.ndarray, pillar_idx: jnp.ndarray,
                   valid: jnp.ndarray, n_pillars: int,
                   interpret: bool | None = None) -> jnp.ndarray:
    """(N,C) features + (N,) pillar ids -> (G,C) max-pooled pillar grid."""
    if interpret is None:  # platform default: compiled on a TPU
        interpret = registry.default_interpret()
    n, c = feats.shape
    pad_n = (-n) % TILE_N
    pad_g = (-n_pillars) % TILE_G
    f = jnp.pad(feats.astype(jnp.float32), ((0, pad_n), (0, 0)))
    idx = jnp.where(valid, pillar_idx, -1)
    idx = jnp.pad(idx.astype(jnp.int32), (0, pad_n), constant_values=-1)
    out = pillar_scatter_pallas(f, idx, n_pillars + pad_g, interpret)
    return out[:n_pillars]
