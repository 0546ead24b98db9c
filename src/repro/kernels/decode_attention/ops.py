"""Public wrapper for decode attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    TS, decode_attention_pallas)
from repro.ops import registry


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q: jnp.ndarray, cache_k: jnp.ndarray,
                     cache_v: jnp.ndarray, cache_pos: jnp.ndarray,
                     interpret: bool | None = None) -> jnp.ndarray:
    """q: (B, H, hd); cache_k/v: (B, KV, S, hd); cache_pos: (B,) lengths.

    Returns (B, H, hd) attention output over positions [0, cache_pos).
    """
    if interpret is None:  # platform default: compiled on a TPU
        interpret = registry.default_interpret()
    b, h, hd = q.shape
    kv, s = cache_k.shape[1], cache_k.shape[2]
    g = h // kv
    pad = (-s) % TS
    kf = jnp.pad(cache_k, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        b * kv, s + pad, hd)
    vf = jnp.pad(cache_v, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        b * kv, s + pad, hd)
    qf = q.reshape(b, kv, g, hd).reshape(b * kv, g, hd)
    pos = jnp.repeat(cache_pos.astype(jnp.int32), kv)
    out = decode_attention_pallas(qf, kf, vf, pos, interpret=interpret)
    return out.reshape(b, kv, g, hd).reshape(b, h, hd)
