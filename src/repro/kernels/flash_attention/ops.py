"""Public wrapper: layout + padding + interpret switch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import (TK, TQ,
                                                           flash_attention_pallas)
from repro.ops import registry


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, interpret: bool | None = None
                    ) -> jnp.ndarray:
    """q: (B, H, SQ, hd); k/v: (B, KV, SK, hd) -> (B, H, SQ, hd)."""
    if interpret is None:  # platform default: compiled on a TPU
        interpret = registry.default_interpret()
    b, h, sq, hd = q.shape
    kv, sk = k.shape[1], k.shape[2]
    pq = (-sq) % TQ
    pk = (-sk) % TK
    qf = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))).reshape(
        b * h, sq + pq, hd)
    kf = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))).reshape(
        b * kv, sk + pk, hd)
    vf = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))).reshape(
        b * kv, sk + pk, hd)
    out = flash_attention_pallas(qf, kf, vf, n_q_heads=h, n_kv_heads=kv,
                                 causal=causal, sk_valid=sk,
                                 interpret=interpret)
    return out.reshape(b, h, sq + pq, hd)[:, :, :sq]
