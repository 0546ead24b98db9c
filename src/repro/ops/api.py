"""Typed entry points for the registered hot ops.

Each function resolves its backend through :mod:`repro.ops.registry` and
dispatches to either the pure-jnp reference or the Pallas kernel wrapper
(whose interpret switch defaults from the platform). These are the
ONLY sanctioned call sites for ``repro.kernels.*.ops`` outside tests —
consumers (core, models, serving, fleet, benchmarks) import from here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import ops as _dec_ops
from repro.kernels.decode_attention import ref as _dec_ref
from repro.kernels.flash_attention import ops as _fa_ops
from repro.kernels.flash_attention import ref as _fa_ref
from repro.kernels.iou2d import ops as _iou_ops
from repro.kernels.iou2d import ref as _iou_ref
from repro.kernels.pillar_scatter import ops as _ps_ops
from repro.kernels.pillar_scatter import ref as _ps_ref
from repro.kernels.point_proj import ops as _pp_ops
from repro.kernels.point_proj import ref as _pp_ref
from repro.kernels.ransac_score import ops as _rs_ops
from repro.kernels.ransac_score import ref as _rs_ref
from repro.ops import registry


# ---------------------------------------------------------------------------
# Differentiable pallas wrappers. ``pl.pallas_call`` has no VJP rule, but
# training paths (LM/detector train steps) differentiate through attention
# and pillar scatter — so those pallas registrations carry a custom VJP
# whose backward pass is the ref implementation's (recompute-style, the
# standard flash-attention treatment). Forward stays on the kernel.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _diff_flash(q, k, v, causal):
    return _fa_ops.flash_attention(q, k, v, causal=causal)


def _flash_fwd(q, k, v, causal):
    return _diff_flash(q, k, v, causal), (q, k, v)


def _flash_bwd(causal, res, g):
    # Recompute-style backward through the *chunked* online-softmax path
    # (per-q-block checkpointing), never the dense ref: differentiating the
    # dense (Sq, Sk) score matrix would materialize exactly what the flash
    # kernel exists to avoid at long sequence lengths.
    from repro.models.layers import _chunked_attention  # deferred: no cycle

    q, k, v = res

    def chunked(q, k, v):
        b, h, sq, hd = q.shape
        kv = k.shape[1]
        qg = q.transpose(0, 2, 1, 3).reshape(b, sq, kv, h // kv, hd)
        out = _chunked_attention(qg, k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3), causal)
        return out.reshape(b, sq, h, v.shape[-1]).transpose(0, 2, 1, 3)

    _, vjp = jax.vjp(chunked, q, k, v)
    return vjp(g)


_diff_flash.defvjp(_flash_fwd, _flash_bwd)


@jax.custom_vjp
def _diff_decode(q, ck, cv, pos):
    return _dec_ops.decode_attention(q, ck, cv, pos)


def _decode_fwd(q, ck, cv, pos):
    return _diff_decode(q, ck, cv, pos), (q, ck, cv, pos)


def _decode_bwd(res, g):
    # jax.vjp yields the correct float0 cotangent for the int positions.
    _, vjp = jax.vjp(_dec_ref.decode_attention_ref, *res)
    return vjp(g)


_diff_decode.defvjp(_decode_fwd, _decode_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _diff_pillar_scatter(f, idx, val, g):
    return _ps_ops.pillar_scatter(f, idx, val, g)


def _scatter_fwd(f, idx, val, g):
    return _diff_pillar_scatter(f, idx, val, g), (f, idx, val)


def _scatter_bwd(g_pillars, res, ct):
    f, idx, val = res
    _, vjp = jax.vjp(lambda a, b, c: _ps_ref.pillar_scatter_ref(
        a, b, c, g_pillars), f, idx, val)
    return vjp(ct)


_diff_pillar_scatter.defvjp(_scatter_fwd, _scatter_bwd)


# ---------------------------------------------------------------------------
# Registrations: name -> (ref, pallas).
# ---------------------------------------------------------------------------

registry.register_op(
    "point_proj",
    ref=lambda pts, tr, p, h, w: _pp_ref.point_proj_ref(pts, tr, p, h, w),
    pallas=lambda pts, tr, p, h, w: _pp_ops.point_proj(pts, tr, p, h, w))

registry.register_op(
    "iou2d",
    ref=lambda a, b: _iou_ref.iou2d_ref(a, b),
    pallas=lambda a, b: _iou_ops.iou2d(a, b))

registry.register_op(
    "ransac_score",
    ref=lambda pts, val, nrm, off, th: _rs_ref.ransac_score_ref(
        pts, val, nrm, off, th),
    pallas=lambda pts, val, nrm, off, th: _rs_ops.ransac_score(
        pts, val, nrm, off, float(th)))

registry.register_op(
    "pillar_scatter",
    ref=lambda f, idx, val, g: _ps_ref.pillar_scatter_ref(f, idx, val, g),
    pallas=_diff_pillar_scatter)

registry.register_op(
    "flash_attention",
    ref=lambda q, k, v, causal: _fa_ref.flash_attention_ref(
        q, k, v, causal=causal),
    pallas=_diff_flash)

registry.register_op(
    "decode_attention",
    ref=lambda q, ck, cv, pos: _dec_ref.decode_attention_ref(q, ck, cv, pos),
    pallas=_diff_decode)


# ---------------------------------------------------------------------------
# Public wrappers
# ---------------------------------------------------------------------------


def point_proj(points: jnp.ndarray, tr: jnp.ndarray, p: jnp.ndarray,
               height: int, width: int, *, backend: str | None = None):
    """Fused LiDAR->pixel projection.

    (N,3) points + (3,4) Tr/P calibration -> (uv (N,2), depth (N,),
    visible (N,) bool, flat (N,) int32 gather index).
    """
    return registry.get_impl("point_proj", backend)(points, tr, p,
                                                    height, width)


def label_points(flat: jnp.ndarray, visible: jnp.ndarray,
                 label_img: jnp.ndarray) -> jnp.ndarray:
    """Instance-id gather at the projected pixels (backend-independent:
    the XLA gather is already optimal on every platform)."""
    return _pp_ops.label_points(flat, visible, label_img)


def iou2d(a: jnp.ndarray, b: jnp.ndarray, *,
          backend: str | None = None) -> jnp.ndarray:
    """Pairwise axis-aligned IoU: (N,4) x (M,4) -> (N,M)."""
    return registry.get_impl("iou2d", backend)(a, b)


def ransac_score(points: jnp.ndarray, valid: jnp.ndarray,
                 normals: jnp.ndarray, offsets: jnp.ndarray, thresh: float,
                 *, backend: str | None = None) -> jnp.ndarray:
    """Plane-hypothesis inlier counts: (O,P,3),(O,P),(O,K,3),(O,K) ->
    (O,K) int32."""
    return registry.get_impl("ransac_score", backend)(points, valid, normals,
                                                      offsets, thresh)


def pillar_scatter(feats: jnp.ndarray, pillar_idx: jnp.ndarray,
                   valid: jnp.ndarray, n_pillars: int, *,
                   backend: str | None = None) -> jnp.ndarray:
    """Scatter-max (N,C) point features into a (G,C) pillar grid."""
    return registry.get_impl("pillar_scatter", backend)(feats, pillar_idx,
                                                        valid, n_pillars)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, *,
                    backend: str | None = None) -> jnp.ndarray:
    """q: (B,H,SQ,hd); k/v: (B,KV,SK,hd) -> (B,H,SQ,hd). Requires the
    value head dim to equal the qk head dim (use the ref path for MLA)."""
    return registry.get_impl("flash_attention", backend)(q, k, v, causal)


def decode_attention(q: jnp.ndarray, cache_k: jnp.ndarray,
                     cache_v: jnp.ndarray, cache_pos: jnp.ndarray, *,
                     backend: str | None = None) -> jnp.ndarray:
    """Single-token decode: q (B,H,hd) over caches (B,KV,S,hd), attending
    positions [0, cache_pos) per request -> (B,H,hd)."""
    return registry.get_impl("decode_attention", backend)(q, cache_k,
                                                          cache_v, cache_pos)
