"""Pallas kernel: RANSAC plane-hypothesis inlier counting.

The hot loop of Moby's 3D box estimation (Fig. 15: ~30% of on-board time):
for every object cluster, score K candidate planes against P points.
Per grid step (one object) the kernel computes an MXU-shaped
(K, 3) x (3, P) matmul, the |.|<tau compare, and the P-reduction — all in
VMEM. Layouts: P and K padded to lane multiples (128) by ops.py. Every
operand carries the object on a leading axis and keeps its last two dims
whole (valid (O, 1, P), offsets and counts (O, K, 1)), so each block meets
Mosaic's (8, 128) rule alone and with a leading stream axis from ``vmap``.

VMEM budget per step (P=256, K=128): points 3*256*4 = 3 KB, normals
128*3*4 = 1.5 KB, dist 128*256*4 = 128 KB — comfortably under v5e's 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(pts_ref, valid_ref, nrm_ref, off_ref, out_ref, *, thresh):
    # pts: (1, 3, P); valid: (1, 1, P); nrm: (1, K, 3); off: (1, K, 1).
    pts = pts_ref[0]                       # (3, P)
    nrm = nrm_ref[0]                       # (K, 3)
    off = off_ref[0]                       # (K, 1)
    dist = jnp.abs(
        jax.lax.dot_general(nrm, pts, (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
        + off)                             # (K, P)
    inl = (dist < thresh) & (valid_ref[0] > 0)
    out_ref[0] = jnp.sum(inl.astype(jnp.int32), axis=1, keepdims=True)


def ransac_score_pallas(points_t: jnp.ndarray, valid: jnp.ndarray,
                        normals: jnp.ndarray, offsets: jnp.ndarray,
                        thresh: float, interpret: bool = False) -> jnp.ndarray:
    """points_t: (O, 3, P); valid: (O, 1, P) int32; normals: (O, K, 3);
    offsets: (O, K, 1). Returns (O, K, 1) int32 counts. P, K should be
    padded to 128 multiples by the caller (ops.py)."""
    o, _, p = points_t.shape
    k = normals.shape[1]
    return pl.pallas_call(
        functools.partial(_kernel, thresh=thresh),
        grid=(o,),
        in_specs=[
            pl.BlockSpec((1, 3, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, k, 3), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, k, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, k, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((o, k, 1), jnp.int32),
        interpret=interpret,
    )(points_t, valid, normals, offsets)
