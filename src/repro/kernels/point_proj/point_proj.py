"""Pallas kernel: fused LiDAR->pixel projection (Moby hot spot #3, Fig. 15).

Fuses the two 4x3 calibration matmuls, the perspective divide, the bounds
test, and the flat gather-index computation over N-point blocks, with the
composed 4x4 (lidar->pixel) matrix precomputed by ops.py and kept in VMEM.
The label-image gather itself stays outside the kernel (XLA gather is
efficient on TPU; per-lane dynamic VMEM gathers are not).

Layout: points are passed transposed (3, N) so the block compute is a
(4, 3) x (3, TN) MXU matmul. Every output is a lane-dense 2-D array with
N on the lanes: ``geo`` (3, N) f32 holds rows [u, v, depth] and ``idx``
(2, N) int32 holds rows [visible, flat]. Their leading dims equal the full
array dims, so the blocks satisfy Mosaic's (8, 128) rule alone and with a
leading stream axis added by ``vmap``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 1024


def _kernel(pts_ref, mat_ref, geo_ref, idx_ref, *, height, width):
    pts = pts_ref[...]                       # (3, TN)
    mat = mat_ref[...]                       # (3, 4) composed projection
    prod = jax.lax.dot_general(mat[:, :3], pts, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    pix = prod + mat[:, 3:4]                 # (3, TN)
    depth = pix[2:3]
    w = jnp.where(jnp.abs(depth) < 1e-6, 1e-6, depth)
    u = pix[0:1] / w
    v = pix[1:2] / w
    vis = (depth > 0.1) & (u >= 0) & (u < width) & (v >= 0) & (v < height)
    ui = jnp.clip(jnp.round(u).astype(jnp.int32), 0, width - 1)
    vi = jnp.clip(jnp.round(v).astype(jnp.int32), 0, height - 1)
    geo_ref[...] = jnp.concatenate([u, v, depth], axis=0)
    idx_ref[...] = jnp.concatenate([vis.astype(jnp.int32), vi * width + ui],
                                   axis=0)


def point_proj_pallas(points_t: jnp.ndarray, mat: jnp.ndarray, height: int,
                      width: int, interpret: bool = False):
    """points_t: (3, N) with N a multiple of TILE_N; mat: (3, 4) composed
    lidar->pixel matrix. Returns (geo (3, N) f32 rows [u, v, depth],
    idx (2, N) int32 rows [visible, flat])."""
    n = points_t.shape[1]
    kernel = functools.partial(_kernel, height=height, width=width)
    return pl.pallas_call(
        kernel,
        grid=(n // TILE_N,),
        in_specs=[
            pl.BlockSpec((3, TILE_N), lambda i: (0, i)),
            pl.BlockSpec((3, 4), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((3, TILE_N), lambda i: (0, i)),
            pl.BlockSpec((2, TILE_N), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((3, n), jnp.float32),
            jax.ShapeDtypeStruct((2, n), jnp.int32),
        ],
        interpret=interpret,
    )(points_t, mat)
