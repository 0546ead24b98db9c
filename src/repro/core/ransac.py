"""RANSAC plane fitting for 3D bounding box estimation (§3.3).

Moby finds the dominant visible surface of each point cluster by sampling
three points, forming a plane, and keeping the plane with the most inliers.
The paper uses 30 iterations (Fig. 16a/b sensitivity).

The inlier-scoring step is the compute hot spot (30.1 % of on-board latency
in Fig. 15 together with box estimation): for K hypotheses over P points it
is a (K,3)x(3,P) matmul + compare + reduce, which maps directly onto the
MXU. Scoring dispatches through the ops registry (``repro.ops``): the
``ref`` backend is the jnp einsum below, the ``pallas`` backend is
``repro.kernels.ransac_score``. This module keeps the sampling/selection
logic, which is cheap and backend-independent.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import ops


class RansacParams(NamedTuple):
    num_iters: int = 30          # paper default (Fig. 16)
    inlier_thresh: float = 0.10  # metres from plane
    # Reject near-horizontal planes (top/bottom surfaces). The paper notes
    # (§3.3 fn 2) top surfaces are rarely found and can be handled by
    # removing them and re-running; we instead fold that into scoring.
    max_abs_nz: float = 0.7


class PlaneFit(NamedTuple):
    normal: jnp.ndarray     # (3,) unit normal
    offset: jnp.ndarray     # scalar d in n.x + d = 0
    inliers: jnp.ndarray    # (P,) bool
    num_inliers: jnp.ndarray
    ok: jnp.ndarray         # bool: a valid plane was found


def _sample_triplets(key: jax.Array, valid: jnp.ndarray, k: int) -> jnp.ndarray:
    """Sample (k, 3) indices of valid points (with replacement across triplets).

    Valid points are compacted to the front via argsort so uniform integers
    over [0, n_valid) index real points. Degenerate clusters (<3 points)
    produce index 0 triplets which later score 0.
    """
    p = valid.shape[0]
    order = jnp.argsort(~valid)  # valid points first, stable
    n_valid = jnp.maximum(jnp.sum(valid), 1)
    u = jax.random.randint(key, (k, 3), 0, n_valid)
    return order[u]


def plane_from_triplets(points: jnp.ndarray, tri: jnp.ndarray):
    """Planes through point triplets. points (P,3), tri (K,3) -> normals (K,3), d (K,)."""
    p0 = points[tri[:, 0]]
    p1 = points[tri[:, 1]]
    p2 = points[tri[:, 2]]
    n = jnp.cross(p1 - p0, p2 - p0)
    norm = jnp.linalg.norm(n, axis=-1, keepdims=True)
    ok = norm[:, 0] > 1e-8
    n = n / jnp.where(norm < 1e-8, 1.0, norm)
    d = -jnp.sum(n * p0, axis=-1)
    return n, d, ok


def ransac_plane(key: jax.Array, points: jnp.ndarray, valid: jnp.ndarray,
                 params: RansacParams = RansacParams(),
                 backend: str | None = None) -> PlaneFit:
    """Fit the dominant (near-vertical) plane of one cluster.

    Args:
      key: PRNG key.
      points: (P, 3) buffer.
      valid: (P,) mask.
      params: RANSAC parameters.
      backend: ops backend for inlier scoring ("ref" / "pallas" / None).

    Returns: PlaneFit with the best plane and its inlier mask.
    """
    fit = ransac_planes(key, points[None], valid[None], params,
                        backend=backend, _presplit=True)
    return jax.tree_util.tree_map(lambda x: x[0], fit)


def ransac_planes(key: jax.Array, points: jnp.ndarray, valid: jnp.ndarray,
                  params: RansacParams = RansacParams(),
                  backend: str | None = None,
                  _presplit: bool = False) -> PlaneFit:
    """Vectorized over objects: points (O, P, 3), valid (O, P).

    Sampling and selection vmap over the object axis; inlier scoring is
    one batched (O, K, 3) x (O, 3, P) contraction dispatched through the
    ops registry, so the Pallas kernel sees all objects at once.
    """
    o = points.shape[0]
    keys = key[None] if _presplit else jax.random.split(key, o)
    tri = jax.vmap(lambda k, v: _sample_triplets(k, v, params.num_iters))(
        keys, valid)                                          # (O, K, 3)
    normals, offsets, tri_ok = jax.vmap(plane_from_triplets)(points, tri)
    counts = ops.ransac_score(points, valid, normals, offsets,
                              params.inlier_thresh, backend=backend)
    vertical = jnp.abs(normals[..., 2]) <= params.max_abs_nz
    counts = jnp.where(tri_ok & vertical, counts, 0)          # (O, K)
    best = jnp.argmax(counts, axis=1)
    n_best = jnp.take_along_axis(normals, best[:, None, None], axis=1)[:, 0]
    d_best = jnp.take_along_axis(offsets, best[:, None], axis=1)[:, 0]
    dist = jnp.abs(jnp.einsum("opc,oc->op", points, n_best,
                              precision=jax.lax.Precision.HIGHEST)
                   + d_best[:, None])
    inliers = (dist < params.inlier_thresh) & valid
    num = jnp.take_along_axis(counts, best[:, None], axis=1)[:, 0]
    ok = num >= 3
    return PlaneFit(normal=n_best, offset=d_best, inliers=inliers,
                    num_inliers=num, ok=ok)
