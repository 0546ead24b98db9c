"""Public wrapper: padding/layout + interpret switch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ransac_score.ransac_score import ransac_score_pallas
from repro.ops import registry

_LANE = 128


def _pad_to(x, axis, mult, value=0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("thresh", "interpret"))
def ransac_score(points: jnp.ndarray, valid: jnp.ndarray,
                 normals: jnp.ndarray, offsets: jnp.ndarray,
                 thresh: float, interpret: bool | None = None) -> jnp.ndarray:
    """(O,P,3),(O,P),(O,K,3),(O,K) -> (O,K) int32 inlier counts."""
    if interpret is None:  # platform default: compiled on a TPU
        interpret = registry.default_interpret()
    k = normals.shape[1]
    pts_t = _pad_to(jnp.swapaxes(points, 1, 2), 2, _LANE)      # (O, 3, P')
    val = _pad_to(valid.astype(jnp.int32), 1, _LANE)[:, None]  # (O, 1, P')
    nrm = _pad_to(normals, 1, _LANE)                           # (O, K', 3)
    # Padded hypotheses get a huge offset -> zero inliers.
    off = _pad_to(offsets, 1, _LANE, value=1e9)[..., None]     # (O, K', 1)
    out = ransac_score_pallas(pts_t, val, nrm, off, thresh, interpret)
    return out[:, :k, 0]
