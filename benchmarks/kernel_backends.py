"""Per-op and end-to-end latency of the unified ops backends.

For every registered hot op: wall time of the ``ref`` (pure jnp) vs the
``pallas`` implementation on representative Moby shapes, plus the full
``transform_step`` frame latency under each backend. On this CPU host the
pallas column runs in interpret mode (correctness/parity path, expected
slower); on a TPU it is the compiled kernel — the rows are the
before/after ledger for per-kernel tuning work.

Next to each measured wall time the benchmark reports the *modeled*
latency of the same op on the registered device profiles (napkin
FLOP/byte counts through ``profiles.roofline_latency``) — what the run
*should* cost on the TX2 edge part and the TPU v5e kernel target, so the
wall-time rows measured on this host have a hardware yardstick.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, small_scene, timed
from repro import ops
from repro.core import projection, transform
from repro.data import scenes
from repro.launch.compile_cache import use_compile_cache
from repro.runtime import profiles
from repro.serving.common import nominal_transform_time

_BACKENDS = ("ref", "pallas")
_PROFILES = ("jetson_tx2", "tpu_v5e")


def _op_costs(n, o, p, k):
    """(flops, bytes) napkin estimates for the _per_op benchmark shapes
    (fp32 host arrays; counts follow each ref implementation)."""
    return {
        # 3x4 + 3x4 projection matmuls + bounds tests per point.
        "point_proj": (n * (2 * 12 + 2 * 12 + 10), n * (3 + 4) * 4),
        # 64x64 pairwise intersection/union.
        "iou2d": (64 * 64 * 16, (64 * 4 * 2 + 64 * 64) * 4),
        # (O,K) hypotheses x P points: plane distance + threshold count.
        "ransac_score": (o * k * p * 8, (o * p * 4 + o * k * 4) * 4),
        # One pass over the points, max-combine into the grid.
        "pillar_scatter": (n * 32 * 2, (n * 33 + 1024 * 32) * 4),
        # 2 matmuls over the (Sq, Sk) score matrix.
        "flash_attention": (4 * 8 * 512 * 512 * 64,
                            (3 * 8 * 512 * 64 + 8 * 512 * 512) * 4),
        # Single-token decode over the cache.
        "decode_attention": (4 * 4 * 8 * 1024 * 64,
                             4 * (2 * 4 * 1024 * 64 + 8 * 64) * 4),
    }


def _per_op(rng):
    n, o, p, k = 8192, 12, 256, 30
    pts = jnp.asarray(rng.normal(0, 20, (n, 3)).astype(np.float32))
    tr = jnp.asarray(rng.normal(size=(3, 4)).astype(np.float32))
    pm = jnp.asarray(rng.normal(size=(3, 4)).astype(np.float32))
    cl_pts = jnp.asarray(rng.normal(0, 5, (o, p, 3)).astype(np.float32))
    cl_val = jnp.asarray(rng.uniform(size=(o, p)) < 0.8)
    nrm = rng.normal(size=(o, k, 3))
    nrm /= np.linalg.norm(nrm, axis=-1, keepdims=True)
    nrm = jnp.asarray(nrm.astype(np.float32))
    off = jnp.asarray(rng.normal(0, 3, (o, k)).astype(np.float32))
    bx = jnp.asarray(rng.uniform(0, 100, (64, 4)).astype(np.float32))
    feats = jnp.asarray(rng.normal(size=(n, 32)).astype(np.float32))
    pid = jnp.asarray(rng.integers(0, 1024, n).astype(np.int32))
    pval = jnp.asarray(rng.uniform(size=n) < 0.9)
    q = jnp.asarray(rng.normal(size=(1, 8, 512, 64)).astype(np.float32))
    kv = jnp.asarray(rng.normal(size=(1, 4, 512, 64)).astype(np.float32))
    dq = jnp.asarray(rng.normal(size=(4, 8, 64)).astype(np.float32))
    dkv = jnp.asarray(rng.normal(size=(4, 4, 1024, 64)).astype(np.float32))
    dpos = jnp.asarray(rng.integers(1, 1024, 4).astype(np.int32))

    def cases(be):
        return {
            "point_proj": (jax.jit(
                lambda x: ops.point_proj(x, tr, pm, 128, 416, backend=be)),
                (pts,)),
            "iou2d": (jax.jit(lambda a: ops.iou2d(a, a, backend=be)), (bx,)),
            "ransac_score": (jax.jit(
                lambda c, v, nn, oo: ops.ransac_score(c, v, nn, oo, 0.1,
                                                      backend=be)),
                (cl_pts, cl_val, nrm, off)),
            "pillar_scatter": (jax.jit(
                lambda f, i, v: ops.pillar_scatter(f, i, v, 1024,
                                                   backend=be)),
                (feats, pid, pval)),
            "flash_attention": (jax.jit(
                lambda a, b, c: ops.flash_attention(a, b, c, True,
                                                    backend=be)),
                (q, kv, kv)),
            "decode_attention": (jax.jit(
                lambda a, b, c, d: ops.decode_attention(a, b, c, d,
                                                        backend=be)),
                (dq, dkv, dkv, dpos)),
        }
    for be in _BACKENDS:
        for name, (fn, args) in cases(be).items():
            t, _ = timed(fn, *args, warmup=2, iters=5)
            emit(f"kernel_backends/{name}/{be}_ms", round(t * 1e3, 3))
    # Modeled per-op latency on the registered device profiles (the
    # hardware yardstick next to this host's wall times).
    costs = _op_costs(n, o, p, k)
    for name, (flops, bytes_moved) in costs.items():
        for prof in _PROFILES:
            t = profiles.roofline_latency(profiles.get_profile(prof),
                                          flops, bytes_moved)
            emit(f"kernel_backends/{name}/modeled_{prof}_ms",
                 round(t * 1e3, 4),
                 f"roofline: {flops / 1e6:.1f} MFLOP, "
                 f"{bytes_moved / 1e6:.2f} MB")


def _end_to_end():
    cfg = small_scene(seed=3)
    stream = scenes.SceneStream(cfg, seed=3)
    frames = list(stream.frames(2))
    calib = projection.Calibration(tr=jnp.asarray(stream.tr),
                                   p=jnp.asarray(stream.p),
                                   height=cfg.img_h, width=cfg.img_w)
    rng = np.random.default_rng(3)
    b2, v2, li = scenes.oracle_detect_2d(frames[1], rng)
    pts = jnp.asarray(frames[1].points)
    args = (jnp.asarray(b2), jnp.asarray(v2), jnp.asarray(li))

    step = jax.jit(transform.transform_step, static_argnames=("params",))
    for be in _BACKENDS:
        params = transform.TransformParams(backend=be)
        state = transform.init_state(2 * cfg.max_obj, jax.random.key(0))
        fn = lambda st: step(st, pts, *args, calib, params=params)
        t, _ = timed(fn, state, warmup=2, iters=5)
        emit(f"kernel_backends/e2e_transform_step/{be}_ms",
             round(t * 1e3, 2),
             "full 2D->3D frame transformation")
    # Modeled end-to-end frame cost from each device profile's component
    # model (what the engines charge as on-board time, see
    # runtime.profiles.component_times).
    for prof in _PROFILES:
        comp = profiles.component_times(prof)
        t = nominal_transform_time(comp, use_tba=True, use_fos=True)
        emit(f"kernel_backends/e2e_transform_step/modeled_{prof}_ms",
             round(t * 1e3, 2),
             "profile component model (Fig. 15)")


def run():
    _per_op(np.random.default_rng(0))
    _end_to_end()


if __name__ == "__main__":
    use_compile_cache()
    run()
