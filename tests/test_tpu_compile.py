"""Compile the served Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel wrapper with ``interpret=False``
against a ``v5e:2x2`` topology that is described, not attached, and
compiles it with the TPU's compiler. That is where Mosaic refuses a block
layout that interpret mode accepts (e.g. a block whose last two dims break
the (8, 128) tiling rule). Every kernel of the served path is compiled at
two widths, alone and vmapped over a 16-stream fleet, as the fleet step
calls it.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest-xdist
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.iou2d import ops as iou_ops
from repro.kernels.point_proj import ops as pp_ops
from repro.kernels.ransac_score import ops as rs_ops

FLEET = 16
MARKER = 'custom_call_target="tpu_custom_call"'

# (N points, image H, image W): the kitti-urban preset and the KITTI
# sensor (HDL-64E sweep, one 1242x375 camera).
PROJ_WIDTHS = {"kitti-urban": (8192, 128, 416),
               "kitti-sensor": (120000, 375, 1242)}
# (objects O, cluster points P, hypotheses K): kitti-urban's RANSAC, and
# a 128-hypothesis sweep.
RANSAC_WIDTHS = {"kitti-urban": (12, 256, 30), "k128": (8, 256, 128)}
# (tracks T, detections D): kitti-urban (max_obj 12) and dense-traffic
# (max_obj 20).
IOU_WIDTHS = {"kitti-urban": (24, 12), "dense-traffic": (40, 20)}


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, args, sharding, batched):
    if batched:
        fn = jax.vmap(fn, in_axes=tuple(0 if b else None for _, _, b in args))
    specs = [jax.ShapeDtypeStruct(((FLEET,) if batched and b else ()) + s, d,
                                  sharding=sharding) for s, d, b in args]
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("batched", [False, True], ids=["alone", "fleet"])
@pytest.mark.parametrize("width", sorted(PROJ_WIDTHS))
def test_point_proj(one_chip, width, batched):
    n, h, w = PROJ_WIDTHS[width]
    hlo = _compile(
        lambda pts, tr, p: pp_ops.point_proj(pts, tr, p, h, w,
                                             interpret=False),
        [((n, 3), jnp.float32, True), ((3, 4), jnp.float32, False),
         ((3, 4), jnp.float32, False)], one_chip, batched)
    assert hlo.count(MARKER) == 1


@pytest.mark.parametrize("batched", [False, True], ids=["alone", "fleet"])
@pytest.mark.parametrize("width", sorted(RANSAC_WIDTHS))
def test_ransac_score(one_chip, width, batched):
    o, p, k = RANSAC_WIDTHS[width]
    hlo = _compile(
        lambda pts, val, nrm, off: rs_ops.ransac_score(
            pts, val, nrm, off, 0.1, interpret=False),
        [((o, p, 3), jnp.float32, True), ((o, p), jnp.bool_, True),
         ((o, k, 3), jnp.float32, True), ((o, k), jnp.float32, True)],
        one_chip, batched)
    assert hlo.count(MARKER) == 1


@pytest.mark.parametrize("batched", [False, True], ids=["alone", "fleet"])
@pytest.mark.parametrize("width", sorted(IOU_WIDTHS))
def test_iou2d(one_chip, width, batched):
    t, d = IOU_WIDTHS[width]
    hlo = _compile(lambda a, b: iou_ops.iou2d(a, b, interpret=False),
                   [((t, 4), jnp.float32, True), ((d, 4), jnp.float32, True)],
                   one_chip, batched)
    assert hlo.count(MARKER) == 1
