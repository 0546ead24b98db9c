"""A configuration's chip layout: ``"mesh": {"streams": M}`` serves the
cell through the sharded fleet step on a ``streams`` mesh of M chips, and
a layout that does not fit the cell is refused before any tape is
recorded. On four virtual CPU devices (the flag must precede JAX's start,
hence a subprocess) a mesh-4 cell is correct, and its drives are the
unsharded engine's on the same tapes, bit for bit."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time

import pytest

from bench import harness
from conftest import ROOT, make_root


@pytest.mark.parametrize("streams,mesh,chips,match", [
    (8, 4, 1, r"over 4 chip\(s\), the cell asks for 1"),
    (8, None, 4, r"over 1 chip\(s\), the cell asks for 4"),
    (6, 4, 4, "6 streams do not divide over a mesh of 4 chips"),
], ids=["mesh4-on-1-chip", "no-mesh-on-4-chips", "6-streams-over-4"])
def test_a_layout_that_does_not_fit_the_cell_is_refused(
        tmp_path, monkeypatch, streams, mesh, chips, match):
    root = make_root(tmp_path, streams=streams, mesh=mesh, chips=chips)

    def no_tapes(*_):
        raise AssertionError("tapes recorded for a refused layout")
    monkeypatch.setattr(harness, "record_tapes", no_tapes)
    with pytest.raises(harness.HarnessError, match=match):
        harness.run("tiny.pair", 1, 1.0, False, time.perf_counter(),
                    check_device=False, root=root)


@pytest.mark.parametrize("mesh", [{"streams": 4, "data": 1}, 4],
                         ids=["two-axes", "a-number"])
def test_a_mesh_other_than_the_stream_axis_is_refused(tmp_path, mesh):
    root = make_root(tmp_path, streams=8, mesh=4, chips=4)
    path = root / "bench" / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    cfg["mesh"] = mesh
    path.write_text(json.dumps(cfg))
    with pytest.raises(harness.HarnessError, match="shards the stream axis"):
        harness.Cell("tiny.pair", root)


# The kernels' instructions of the sharded fleet step, as the TPU's
# compiler gave them for a described v5e:2x2 at 64 streams (16 a chip;
# operand layouts and backend configs cut).
SHARDED_STEP = """HloModule jit__stream_step, is_scheduled=true
  %iou2d.1 = f32[16,128,128]{2,1,0:T(8,128)S(1)} custom-call(%select_maximum_fusion, %pad.71), custom_call_target="tpu_custom_call", metadata={op_name="jit(_stream_step)/shard_map/vmap(associate)/jit(iou2d)/pallas_call" stack_frame_id=95}
  %point_proj.1 = (f32[16,3,120832]{2,1,0:T(4,128)}, s32[16,2,120832]{2,1,0:T(2,128)S(1)}) custom-call(%pad_bitcast_fusion, %fusion.510), custom_call_target="tpu_custom_call", metadata={op_name="jit(_stream_step)/shard_map/vmap(project)/jit(point_proj)/pallas_call" stack_frame_id=216}
  %ransac_score.1 = s32[16,12,128,1]{3,2,1,0:T(8,128)S(1)} custom-call(%bitcast.1715, %copy.4029, %pad.73, %copy.4030), custom_call_target="tpu_custom_call", metadata={op_name="jit(_stream_step)/shard_map/vmap(ransac)/jit(ransac_score)/pallas_call" stack_frame_id=293}
"""


def test_kernel_names_are_found_under_the_shard_map_scope():
    assert harness.program_names(SHARDED_STEP) == {
        "step_module": "jit__stream_step",
        "kernel_ops": {"iou2d": ["iou2d.1"], "point_proj": ["point_proj.1"],
                       "ransac_score": ["ransac_score.1"]}}


FOUR_DEVICES = textwrap.dedent("""
    import json, pathlib, sys, time
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bench import harness
    from conftest import make_root

    tmp = pathlib.Path(sys.argv[1])
    mesh_root = make_root(tmp / "mesh4", streams=8, mesh=4, chips=4)
    flat_root = make_root(tmp / "flat", streams=8)
    res = harness.run("tiny.pair", 2 ** 31 + 9, 1.0, False,
                      time.perf_counter(), check_device=False,
                      root=mesh_root, log=lambda m: None)
    cell4 = harness.Cell("tiny.pair", mesh_root)
    cell1 = harness.Cell("tiny.pair", flat_root)
    tapes = harness.record_tapes(cell4, 2 ** 31 + 5)
    e4 = harness.build_engine(cell4, 2 ** 31 + 5, tapes)
    e1 = harness.build_engine(cell1, 2 ** 31 + 5, tapes)
    d4 = harness.run_drive(e4, cell4.rounds).out
    d1 = harness.run_drive(e1, cell1.rounds).out
    want = NamedSharding(e4.mesh, P("streams"))
    args = jax.tree.leaves(harness.step_args(e4, cell4.rounds)[:3])
    print(json.dumps({
        "devices": len(jax.devices()),
        "correct": res["correct"], "compared": res["compared"],
        "device_count": res["device"]["count"],
        "n_shards": e4.n_shards, "axes": list(e4.mesh.axis_names),
        "bitwise": {c: d4[c].tobytes() == d1[c].tobytes() for c in d4},
        "args_sharded": all(a.sharding.is_equivalent_to(want, a.ndim)
                            for a in args),
        "module": harness.step_program_names(e4, cell4.rounds)
        ["step_module"]}))
""")


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    """One subprocess on four virtual CPU devices; its findings."""
    tmp = tmp_path_factory.mktemp("layout")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT), str(ROOT / "src"),
                    str(pathlib.Path(__file__).parent)]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"
                          ).strip())
    out = subprocess.run([sys.executable, "-c", FOUR_DEVICES, str(tmp)],
                         env=env, capture_output=True, text=True,
                         timeout=600, cwd=str(ROOT))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_a_mesh4_cell_is_correct_on_four_devices(four_devices):
    assert four_devices["devices"] == four_devices["device_count"] == 4
    assert four_devices["correct"] is True, four_devices["compared"]
    assert all(c["value"] == 0 for c in four_devices["compared"].values())


def test_the_engine_steps_on_a_four_device_streams_mesh(four_devices):
    assert four_devices["n_shards"] == 4
    assert four_devices["axes"] == ["streams"]


def test_sharded_drives_match_the_unsharded_bit_for_bit(four_devices):
    assert four_devices["bitwise"] == {c: True for c in harness.Drive.COLS}


def test_the_sharded_step_is_lowered_on_the_engines_shardings(four_devices):
    assert four_devices["args_sharded"] is True
    assert four_devices["module"].startswith("jit_")
