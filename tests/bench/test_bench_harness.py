"""The harness on the CPU: it finds a cell's files by name, its rounds add
up to each drive's wall time, it names the device, counts the compiles in
the window, and refuses to run without a TPU or without the program."""
import json
import time

import numpy as np
import pytest

from bench import harness
from conftest import make_root


def _run(root, cell, trace=False):
    logs = []
    res = harness.run(cell, 2 ** 31 + 9, 1.0, trace, time.perf_counter(),
                      check_device=False, root=root, log=logs.append)
    return res, logs


def test_a_new_cell_is_found_by_name_from_files_alone(tmp_path):
    root = make_root(tmp_path, config="tmpcfg", traffic="tmpmix",
                     metric="tmp_metric")
    res, logs = _run(root, "tmpcfg.tmpmix")
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "stream_frames_per_s",
                                   "round_p95_ms"}
    assert list(res)[-1] == "compared"
    cell = harness.Cell("tmpcfg.tmpmix", root)
    assert cell.traffic["name"] == "tmpmix"
    assert cell.config["name"] == "tmpcfg"
    cell.per_layer = [m for m in cell.per_layer if m["name"] == "tmp_metric"]
    got = harness.read_per_layer(cell, {"trace": {}, "drives": []})
    assert got["tmp_metric"] == {"value": 7.0, "unit": "ms"}


def test_result_names_the_device_and_the_window_compiles_nothing(tiny_root):
    res, logs = _run(tiny_root, "tiny.pair")
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(res["device"])
    assert res["device"]["platform"] == "cpu"
    assert "backend compiles inside the window: 0 " in "\n".join(logs)
    assert res["compared"]["compiles_in_window"]["value"] == 0.0
    json.dumps(res)


def test_round_latencies_add_up_to_the_drive(tiny_root):
    cell = harness.Cell("tiny.pair", tiny_root)
    tapes = harness.record_tapes(cell, 4)
    engine = harness.build_engine(cell, 4, tapes)
    harness.run_drive(engine, cell.rounds)
    d = harness.run_drive(engine, cell.rounds)
    assert len(d.rounds) == cell.rounds
    assert (d.rounds > 0).all()
    assert np.sum(d.rounds) == pytest.approx(d.wall_s, rel=1e-12)


def test_round_latencies_from_spans():
    spans = [{"name": "fleet/dispatch", "t0": 0.5, "dur": 0.1},
             {"name": "fleet/fetch", "t0": 0.6, "dur": 0.2},
             {"name": "fleet/dispatch", "t0": 0.8, "dur": 0.1},
             {"name": "fleet/fetch", "t0": 0.9, "dur": 0.3}]
    lat = harness.round_latencies(spans, 2.0)
    np.testing.assert_allclose(lat, [1.6, 0.4])
    assert lat.sum() == pytest.approx(2.0)


def test_no_tpu_no_result(tiny_root, capsys):
    with pytest.raises(harness.HarnessError, match="needs a TPU"):
        harness.run("tiny.pair", 1, 1.0, False, time.perf_counter(),
                    root=tiny_root)
    from bench import run as run_main
    assert run_main.main(["--workload", "kitti-hdl64.fleet16", "--seed",
                          "1", "--seconds", "1"]) == 1
    assert capsys.readouterr().out == ""


def test_no_program_no_result(tiny_root):
    (tiny_root / "src").unlink()
    with pytest.raises(harness.HarnessError, match="no program"):
        harness.run("tiny.pair", 1, 1.0, False, time.perf_counter(),
                    check_device=False, root=tiny_root)


def test_unknown_cell_is_refused(tiny_root):
    with pytest.raises(harness.HarnessError, match="unknown workload"):
        harness.Cell("nope.nope", tiny_root)


@pytest.mark.parametrize("section,key,value", [
    ("configs", "precision", "high"), ("traffic", "loop", "open")])
def test_a_setting_the_harness_does_not_run_is_refused(tiny_root, section,
                                                      key, value):
    path = next((tiny_root / "bench" / section).glob("*.json"))
    spec = json.loads(path.read_text())
    spec[key] = value
    path.write_text(json.dumps(spec))
    with pytest.raises(harness.HarnessError, match=f"{key} {value!r}"):
        harness.Cell("tiny.pair", tiny_root)


def test_every_seed_serves_the_same_scenes_in_its_own_order(tiny_root):
    cell = harness.Cell("tiny.pair", tiny_root)
    a = harness.record_tapes(cell, 2 ** 31 + 1)
    b = harness.record_tapes(cell, 2 ** 31 + 1)
    c = harness.record_tapes(cell, 3)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.points, y.points)
    key = [t.points.tobytes() for t in a]
    assert sorted(key) == sorted(t.points.tobytes() for t in c)
