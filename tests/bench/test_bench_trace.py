"""The reduction from a profiler trace to per-layer numbers: on a small
hand-made event list with known answers, and on a trace recorded on a
TPU v5e at smoke size (2 vehicles, 3-round drives, 2 traced drives)."""
import json
import pathlib

import pytest

from bench import trace

RECORDED = pathlib.Path(__file__).resolve().parent / "data" / \
    "small_trace.json.gz"


def _events():
    ops = [[0, trace.OPS_LINE, "%sort.1 = (s32[4]) sort(s32[4] %a)", 10, 20],
           [0, trace.OPS_LINE, "%fusion.2 = f32[4] fusion(f32[4] %b)", 25, 15],
           [0, trace.OPS_LINE, "%fusion.2 = f32[4] fusion(f32[4] %b)", 70, 10],
           [0, trace.MODULES_LINE, "jit__stream_step(7)", 10, 30],
           [0, trace.MODULES_LINE, "jit__stream_step(7)", 70, 10]]
    host = [["bench/drive", 0, 100], ["moby/fleet/dispatch", 0, 8],
            ["moby/fleet/fetch", 8, 40], ["moby/fleet/fetch", 85, 10]]
    return {"device": ops, "host": host}


def test_busy_idle_and_attribution_of_a_known_trace():
    r = trace.reduce(_events())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)      # [10, 40) and [70, 80)
    gaps = dict(r["idle_gaps"])
    # idle [0, 10), [40, 70), [80, 100): [0, 8) in the dispatch, [8, 10),
    # [40, 48) and [85, 95) in a fetch, the rest unspanned
    assert gaps["moby/fleet/dispatch"] == pytest.approx(8e-9)
    assert gaps["moby/fleet/fetch"] == pytest.approx(20e-9)
    assert gaps["host loop, unspanned"] == pytest.approx(32e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["ops"]["sort.1"] == [1, pytest.approx(20e-9)]
    assert r["ops"]["fusion.2"] == [2, pytest.approx(25e-9)]
    assert r["device_ops"][0][0].startswith("%fusion.2 = ")
    assert r["modules"]["jit__stream_step(7)"] == [2, pytest.approx(40e-9)]


def _chip(events, chip):
    """``events`` with one chip's device events alone, as chip 0."""
    return {"host": events["host"],
            "device": [[0] + e[1:] for e in events["device"] if e[0] == chip]}


def test_idle_gaps_are_averaged_over_every_chip():
    ev = _events()
    # Chip 1 runs [0, 50) and [85, 100): it idles [50, 85), outside any
    # fleet span, where chip 0 idles mostly in the dispatch and the fetch.
    ev["device"] += [
        [1, trace.OPS_LINE, "%sort.1 = (s32[4]) sort(s32[4] %a)", 0, 50],
        [1, trace.OPS_LINE, "%fusion.2 = f32[4] fusion(f32[4] %b)", 85, 15],
        [1, trace.MODULES_LINE, "jit__stream_step(7)", 0, 50]]
    both = trace.reduce(ev, n_chips=2)
    one = [trace.reduce(_chip(ev, c)) for c in (0, 1)]
    assert dict(one[1]["idle_gaps"]) == {
        "host loop, unspanned": pytest.approx(35e-9)}
    gaps = dict(both["idle_gaps"])
    labels = set(dict(one[0]["idle_gaps"])) | set(dict(one[1]["idle_gaps"]))
    assert set(gaps) == labels
    for label in labels:
        assert gaps[label] == pytest.approx(
            sum(dict(r["idle_gaps"]).get(label, 0.0) for r in one) / 2)
    assert both["busy_s"] == pytest.approx(
        (one[0]["busy_s"] + one[1]["busy_s"]) / 2)
    assert sum(gaps.values()) == pytest.approx(
        both["window_s"] - both["busy_s"])


def test_a_trace_without_drives_is_refused():
    ev = _events()
    ev["host"] = [h for h in ev["host"] if h[0] != "bench/drive"]
    with pytest.raises(ValueError, match="no bench/drive"):
        trace.reduce(ev)


def test_op_names_are_hlo_instruction_names():
    assert trace.op_name("%vmap_jit_point_proj__.1 = (f32[2]) custom-call()") \
        == "vmap_jit_point_proj__.1"
    assert trace.op_name("fusion.3") == "fusion.3"


def _recorded():
    import gzip
    d = json.loads(gzip.decompress(RECORDED.read_bytes()))
    lines = (trace.OPS_LINE, trace.MODULES_LINE)
    device = [[c, lines[ln], d["names"][i], float(t), float(dur)]
              for c, ln, i, t, dur in d["rows"]]
    host = [[n, float(t), float(dur)] for n, t, dur in d["host"]]
    return d, {"device": device, "host": host}


def test_one_chip_idle_gaps_are_the_single_chip_reduction():
    """On one chip the gaps are what the reduction that attributed chip 0
    alone gave for the recorded trace, to the last bit."""
    _, events = _recorded()
    assert trace.reduce(events)["idle_gaps"] == [
        ["host loop, unspanned", 0.05676424799999995],
        ["moby/fleet/fetch", 0.007300180999999989],
        ["moby/fleet/dispatch", 0.00027352699999999965]]


def test_recorded_chip_trace_reduces_to_the_per_layer_metrics():
    import types

    import numpy as np

    from bench import harness
    d, events = _recorded()
    r = trace.reduce(events)
    rounds = len(d["rounds"])
    assert r["window_s"] == pytest.approx(d["wall"], rel=1e-3)
    assert 0 < r["busy_s"] < r["window_s"]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    assert len(r["device_ops"]) == trace.TOP
    step = [v for k, v in r["modules"].items()
            if k.startswith(d["program"]["step_module"] + "(")]
    assert step and step[0][0] == rounds
    for kernel in ("point_proj", "ransac_score"):
        for name in d["program"]["kernel_ops"][kernel]:
            assert r["ops"][name][0] == rounds
    drive = types.SimpleNamespace(rounds=np.asarray(d["rounds"]),
                                  fetch_s=np.asarray(d["fetch"]))
    # The recorded drive stands for the untraced window too.
    ctx = {"trace": r, "drives": [drive], "window_s": d["wall"],
           "device_kind": d["device_kind"], "rounds_traced": rounds,
           "shapes": {"streams": 2, "n_points": 1024, "max_obj": 6,
                      "pts_per_obj": 256, "ransac_iters": 30},
           **d["program"]}
    root = pathlib.Path(__file__).resolve().parents[2]
    got = {m: harness.load_reader(root, m)(ctx) for m in (
        "host_ms_per_round", "fetch_wait_ms_per_round",
        "step_device_ms_per_round", "point_proj_roofline",
        "ransac_score_roofline", "device_idle_pct")}
    assert 0 < got["device_idle_pct"] < 100
    assert got["device_idle_pct"] == pytest.approx(
        100 * (1 - r["busy_s"] / d["wall"]))
    assert 0 < got["point_proj_roofline"] <= 100
    assert 0 < got["ransac_score_roofline"] <= 100
    assert got["step_device_ms_per_round"] == pytest.approx(
        1e3 * step[0][1] / rounds)
    assert got["host_ms_per_round"] + got["fetch_wait_ms_per_round"] \
        == pytest.approx(1e3 * d["wall"] / rounds)


def test_readers_count_the_rounds_whose_step_the_trace_holds():
    """A trace that lost a round's device events gives per-round device
    times over the rounds it holds, not over the rounds driven."""
    from bench import harness
    d, events = _recorded()
    step = d["program"]["step_module"]
    rounds = len(d["rounds"])
    assert harness.rounds_in_trace(trace.reduce(events), step, 1) == rounds
    runs = sorted((e for e in events["device"]
                   if e[1] == trace.MODULES_LINE
                   and e[2].startswith(step + "(")), key=lambda e: e[3])
    lost = dict(events, device=[e for e in events["device"]
                                if e[3] < runs[-1][3]])
    part = trace.reduce(lost)
    assert harness.rounds_in_trace(part, step, 1) == rounds - 1
    root = pathlib.Path(__file__).resolve().parents[2]
    got = harness.load_reader(root, "step_device_ms_per_round")(
        {"trace": part, "step_module": step, "rounds_traced": rounds - 1})
    assert got == pytest.approx(
        1e3 * sum(e[4] for e in runs[:-1]) * 1e-9 / (rounds - 1), rel=1e-12)


@pytest.mark.parametrize("modules,chips,want", [
    ({"jit__stream_step(7)": [8, 1.0], "jit_add(3)": [5, 0.1]}, 2, 4),
    ({"jit_add(3)": [5, 0.1]}, 1, 0)], ids=["two-chips", "no-step"])
def test_rounds_in_trace_counts_step_runs_per_chip(modules, chips, want):
    from bench import harness
    assert harness.rounds_in_trace({"modules": modules}, "jit__stream_step",
                                   chips) == want
