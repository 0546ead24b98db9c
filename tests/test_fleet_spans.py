"""The fleet round's host spans and the device step's named stages.

Observed (``ObsConfig(trace=True)``), ``FleetEngine.run`` records one
``fleet/round`` span per round with its five phases as children (every
host->device put of a round inside its ``fleet/inputs``), a
``fleet/prologue`` and a ``fleet/epilogue`` per run, each on one
process-wide clock (``t_ns``) with the run's id. The compiled step names
every stage of the device work in its entry instructions' ``op_name``.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro import api, obs
from repro.core import transform
from repro.data import scenes
from repro.fleet import FleetEngine
from repro.fleet import step as step_lib

jax.config.update("jax_platform_name", "cpu")

FRAMES = 4
PHASES = ("fleet/inputs", "fleet/telemetry", "fleet/dispatch",
          "fleet/fetch", "fleet/contention")
STAGES = (transform.STAGE_ASSOCIATE, transform.STAGE_PROJECT,
          transform.STAGE_CLUSTERS, transform.STAGE_FILTRATION,
          transform.STAGE_RANSAC, transform.STAGE_BOXES,
          step_lib.STAGE_SCHEDULER, step_lib.STAGE_SCORE)


def _cfg():
    return scenes.SceneConfig(max_obj=6, n_points=512, img_h=32, img_w=104,
                              mean_objects=3, density_scale=2500.0)


@pytest.fixture(scope="module")
def engine():
    return FleetEngine(_cfg(), "oracle", n_streams=2, seed=3,
                       obs=obs.ObsConfig(trace=True))


@pytest.fixture(scope="module")
def drives(engine):
    """Two consecutive observed drives' span records."""
    return [engine.run(FRAMES).obs.measured for _ in range(2)]


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _end(s):
    return s["t_ns"] + s["dur"] * 1e9


def test_one_round_span_per_round_with_its_phases_inside_in_order(drives):
    for spans in drives:
        rounds = _named(spans, "fleet/round")
        assert [r["frame"] for r in rounds] == list(range(FRAMES))
        assert all(r["parent"] is None for r in rounds)
        for r in rounds:
            kids = [s for s in spans if s["parent"] == r["frame"]]
            assert [k["name"] for k in kids] == list(PHASES)
            assert all(k["frame"] == r["frame"] for k in kids)
            # inside the round, one after the other (1 us of float slack)
            edges = [r["t_ns"]] + [x for k in kids
                                   for x in (k["t_ns"], _end(k))] \
                + [_end(r)]
            assert all(a <= b + 1e3 for a, b in zip(edges, edges[1:]))


def test_each_run_has_one_prologue_and_one_epilogue_and_one_id(drives):
    ids = []
    for spans in drives:
        pro, epi = _named(spans, "fleet/prologue"), \
            _named(spans, "fleet/epilogue")
        assert len(pro) == 1 and len(epi) == 1
        assert pro[0]["parent"] is None and epi[0]["parent"] is None
        rounds = _named(spans, "fleet/round")
        assert _end(pro[0]) <= rounds[0]["t_ns"] + 1e3
        assert _end(rounds[-1]) <= epi[0]["t_ns"] + 1e3
        assert pro[0]["t0"] == 0.0
        assert len({s["run"] for s in spans}) == 1
        ids.append(spans[0]["run"])
    assert ids[0] != ids[1]


def test_inputs_span_counts_the_bytes_and_puts_of_the_round(engine, drives):
    stack = engine._stacked(FRAMES)
    for spans in drives:
        for s in _named(spans, "fleet/inputs"):
            inp = engine._frame_inputs(stack, s["frame"])
            # the tape's frame, then the (S,) test arrivals and the int32
            # round index
            assert s["bytes"] == sum(int(a.nbytes) for a in inp) \
                + engine.n_streams + 4
            assert s["puts"] == len(inp) + 2


def test_on_one_device_the_round_puts_ten_arrays_on_one_chip(drives):
    for spans in drives:
        for s in _named(spans, "fleet/inputs"):
            assert (s["puts"], s["chips"]) == (10, 1)
            assert s["bytes_per_chip"] == s["bytes"]
        assert all(s["chips"] == 1 for s in _named(spans, "fleet/fetch"))


def test_every_put_of_a_round_happens_inside_its_inputs_span(engine,
                                                            monkeypatch):
    open_spans, seen = [], []
    measured_span = obs.Observer.measured_span
    put = FleetEngine._put

    @contextlib.contextmanager
    def tracked(self, name, *a, **kw):
        open_spans.append(name)
        try:
            with measured_span(self, name, *a, **kw) as extra:
                yield extra
        finally:
            open_spans.pop()

    def recorded(self, a, spec):
        seen.append(list(open_spans))
        return put(self, a, spec)

    monkeypatch.setattr(obs.Observer, "measured_span", tracked)
    monkeypatch.setattr(FleetEngine, "_put", recorded)
    engine.run(FRAMES)
    assert seen == [["fleet/round", "fleet/inputs"]] * (10 * FRAMES)


def test_input_bytes_counter_sums_the_rounds_puts_per_shard():
    reg = obs.MetricsRegistry()
    report = FleetEngine(_cfg(), "oracle", n_streams=2, seed=3,
                         obs=obs.ObsConfig(metrics=True, registry=reg)
                         ).run(FRAMES)
    report.obs.flush_metrics(report)
    inputs = _named(report.obs.measured, "fleet/inputs")
    c = reg.counter("moby_fleet_input_bytes_total", labels=("shard",))
    assert [k for k, _ in c.samples()] == [("0",)]
    assert c.value(shard=0) == sum(s["bytes"] for s in inputs)


def test_contention_span_counts_the_round_senders(drives):
    for spans in drives:
        senders = [s["senders"] for s in _named(spans, "fleet/contention")]
        assert all(0 <= n <= 2 for n in senders)
        assert senders[0] == 2    # round 0 anchors every stream


def test_clock_runs_on_across_consecutive_drives(drives):
    first, second = drives
    assert max(_end(s) for s in first) <= min(s["t_ns"] for s in second)
    for spans in drives:
        starts = [s["t_ns"] for s in _named(spans, "fleet/round")]
        assert starts == sorted(starts)


def test_profiler_annotations_are_prefixed_once(monkeypatch):
    seen = []

    class Recorder:
        def __init__(self, name, **kw):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    cfg = obs.ObsConfig(trace=True)
    api.Session(api.scenario("smoke", seed=0), obs=cfg).run(2)
    FleetEngine(_cfg(), "oracle", n_streams=2, obs=cfg).run(2)
    assert {"moby/anchor_step", "moby/transform_step",
            "moby/frame_stats_fetch", "moby/fleet/round",
            "moby/fleet/inputs"} <= set(seen)
    assert not [n for n in seen if n.startswith("moby/moby/")]


def test_every_stage_names_entry_instructions_of_the_compiled_step(engine):
    text = engine._step.lower(
        engine._init_state(),
        engine._frame_inputs(engine._stacked(FRAMES), 0),
        jnp.zeros((engine.n_streams,), bool), jnp.int32(0)).compile() \
        .as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    op_names = re.findall(r'op_name="([^"]*)"', entry)
    for stage in STAGES:
        scoped = re.compile(r"(^|/)(vmap\()*%s\)*(/|$)" % stage)
        assert any(scoped.search(n) for n in op_names), stage

