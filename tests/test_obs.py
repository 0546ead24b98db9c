"""repro.obs tier-1 tests (ISSUE 6).

Contract under test:

* **free when off** — engine outputs with ``obs=None`` are *bitwise*
  identical to an observed run, on both ops backends (the observability
  layer must never perturb the model);
* **metrics** — registry semantics (get-or-create, label checking,
  counter monotonicity, cumulative histogram buckets), Prometheus text
  exposition grammar, and the per-device p95 gauge agreeing exactly with
  ``RunReport.device_p95_latency``;
* **trace** — Chrome trace-event schema (ph/ts/dur/pid/tid, metadata
  names), per-GPU cloud lanes that never overlap and whose durations sum
  to the pool's ``busy_s_g`` accounting, stream lanes reconstructable
  with no observer attached;
* **audit** — exactly one decision row per stream-frame carrying every
  policy input, JSONL/CSV export, and the scan-mode refusal (audit needs
  the orchestrated loop).
"""
import json
import re

import jax
import numpy as np
import pytest

from repro import api, obs
from repro.obs import trace as trace_lib

jax.config.update("jax_platform_name", "cpu")

FRAMES = 8


def run_observed(preset="smoke", frames=FRAMES, *, n_streams=None,
                 cfg=None, **scn_kw):
    if n_streams is not None:
        scn_kw["n_streams"] = n_streams
    cfg = cfg or obs.ObsConfig(metrics=True, trace=True, audit=True,
                               registry=obs.MetricsRegistry())
    sess = api.Session(api.scenario(preset, seed=0, **scn_kw), obs=cfg)
    return sess.run(frames)


# ---------------------------------------------------------------------------
# free when off
# ---------------------------------------------------------------------------


class TestDisabledIsFree:
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_single_stream_bitwise_parity(self, backend):
        rep = run_observed(backend=backend)
        off = api.Session(api.scenario("smoke", seed=0,
                                       backend=backend)).run(FRAMES)
        np.testing.assert_array_equal(rep.kind, off.kind)
        np.testing.assert_array_equal(rep.latency_s, off.latency_s)
        np.testing.assert_array_equal(rep.onboard_s, off.onboard_s)
        np.testing.assert_array_equal(rep.f1, off.f1)

    def test_fleet_bitwise_parity(self):
        rep = run_observed(n_streams=4)
        off = api.Session(api.scenario("smoke", seed=0,
                                       n_streams=4)).run(FRAMES)
        np.testing.assert_array_equal(rep.kind, off.kind)
        np.testing.assert_array_equal(rep.latency_s, off.latency_s)
        np.testing.assert_array_equal(rep.f1, off.f1)

    def test_disabled_config_attaches_nothing(self):
        rep = api.Session(api.scenario("smoke", seed=0),
                          obs=obs.ObsConfig()).run(4)
        assert rep.obs is None
        assert obs.make_observer(None) is None
        assert obs.make_observer(obs.ObsConfig()) is None


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


# One Prometheus text-format line: name{labels} value.
_PROM_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r' (-?\d+(\.\d+)?([eE][+-]?\d+)?|\+Inf|-Inf|NaN)$')


class TestMetrics:
    def test_registry_get_or_create_and_type_clash(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("x_total", "help", labels=("a",))
        assert reg.counter("x_total", labels=("a",)) is c
        with pytest.raises(ValueError):
            reg.gauge("x_total", labels=("a",))
        with pytest.raises(ValueError):
            reg.counter("x_total", labels=("b",))

    def test_counter_semantics(self):
        c = obs.Counter("n_total", labels=("k",))
        c.inc(k="a")
        c.inc(2, k="a")
        assert c.value(k="a") == 3
        with pytest.raises(ValueError):
            c.inc(-1, k="a")
        with pytest.raises(ValueError):
            c.inc(k="a", wrong="label")

    def test_histogram_cumulative_buckets(self):
        h = obs.Histogram("lat", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        lines = h.expose()
        assert 'lat_bucket{le="0.1"} 1' in lines
        assert 'lat_bucket{le="1"} 2' in lines
        assert 'lat_bucket{le="+Inf"} 3' in lines
        assert h.count() == 3 and h.sum() == pytest.approx(5.55)

    def test_exposition_grammar(self):
        rep = run_observed(n_streams=2)
        for line in rep.to_prometheus().splitlines():
            if line.startswith("# HELP ") or line.startswith("# TYPE "):
                continue
            assert _PROM_SAMPLE.match(line), f"bad exposition line: {line!r}"

    def test_device_p95_gauge_matches_report(self):
        rep = run_observed(n_streams=2)
        g = rep.metrics_registry().get("moby_device_p95_latency_seconds")
        for dev, p95 in rep.device_p95_latency().items():
            assert g.value(scenario=rep.scenario, policy=rep.policy,
                           device=dev) == p95

    def test_frames_total_partition(self):
        rep = run_observed(n_streams=2)
        c = rep.metrics_registry().get("moby_frames_total")
        total = sum(v for _, v in c.samples())
        assert total == rep.n_streams * rep.n_frames

    def test_json_export_round_trips(self):
        rep = run_observed()
        doc = json.loads(rep.metrics_registry().to_json())
        names = {m["name"] for m in doc["metrics"]}
        assert "moby_frames_total" in names
        assert "moby_frame_latency_seconds" in names


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------


class TestTrace:
    def test_chrome_schema(self, tmp_path):
        rep = run_observed(n_streams=4)
        path = tmp_path / "t.json"
        doc = rep.to_trace(path)
        assert json.loads(path.read_text()) == doc
        evs = doc["traceEvents"]
        assert evs, "empty trace"
        for e in evs:
            assert e["ph"] in ("X", "M")
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
            if e["ph"] == "X":
                assert e["ts"] >= 0 and e["dur"] >= 0
        # every track/lane used by a span is named via metadata
        named = {(e["pid"], e["tid"]) for e in evs
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        used = {(e["pid"], e["tid"]) for e in evs if e["ph"] == "X"}
        assert used <= named

    def test_stream_lane_count_and_wall_recurrence(self):
        rep = run_observed(n_streams=2)
        evs = rep.to_trace()["traceEvents"]
        spans = [e for e in evs if e["ph"] == "X"
                 and e["pid"] == trace_lib.PID_STREAMS]
        assert len(spans) == rep.n_streams * rep.n_frames
        # frame spans in one lane start in order, spaced >= frame_dt
        for s in range(rep.n_streams):
            ts = [e["ts"] for e in spans if e["tid"] == s]
            diffs = np.diff(ts)
            assert (diffs >= rep.frame_dt * 1e6 - 1).all()

    def test_gpu_lanes_nonoverlapping_and_conserve_busy(self):
        rep = run_observed("fleet-16-congested", frames=6)
        evs = rep.to_trace()["traceEvents"]
        gpu = [e for e in evs if e["ph"] == "X"
               and e["pid"] == trace_lib.PID_CLOUD]
        assert gpu, "no cloud GPU spans in a fleet run with anchors"
        by_lane = {}
        for e in gpu:
            by_lane.setdefault(e["tid"], []).append(e)
        for lane in by_lane.values():
            lane.sort(key=lambda e: e["ts"])
            for a, b in zip(lane, lane[1:]):
                assert a["ts"] + a["dur"] <= b["ts"] + 1e-3, \
                    "busy intervals overlap within one GPU lane"
        busy = sum(e["dur"] for e in gpu) / 1e6
        assert busy == pytest.approx(sum(rep.obs.busy_s_g), rel=1e-6)

    def test_trace_without_observer(self):
        rep = api.Session(api.scenario("smoke", seed=0)).run(FRAMES)
        assert rep.obs is None
        doc = rep.to_trace()
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(spans) == rep.n_frames
        assert all(e["pid"] == trace_lib.PID_STREAMS for e in spans)

    def test_measured_host_spans_present(self):
        rep = run_observed()
        evs = rep.to_trace()["traceEvents"]
        host = [e for e in evs if e["ph"] == "X"
                and e["pid"] == trace_lib.PID_HOST]
        names = {e["name"] for e in host}
        assert "frame_stats_fetch" in names
        assert "transform_step" in names


# ---------------------------------------------------------------------------
# audit
# ---------------------------------------------------------------------------


class TestAudit:
    def test_one_row_per_stream_frame(self):
        rep = run_observed(n_streams=3)
        assert len(rep.obs.audit) == rep.n_streams * rep.n_frames
        rows = rep.obs.audit.rows
        keys = {(r["stream"], r["frame"]) for r in rows}
        assert len(keys) == len(rows), "duplicate (stream, frame) rows"
        for r in rows:
            assert set(obs.AUDIT_FIELDS) <= set(r)
            assert r["kind"] in ("anchor", "test", "transform")

    def test_audit_kinds_match_report(self):
        rep = run_observed(n_streams=2)
        for r in rep.obs.audit.rows:
            assert r["kind"] == str(rep.kind[r["stream"], r["frame"]])

    def test_jsonl_and_csv_export(self, tmp_path):
        rep = run_observed()
        jl = tmp_path / "a.jsonl"
        rep.to_audit(jl)
        rows = [json.loads(x) for x in jl.read_text().splitlines()]
        assert len(rows) == rep.n_frames
        cv = tmp_path / "a.csv"
        rep.to_audit(cv)
        header = cv.read_text().splitlines()[0].split(",")
        assert set(obs.AUDIT_FIELDS) <= set(header)

    def test_unaudited_report_raises(self):
        rep = api.Session(api.scenario("smoke", seed=0)).run(4)
        with pytest.raises(ValueError, match="audit"):
            rep.to_audit()

    def test_scan_mode_refuses_audit(self):
        sess = api.Session(api.scenario("smoke", seed=0, n_streams=2),
                           obs=obs.ObsConfig(audit=True))
        with pytest.raises(ValueError, match="scan"):
            sess.run(4, scan=True)

    def test_scan_mode_metrics_and_trace_work(self):
        cfg = obs.ObsConfig(metrics=True, trace=True,
                            registry=obs.MetricsRegistry())
        sess = api.Session(api.scenario("smoke", seed=0, n_streams=2),
                           obs=cfg)
        rep = sess.run(4, scan=True)
        assert rep.obs is not None
        spans = [e for e in rep.to_trace()["traceEvents"]
                 if e["ph"] == "X" and e["pid"] == trace_lib.PID_STREAMS]
        assert len(spans) == rep.n_streams * rep.n_frames
        assert "moby_frames_total" in rep.metrics_registry().names()


# ---------------------------------------------------------------------------
# export plumbing
# ---------------------------------------------------------------------------


class TestExports:
    def test_session_export_paths_with_placeholders(self, tmp_path):
        cfg = obs.ObsConfig(
            trace_path=str(tmp_path / "t-{scenario}-{policy}.json"),
            metrics_path=str(tmp_path / "m" / "metrics.prom"),
            audit_path=str(tmp_path / "a-{scenario}.csv"),
            registry=obs.MetricsRegistry())
        api.Session(api.scenario("smoke", seed=0), obs=cfg).run(4)
        assert (tmp_path / "t-smoke-fos.json").exists()
        assert (tmp_path / "m" / "metrics.prom").exists()
        assert (tmp_path / "a-smoke.csv").exists()

    def test_baseline_mode_observed(self):
        cfg = obs.ObsConfig(metrics=True, audit=True,
                            registry=obs.MetricsRegistry())
        rep = api.Session(api.scenario("smoke", seed=0,
                                       mode="cloud_only"), obs=cfg).run(4)
        assert len(rep.obs.audit) == rep.n_frames
        assert all(r["kind"] == "cloud_only" for r in rep.obs.audit.rows)


if __name__ == "__main__":
    pytest.main([__file__, "-v", "-x"])
