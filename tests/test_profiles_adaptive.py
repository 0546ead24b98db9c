"""Profile-driven runtime layer tests (ISSUE 4).

Contract under test:
* the device-profile registry round-trips custom profiles, scales
  ``ComponentTimes`` from the TX2 calibration, and raises KeyError
  listing registered names on unknown devices;
* the ``"auto"`` ops backend resolves per op, deterministically, from a
  pinned measurement table;
* ``SchedulerState`` telemetry flows (observe_telemetry broadcast, EWMA
  maintenance in the post step) and the ``adaptive`` policy orders its
  anchor rate sanely against ``fos`` / ``periodic(k)`` under a drifting
  trace — and its (accuracy, offload-rate) point weakly dominates at
  least one baseline policy in the sweep.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, ops
from repro.core import scheduler
from repro.runtime import profiles
from repro.serving.common import ComponentTimes

jax.config.update("jax_platform_name", "cpu")


class TestProfileRegistry:
    def test_builtin_profiles_resolve(self):
        for name in ("jetson_tx2", "rtx_2080ti", "tpu_v5e"):
            assert profiles.get_profile(name).name == name
        # Aliases and pass-through.
        assert profiles.get_profile("tx2") is profiles.JETSON_TX2
        assert profiles.get_profile(profiles.TPU_V5E) is profiles.TPU_V5E

    def test_register_roundtrip(self):
        orin = dataclasses.replace(profiles.JETSON_TX2, name="orin_test",
                                   peak_flops=5.3e12)
        profiles.register_profile(orin)
        try:
            assert profiles.get_profile("orin_test") is orin
            assert "orin_test" in profiles.list_profiles()
            # A faster edge part models faster inference and components.
            assert profiles.detector_latency("pointpillar", "orin_test") < \
                profiles.detector_latency("pointpillar", "jetson_tx2")
            assert profiles.component_times("orin_test").seg_2d < \
                ComponentTimes().seg_2d
        finally:
            profiles._PROFILES.pop("orin_test")

    def test_unknown_profile_lists_names(self):
        with pytest.raises(KeyError, match="jetson_tx2"):
            profiles.get_profile("does-not-exist")
        with pytest.raises(KeyError, match="registered"):
            api.scenario("smoke", device="nope").device_profile()

    def test_tx2_is_the_calibration_anchor(self):
        """component_times must reproduce the calibrated defaults exactly
        on the TX2 (engine parity depends on it)."""
        assert profiles.component_times("jetson_tx2") == ComponentTimes()
        tpu = profiles.component_times("tpu_v5e")
        for f in dataclasses.fields(ComponentTimes):
            assert getattr(tpu, f.name) < getattr(ComponentTimes(), f.name)

    def test_device_threads_through_session(self):
        """device= reaches the engine: a v5e edge models faster on-board
        time than the TX2 default."""
        tx2 = api.Session(api.scenario("smoke", seed=5)).run(6)
        v5e = api.Session(api.scenario("smoke", seed=5,
                                       device="tpu_v5e")).run(6)
        assert v5e.mean_onboard < tx2.mean_onboard
        assert v5e.kinds(0) == tx2.kinds(0)   # fos ignores the profile


class TestAutoBackend:
    @pytest.fixture(autouse=True)
    def _clean_table(self):
        yield
        ops.clear_measurements()

    def test_pinned_table_resolution_is_deterministic(self):
        table = {op: {"ref": 1.0, "pallas": 2.0} for op in ops.list_ops()}
        table["iou2d"] = {"ref": 2.0, "pallas": 1.0}
        ops.set_measurements(table)
        for _ in range(3):
            assert ops.best_backend("iou2d") == "pallas"
            assert ops.best_backend("point_proj") == "ref"
        # get_impl("auto") returns exactly the winner's registered impl.
        assert ops.get_impl("iou2d", "auto") is \
            ops.get_impl("iou2d", "pallas")
        assert ops.get_impl("point_proj", "auto") is \
            ops.get_impl("point_proj", "ref")
        # Re-pinning flips resolution (no stale cache).
        table["iou2d"] = {"ref": 0.5, "pallas": 1.0}
        ops.set_measurements(table)
        assert ops.best_backend("iou2d") == "ref"

    def test_tie_and_missing_rows_are_deterministic(self):
        ops.set_measurements({"iou2d": {"ref": 1.0, "pallas": 1.0}})
        assert ops.best_backend("iou2d") == "ref"   # tie -> first backend
        # Ops without a row fall back to the process default.
        assert ops.best_backend("point_proj") in ops.BACKENDS

    def test_env_auto_accepted(self, monkeypatch):
        monkeypatch.setenv("MOBY_BACKEND", "auto")
        assert ops.default_backend() == "auto"
        assert ops.resolve_backend(None) == "auto"

    def test_auto_run_matches_pinned_ref(self):
        """A run under backend="auto" with a ref-pinned table is exactly
        the ref run (resolution is per op but fully determined)."""
        ops.set_measurements({op: {"ref": 0.0, "pallas": 1.0}
                              for op in ops.list_ops()})
        auto = api.Session(api.scenario("smoke", seed=5,
                                        backend="auto")).run(6)
        ref = api.Session(api.scenario("smoke", seed=5,
                                       backend="ref")).run(6)
        assert auto.kinds(0) == ref.kinds(0)
        np.testing.assert_array_equal(auto.f1, ref.f1)


class TestTelemetry:
    def test_observe_broadcasts_over_streams(self):
        st = scheduler.init_scheduler_fleet(3, 4)
        st = scheduler.observe_telemetry(st, bw_mbps=12.5, edge_cost_s=0.07,
                                         offload_cost_s=0.3)
        np.testing.assert_allclose(np.asarray(st.bw_mbps), 12.5)
        assert st.bw_mbps.shape == (3,)
        # Per-stream arrays pass through unchanged.
        st = scheduler.observe_telemetry(st, bw_mbps=jnp.arange(3.0))
        np.testing.assert_allclose(np.asarray(st.bw_mbps), [0.0, 1.0, 2.0])
        # Untouched fields stay put.
        np.testing.assert_allclose(np.asarray(st.edge_cost_s), 0.07)

    def test_post_maintains_ewma_and_anchor_clock(self):
        st = scheduler.init_scheduler(4)
        params = scheduler.SchedulerParams()
        boxes = jnp.zeros((4, 7))
        valid = jnp.zeros((4,), bool)
        # Frame 0: the pending anchor runs; drift clock resets.
        act = scheduler.scheduler_pre(st, params)
        assert bool(act.run_as_anchor)
        st = scheduler.scheduler_post(st, act, boxes, valid,
                                      jnp.bool_(False), boxes, valid, params)
        assert int(st.frames_since_anchor) == 0
        # A returned test that disagrees completely (we buffered nothing,
        # the cloud saw one object -> f1 = 0) folds into the EWMA.
        st = st._replace(test_inflight=jnp.bool_(True))
        act = scheduler.SchedulerActions(jnp.bool_(False), jnp.bool_(False))
        tboxes = jnp.zeros((4, 7)).at[0, 3:6].set(2.0)
        tvalid = jnp.zeros((4,), bool).at[0].set(True)
        st = scheduler.scheduler_post(st, act, boxes, valid,
                                      jnp.bool_(True), tboxes, tvalid,
                                      params)
        assert float(st.err_ewma) == pytest.approx(scheduler.EWMA_ALPHA)
        assert int(st.frames_since_anchor) == 1


FRAMES = 40
_DRIFT = dict(name="lossy-uplink", seed=0)   # degraded uplink, drifting fos


def _run(policy, **kw):
    spec = dict(_DRIFT)
    spec.update(kw)
    name = spec.pop("name")
    return api.Session(api.scenario(name, policy=policy, **spec)).run(FRAMES)


class TestAdaptivePolicy:
    def test_registered_and_jit_static(self):
        pol = scheduler.get_policy("adaptive")
        assert pol.name == "adaptive" and pol.uses_tests
        assert hash(scheduler.SchedulerParams(policy="adaptive")) is not None
        with pytest.raises(KeyError, match="no argument"):
            scheduler.get_policy("adaptive(3)")

    def test_anchor_rate_ordering_under_drift(self):
        """On a drifting trace the adaptive anchor rate sits strictly
        between the never/always bounds and does not exceed periodic(4)'s
        fixed cadence."""
        always = _run("always_anchor").anchor_rate
        never = _run("never_anchor").anchor_rate
        adaptive = _run("adaptive").anchor_rate
        periodic = _run("periodic(4)").anchor_rate
        assert always == 1.0
        assert never == pytest.approx(1 / FRAMES)
        assert never < adaptive < always
        assert adaptive <= periodic

    @staticmethod
    def _telemetry_state(**kw):
        st = scheduler.init_scheduler(4)._replace(
            anchor_pending=jnp.bool_(False))
        st = scheduler.observe_telemetry(st, bw_mbps=20.0, edge_cost_s=0.07,
                                         offload_cost_s=0.3)
        return st._replace(**{k: jnp.asarray(v) for k, v in kw.items()})

    def test_drift_raises_anchoring(self):
        """The decision surface: predicted drift below the budget keeps
        the frame on-device, above it forces an anchor, and the drift
        clock (frames since the last anchor) grows the prediction."""
        pre = scheduler.get_policy("adaptive").pre
        params = scheduler.SchedulerParams()
        calm = self._telemetry_state(err_ewma=0.05, frames_since_anchor=2)
        drifting = self._telemetry_state(err_ewma=0.4,
                                         frames_since_anchor=2)
        assert not bool(pre(calm, params).run_as_anchor)
        assert bool(pre(drifting, params).run_as_anchor)
        # The same EWMA eventually anchors as the open-loop run lengthens.
        aged = self._telemetry_state(err_ewma=0.12, frames_since_anchor=40)
        assert bool(pre(aged, params).run_as_anchor)

    def test_budget_scales_with_offload_cost(self):
        """A congested uplink (expensive offload) tolerates more drift
        than a cheap one — the cost half of the trade-off."""
        pre = scheduler.get_policy("adaptive").pre
        params = scheduler.SchedulerParams()
        cheap = self._telemetry_state(err_ewma=0.1)._replace(
            offload_cost_s=jnp.float32(0.001))
        congested = self._telemetry_state(err_ewma=0.1)._replace(
            offload_cost_s=jnp.float32(5.0))
        assert bool(pre(cheap, params).run_as_anchor)
        assert not bool(pre(congested, params).run_as_anchor)

    def test_calm_streams_test_less_often(self):
        """With zero observed drift the test period stretches past the fos
        cadence; near the budget it shrinks back to it."""
        pre = scheduler.get_policy("adaptive").pre
        params = scheduler.SchedulerParams()      # n_t = 4
        calm = self._telemetry_state(frames_since_test=4)
        assert not bool(pre(calm, params).send_test)   # fos would test now
        near = self._telemetry_state(frames_since_test=4, err_ewma=0.12)
        assert bool(pre(near, params).send_test)

    def test_adaptive_dominates_a_baseline(self):
        """Acceptance: the (accuracy, offload-rate) point of the adaptive
        policy weakly dominates at least one of fos / periodic(k) on the
        drifting trace."""
        adaptive = _run("adaptive")
        baselines = {p: _run(p) for p in ("fos", "periodic(4)",
                                          "periodic(8)")}
        dominated = [
            p for p, rep in baselines.items()
            if adaptive.mean_f1 >= rep.mean_f1
            and adaptive.offload_rate <= rep.offload_rate]
        assert dominated, {
            "adaptive": (adaptive.mean_f1, adaptive.offload_rate),
            **{p: (r.mean_f1, r.offload_rate)
               for p, r in baselines.items()}}

    def test_runs_on_both_backends(self):
        """The policy is pure jnp: it traces under the pallas backend and
        decisions match the ref run."""
        ref = api.Session(api.scenario("smoke", seed=5, policy="adaptive",
                                       backend="ref")).run(8)
        pal = api.Session(api.scenario("smoke", seed=5, policy="adaptive",
                                       backend="pallas")).run(8)
        assert ref.kinds(0) == pal.kinds(0)

    def test_threads_through_fleet(self):
        rep = api.Session(api.scenario("smoke", seed=5, n_streams=2,
                                       policy="adaptive")).run(8)
        assert rep.n_streams == 2 and rep.n_frames == 8
        assert [rep.kinds(s)[0] for s in range(2)] == ["anchor"] * 2
        assert rep.policy == "adaptive"


class TestSweep:
    def test_sweep_concatenates_one_csv(self):
        from benchmarks import sweep as sweep_mod
        text, summaries = sweep_mod.sweep(
            scenarios=("smoke",), policies=("fos", "periodic(4)"), frames=4)
        lines = text.strip().splitlines()
        assert lines[0].count("scenario") == 1 and "policy" in lines[0]
        assert len(lines) == 1 + 2 * 4          # one header, 2 cells x 4
        assert any(",smoke,fos" in ln for ln in lines[1:])
        assert any(",smoke,periodic(4)" in ln for ln in lines[1:])
        assert {s["policy"] for s in summaries} == {"fos", "periodic(4)"}
        assert all("offload_rate" in s for s in summaries)


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
