"""repro.api facade tests (ISSUE 3).

Contract under test:
* ``Session(scenario).run(n)`` with the default ``fos`` policy is
  frame-for-frame identical to direct engine construction with the same
  seed — at S=1 (MobyEngine) and S=2 (FleetEngine);
* the scheduler policy registry orders anchor rates sanely
  (``always_anchor`` > ``fos`` > ``never_anchor``) and parameterized
  ``periodic(k)`` anchors on its period;
* unknown scenario / policy / override names raise KeyError listing what
  is available;
* RunReport export (records view, summary, CSV) round-trips.
"""
import jax
import numpy as np
import pytest

from repro import api
from repro.core import scheduler
from repro.fleet import FleetEngine
from repro.serving import engine as engine_lib

jax.config.update("jax_platform_name", "cpu")

FRAMES = 12


class TestSessionParity:
    def test_s1_matches_direct_moby_engine(self):
        scn = api.scenario("smoke", seed=5)
        rep = api.Session(scn).run(FRAMES)
        direct = engine_lib.MobyEngine(scn.scene, scn.detector,
                                       trace=scn.trace, mode="moby",
                                       seed=5).run(FRAMES)
        assert rep.kinds(0) == [r.kind for r in direct.records]
        np.testing.assert_array_equal(rep.f1, direct.f1)
        np.testing.assert_array_equal(rep.latency_s, direct.latency_s)
        np.testing.assert_array_equal(rep.onboard_s, direct.onboard_s)
        assert rep.scenario == "smoke" and rep.policy == "fos"

    def test_s2_matches_direct_fleet_engine(self):
        scn = api.scenario("smoke", seed=5, n_streams=2)
        rep = api.Session(scn).run(FRAMES)
        direct = FleetEngine(scn.scene, scn.detector, n_streams=2,
                             trace=scn.trace, seed=5).run(FRAMES)
        for s in range(2):
            assert rep.kinds(s) == direct.kinds(s)
        np.testing.assert_array_equal(rep.f1, direct.f1)
        np.testing.assert_array_equal(rep.latency_s, direct.latency_s)

    def test_string_scenario_and_engine_choice(self):
        assert isinstance(api.Session("smoke").engine, engine_lib.MobyEngine)
        assert isinstance(
            api.Session(api.scenario("smoke", n_streams=3)).engine,
            FleetEngine)

    def test_baseline_modes_run_single_stream(self):
        """edge/cloud_only are single-stream notions: a fleet preset's
        baseline comparison runs on one stream instead of raising."""
        sess = api.Session(api.scenario("smoke", n_streams=3,
                                        mode="edge_only"))
        assert isinstance(sess.engine, engine_lib.MobyEngine)
        assert sess.n_streams == 1


class TestPolicies:
    def _rate(self, policy, frames=16):
        scn = api.scenario("smoke", seed=5, policy=policy)
        return api.Session(scn).run(frames).anchor_rate

    def test_anchor_rate_ordering(self):
        always = self._rate("always_anchor")
        fos = self._rate("fos")
        never = self._rate("never_anchor")
        assert always == 1.0
        assert always > fos > never
        assert never == pytest.approx(1 / 16)

    def test_periodic_period(self):
        rep = api.Session(
            api.scenario("smoke", seed=5, policy="periodic(4)")).run(12)
        assert rep.kinds(0) == ["anchor", "transform", "transform",
                                "transform"] * 3
        assert rep.policy == "periodic(4)"

    def test_policy_threads_through_fleet(self):
        sess = api.Session(api.scenario("smoke", seed=5, n_streams=2,
                                        policy="periodic(3)"))
        rep = sess.run(9)
        for s in range(2):
            assert rep.kinds(s)[::3] == ["anchor"] * 3
        assert rep.anchor_rate == pytest.approx(1 / 3)

    def test_policy_lives_in_static_params(self):
        sp = scheduler.SchedulerParams(policy="never_anchor")
        assert hash(sp) is not None     # jit-static cache key stays hashable
        pol = scheduler.get_policy(sp.policy)
        assert pol.name == "never_anchor"

    def test_explicit_policy_with_use_fos_off_rejected(self):
        """use_fos=False bypasses the scheduler; an explicit policy must
        error rather than be silently ignored."""
        with pytest.raises(ValueError, match="use_fos"):
            api.Session(api.scenario("smoke", use_fos=False,
                                     policy="periodic(2)"))

    def test_fos_cost_only_charged_for_test_policies(self):
        """ComponentTimes.fos models test-frame scoring; policies that
        never offload tests must not pay it."""
        assert scheduler.get_policy("fos").uses_tests
        assert not scheduler.get_policy("periodic(4)").uses_tests
        fos_eng = api.Session(api.scenario("smoke")).engine
        per_eng = api.Session(
            api.scenario("smoke", policy="periodic(4)")).engine
        assert fos_eng._charge_fos and not per_eng._charge_fos

    def test_reregistration_takes_effect(self):
        def factory(tag):
            return lambda arg: scheduler.get_policy("fos")._replace(name=tag)
        scheduler.register_policy("tmp_test_policy", factory("v1"))
        assert scheduler.get_policy("tmp_test_policy").name == "v1"
        scheduler.register_policy("tmp_test_policy", factory("v2"))
        assert scheduler.get_policy("tmp_test_policy").name == "v2"
        scheduler._POLICIES.pop("tmp_test_policy")
        scheduler.get_policy.cache_clear()


class TestRegistryErrors:
    def test_unknown_scenario_lists_names(self):
        with pytest.raises(KeyError, match="kitti-urban"):
            api.scenario("does-not-exist")

    def test_unknown_override_lists_keys(self):
        with pytest.raises(KeyError, match="density_scale"):
            api.scenario("smoke", densty_scale=1.0)

    def test_unknown_policy_lists_names(self):
        with pytest.raises(KeyError, match="always_anchor"):
            api.Session(api.scenario("smoke", policy="nope"))
        with pytest.raises(KeyError, match="registered policies"):
            scheduler.get_policy("periodic(x)")  # malformed arg

    def test_all_presets_resolve(self):
        for name in api.list_scenarios():
            scn = api.scenario(name)
            assert scn.name == name
            scn.scheduler_params()      # policy validates


class TestRunReport:
    @pytest.fixture(scope="class")
    def report(self):
        return api.Session(api.scenario("smoke", seed=5)).run(8)

    def test_records_view(self, report):
        recs = report.records
        assert len(recs) == 8
        assert recs[0].kind == "anchor"
        assert report.mean_f1 == pytest.approx(
            np.mean([r.f1 for r in recs]), abs=1e-6)

    def test_records_guard_multi_stream(self):
        rep = api.Session(api.scenario("smoke", seed=5, n_streams=2)).run(4)
        with pytest.raises(ValueError, match="stream_records"):
            _ = rep.records
        assert len(rep.stream_records(1)) == 4

    def test_summary_and_csv(self, report):
        s = report.summary()
        assert s["scenario"] == "smoke" and s["n_frames"] == 8
        text = report.to_csv()
        lines = text.strip().splitlines()
        assert lines[0].startswith("stream,frame,kind")
        assert len(lines) == 1 + 8


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
