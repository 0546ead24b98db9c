"""Fleet serving subsystem tests.

Covers the three correctness pillars of repro.fleet:
* S=1 parity — the batched device-resident engine reduces exactly to the
  single-stream MobyEngine when both replay the same tape;
* scheduler batching — vmapped scheduler_pre/scheduler_post over S streams
  equals stepping each stream's state machine individually;
* contention — shared-uplink transfer times degrade monotonically with the
  number of sharers, and the cloud batcher amortizes batches while
  queueing overlapping rounds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scheduler
from repro.data import scenes
from repro.fleet import CloudBatcher, CloudBatcherConfig, FleetEngine
from repro.runtime import netsim
from repro.serving import engine as engine_lib
from repro.serving import tape as tape_lib

jax.config.update("jax_platform_name", "cpu")

FRAMES = 16


def _cfg():
    return scenes.SceneConfig(max_obj=6, n_points=1024, img_h=48, img_w=160,
                              mean_objects=3, density_scale=4000.0, seed=5)


@pytest.fixture(scope="module")
def shared_tape():
    return tape_lib.record_stream_tape(_cfg(), "pointpillar", FRAMES, seed=5)


# Engine settings both engines take: the defaults, on-board anchors, the
# scheduler and/or tracking-based association off, and three other
# scheduler policies.
S1_CASES = {
    "default": {},
    "moby_onboard": dict(mode="moby_onboard"),
    "no_fos": dict(use_fos=False),
    "no_tba": dict(use_tba=False),
    "no_fos_no_tba": dict(use_fos=False, use_tba=False),
    "adaptive": dict(sparams=scheduler.SchedulerParams(policy="adaptive")),
    "periodic3": dict(
        sparams=scheduler.SchedulerParams(policy="periodic(3)")),
    "always_anchor": dict(
        sparams=scheduler.SchedulerParams(policy="always_anchor")),
}


class TestFleetParity:
    @pytest.mark.parametrize("case", list(S1_CASES))
    def test_s1_matches_moby_engine(self, shared_tape, case):
        """Same tape through both engines: identical frame treatments and
        outputs — the vmap/lax.cond path is equivalent to host branching."""
        cfg = _cfg()
        kw = S1_CASES[case]
        moby = engine_lib.MobyEngine(cfg, "pointpillar", seed=5,
                                     tape=shared_tape, **kw).run(FRAMES)
        fleet = FleetEngine(cfg, "pointpillar", n_streams=1, seed=5,
                            tapes=[shared_tape], **kw)
        fr = fleet.run(FRAMES)

        assert [r.kind for r in moby.records] == fr.kinds(0)
        np.testing.assert_allclose(
            [r.f1 for r in moby.records], fr.f1[0], atol=1e-5)
        np.testing.assert_allclose(
            [r.onboard_s for r in moby.records], fr.onboard_s[0], atol=1e-6)
        # With one stream the shared uplink is uncontended and the cloud
        # batch size is 1, so timing also reduces to the seed engine.
        np.testing.assert_allclose(
            [r.latency_s for r in moby.records], fr.latency_s[0], atol=1e-6)

    def test_multi_stream_runs_and_anchors(self):
        cfg = _cfg()
        fr = FleetEngine(cfg, "pointpillar", n_streams=3, seed=5).run(12)
        assert fr.f1.shape == (3, 12)
        # Every stream starts with an anchor frame and stays reasonable.
        assert fr.is_anchor[:, 0].all()
        assert fr.mean_f1 > 0.5


class TestSchedulerBatching:
    def test_vmapped_state_machine_equals_per_stream(self):
        """Advancing S schedulers with one vmapped call is equivalent to
        stepping each stream's state machine on its own."""
        s_n, d = 5, 4
        rng = np.random.default_rng(0)
        sp = scheduler.SchedulerParams(n_t=3, q_t=0.6)
        batched = scheduler.init_scheduler_fleet(s_n, d)
        singles = [scheduler.init_scheduler(d) for _ in range(s_n)]
        v_pre = jax.vmap(lambda st: scheduler.scheduler_pre(st, sp))
        v_post = jax.vmap(
            lambda st, a, b, v, ta, tb, tv: scheduler.scheduler_post(
                st, a, b, v, ta, tb, tv, sp))

        for step in range(10):
            boxes = jnp.asarray(rng.normal(size=(s_n, d, 7)), jnp.float32)
            valid = jnp.asarray(rng.uniform(size=(s_n, d)) < 0.7)
            arrived = jnp.asarray(rng.uniform(size=(s_n,)) < 0.5)
            tboxes = jnp.asarray(rng.normal(size=(s_n, d, 7)), jnp.float32)
            tvalid = jnp.asarray(rng.uniform(size=(s_n, d)) < 0.7)

            acts = v_pre(batched)
            batched = v_post(batched, acts, boxes, valid, arrived,
                             tboxes, tvalid)
            for i in range(s_n):
                a1 = scheduler.scheduler_pre(singles[i], sp)
                np.testing.assert_array_equal(
                    np.asarray(acts.run_as_anchor[i]),
                    np.asarray(a1.run_as_anchor))
                np.testing.assert_array_equal(
                    np.asarray(acts.send_test[i]), np.asarray(a1.send_test))
                singles[i] = scheduler.scheduler_post(
                    singles[i], a1, boxes[i], valid[i], arrived[i],
                    tboxes[i], tvalid[i], sp)
                for name in scheduler.SchedulerState._fields:
                    np.testing.assert_allclose(
                        np.asarray(getattr(batched, name)[i]),
                        np.asarray(getattr(singles[i], name)),
                        atol=1e-6, err_msg=f"{name} @ step {step}")


class TestSharedUplink:
    def test_contention_monotonic(self):
        """More streams sharing the cell -> strictly slower transfers."""
        up = netsim.SharedUplink("belgium2", seed=0)
        times = [up.transfer_time(870_000, n_sharers=k)
                 for k in (1, 2, 4, 8, 16, 64)]
        assert all(a < b for a, b in zip(times, times[1:])), times

    def test_single_sharer_matches_networksim(self):
        base = netsim.NetworkSim("fcc1", seed=3)
        shared = netsim.SharedUplink("fcc1", seed=3)
        for nbytes in (10_000, 870_000, 3_000_000):
            assert shared.transfer_time(nbytes, n_sharers=1) == \
                pytest.approx(base.transfer_time(nbytes))

    def test_share_scales_roughly_linearly_when_saturated(self):
        """A large transfer at 1/k bandwidth takes ~k times longer."""
        up = netsim.SharedUplink("belgium2", seed=0)
        t1 = up.transfer_time(5_000_000, n_sharers=1)
        t8 = up.transfer_time(5_000_000, n_sharers=8)
        assert 6.0 < t8 / t1 < 10.0


class TestCloudBatcher:
    def test_batch_amortizes_per_item(self):
        cfg = CloudBatcherConfig(infer_s=0.1, marginal=0.3, max_batch=32)
        b = CloudBatcher(cfg)
        assert b.batch_infer_time(8) < 8 * b.batch_infer_time(1)
        assert b.batch_infer_time(8) > b.batch_infer_time(1)

    def test_overlapping_rounds_queue(self):
        cfg = CloudBatcherConfig(infer_s=0.5, marginal=0.0)
        b = CloudBatcher(cfg)
        d1 = b.submit_batch([0.0])
        d2 = b.submit_batch([0.1])  # arrives while the server is busy
        assert d1 == [0.5]
        assert d2 == [1.0]          # queued behind round 1

    def test_round_chunks_at_max_batch(self):
        cfg = CloudBatcherConfig(infer_s=0.1, marginal=0.0, max_batch=2)
        b = CloudBatcher(cfg)
        done = b.submit_batch([0.0, 0.0, 0.0])
        assert sorted(done) == pytest.approx([0.1, 0.1, 0.2])

    def test_fleet_anchor_latency_grows_with_contention(self):
        """End to end: the same scenario at S=1 vs S=8 — shared uplink and
        cloud queueing make anchors slower for everyone."""
        cfg = _cfg()
        lat1 = FleetEngine(cfg, "pointpillar", n_streams=1,
                           seed=7).run(10).mean_anchor_latency
        lat8 = FleetEngine(cfg, "pointpillar", n_streams=8,
                           seed=7).run(10).mean_anchor_latency
        assert lat8 > lat1


if __name__ == "__main__":
    pytest.main([__file__, "-v", "-x"])
