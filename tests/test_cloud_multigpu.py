"""Multi-GPU cloud batcher tests (fleet.cloud and the fleet engine).

* **G=1 parity** — the pooled batcher with one GPU reproduces the
  single-server queue exactly (reference reimplementation + the PR 1
  expected values).
* **Conservation** — summed per-GPU busy time equals the total dispatched
  service time, for any G / round pattern.
* **Monotonicity** — anchor latency on the congested fleet preset is
  non-increasing in the pool size.
* **Batch window** — a configured window closes batches early, and never
  splits a fleet round, whose requests arrive at one modeled instant.
"""
import os

import numpy as np
import pytest

import jax

from repro import api
from repro.fleet import CloudBatcher, CloudBatcherConfig
from repro.fleet import cloud as cloud_lib

jax.config.update("jax_platform_name", "cpu")


def _reference_single_server(rounds, cfg):
    """The PR 1 single-GPU queue, reimplemented independently: returns
    per-round completion lists."""
    busy = 0.0
    out = []
    for arrive in rounds:
        order = sorted(range(len(arrive)), key=lambda i: arrive[i])
        done = [0.0] * len(arrive)
        for lo in range(0, len(order), cfg.max_batch):
            chunk = order[lo:lo + cfg.max_batch]
            b = max(min(len(chunk), cfg.max_batch), 1)
            start = max(busy, max(arrive[i] for i in chunk))
            busy = start + cfg.infer_s * (1 + cfg.marginal * (b - 1))
            for i in chunk:
                done[i] = busy
        out.append(done)
    return out


def _random_rounds(rng, n_rounds=12, max_req=40):
    t = 0.0
    rounds = []
    for _ in range(n_rounds):
        n = int(rng.integers(0, max_req))
        rounds.append(list(t + rng.uniform(0, 0.3, n)))
        t += float(rng.uniform(0.05, 0.4))
    return rounds


class TestSingleGpuParity:
    def test_g1_matches_reference_queue(self):
        cfg = CloudBatcherConfig(infer_s=0.08, marginal=0.25, max_batch=6,
                                 n_gpus=1)
        b = CloudBatcher(cfg)
        rng = np.random.default_rng(0)
        rounds = _random_rounds(rng)
        ref = _reference_single_server(rounds, cfg)
        for arrive, want in zip(rounds, ref):
            assert b.submit_batch(arrive) == pytest.approx(want)

    def test_g1_pr1_expected_values(self):
        """The PR 1 test vectors still hold on the pooled implementation."""
        b = CloudBatcher(CloudBatcherConfig(infer_s=0.5, marginal=0.0))
        assert b.submit_batch([0.0]) == [0.5]
        assert b.submit_batch([0.1]) == [1.0]    # queued behind round 1
        b = CloudBatcher(CloudBatcherConfig(infer_s=0.1, marginal=0.0,
                                            max_batch=2))
        assert sorted(b.submit_batch([0.0, 0.0, 0.0])) == \
            pytest.approx([0.1, 0.1, 0.2])


class TestPool:
    @pytest.mark.parametrize("n_gpus", [1, 2, 4])
    def test_busy_time_conservation(self, n_gpus):
        """Sum of per-GPU busy time == total dispatched service time
        (computed independently from the batch sizes served)."""
        cfg = CloudBatcherConfig(infer_s=0.07, marginal=0.3, max_batch=5,
                                 n_gpus=n_gpus)
        b = CloudBatcher(cfg)
        rng = np.random.default_rng(n_gpus)
        expected = 0.0
        for arrive in _random_rounds(rng):
            b.submit_batch(arrive)
            n = len(arrive)
            while n > 0:
                sz = min(n, cfg.max_batch)
                expected += cfg.infer_s * (1 + cfg.marginal * (sz - 1))
                n -= sz
        assert b.busy_s == pytest.approx(expected)
        assert b.busy_s == pytest.approx(sum(b.busy_s_g))

    def test_round_robin_parallelizes_chunks(self):
        """Two same-round chunks land on two GPUs: both finish at the
        chunk service time instead of queueing serially."""
        cfg1 = CloudBatcherConfig(infer_s=0.1, marginal=0.0, max_batch=2,
                                  n_gpus=1)
        cfg2 = cloud_lib.replace_config(cfg1, n_gpus=2)
        d1 = CloudBatcher(cfg1).submit_batch([0.0, 0.0, 0.0, 0.0])
        d2 = CloudBatcher(cfg2).submit_batch([0.0, 0.0, 0.0, 0.0])
        assert sorted(d1) == pytest.approx([0.1, 0.1, 0.2, 0.2])
        assert d2 == pytest.approx([0.1, 0.1, 0.1, 0.1])

    def test_pool_monotone_on_random_rounds(self):
        """Mean completion over a random workload never degrades as the
        pool grows."""
        rng = np.random.default_rng(7)
        rounds = _random_rounds(rng, n_rounds=20)
        means = []
        for g in (1, 2, 4, 8):
            b = CloudBatcher(CloudBatcherConfig(infer_s=0.09, marginal=0.2,
                                                max_batch=4, n_gpus=g))
            done = [t for arrive in rounds for t in b.submit_batch(arrive)]
            means.append(np.mean(done))
        assert all(a >= b - 1e-9 for a, b in zip(means, means[1:])), means

    def test_unset_infer_raises_and_validation(self):
        with pytest.raises(ValueError, match="infer_s"):
            CloudBatcher(CloudBatcherConfig())
        with pytest.raises(ValueError, match="n_gpus"):
            CloudBatcher(CloudBatcherConfig(infer_s=0.1, n_gpus=0))

    def test_window_never_splits_a_fleet_round(self):
        """A fleet round's requests arrive at one modeled instant, so a
        window never splits a round: the windowed run matches the
        window-free one bitwise, exactly like CloudBatcher on simultaneous
        arrivals."""
        def report(cloud):
            sess = api.Session(api.scenario("smoke", n_streams=2, seed=0,
                                            cloud=cloud))
            r = sess.run(4)
            return np.stack([r.latency_s, r.onboard_s, r.f1])

        base = CloudBatcherConfig()
        a = report(cloud_lib.replace_config(base, window_s=0.05))
        b = report(base)
        assert np.array_equal(a, b)
        # ... and the host batcher itself treats simultaneous arrivals
        # identically with and without the window.
        cfg = CloudBatcherConfig(infer_s=0.1, marginal=0.0, max_batch=8)
        d_win = CloudBatcher(
            cloud_lib.replace_config(cfg, window_s=0.05)
        ).submit_batch([0.3, 0.3, 0.3])
        d_no = CloudBatcher(cfg).submit_batch([0.3, 0.3, 0.3])
        assert d_win == d_no


class TestBatchWindow:
    def test_window_splits_late_arrivals(self):
        cfg = CloudBatcherConfig(infer_s=0.1, marginal=0.0, max_batch=8,
                                 window_s=0.05)
        b = CloudBatcher(cfg)
        # 0.0 and 0.04 batch together; 0.2 opens a new batch.
        done = b.submit_batch([0.0, 0.04, 0.2])
        assert done[0] == pytest.approx(done[1])
        assert done[2] == pytest.approx(max(0.2, done[0]) + 0.1)

    def test_no_window_batches_whole_round(self):
        b = CloudBatcher(CloudBatcherConfig(infer_s=0.1, marginal=0.0,
                                            max_batch=8))
        done = b.submit_batch([0.0, 0.04, 0.2])
        assert len(set(round(d, 9) for d in done)) == 1


@pytest.mark.skipif(
    os.environ.get("MOBY_BACKEND", "") == "pallas",
    reason="modeled-latency tier runs on the ref leg")
class TestEngineMonotonicity:
    @pytest.fixture(scope="class")
    def congested_reports(self):
        """fleet-16-congested at G in {1, 2, 4} (tiny frame budget; one
        compile — the G sweep reuses shapes).
        max_batch=4 makes the S=16 anchor storm span 4 chunks, so the
        cloud queue — not just the uplink — actually binds."""
        frames = 8
        out = {}
        for g in (1, 2, 4):
            scn = api.scenario("fleet-16-congested", seed=0,
                               cloud=CloudBatcherConfig(n_gpus=g,
                                                        max_batch=4))
            sess = api.Session(scn)
            out[g] = sess.run(frames)
        return out

    def test_engine_fills_infer_s(self, congested_reports):
        scn = api.scenario("fleet-16-congested",
                           cloud=CloudBatcherConfig(n_gpus=2))
        eng = api.Session(scn).engine
        assert eng.cloud_cfg.infer_s is not None
        assert eng.cloud_cfg.n_gpus == 2

    def test_anchor_latency_non_increasing_in_g(self, congested_reports):
        lats = [congested_reports[g].mean_anchor_latency
                for g in (1, 2, 4)]
        assert all(a >= b - 1e-9 for a, b in zip(lats, lats[1:])), lats
        # The congested S=16 cell actually queues: G=4 is a strict win.
        assert lats[2] < lats[0]

    def test_pool_engages_on_single_chunk_rounds(self):
        """Consecutive one-chunk rounds must still spread over the pool —
        the batcher's round-robin pointer persists across rounds
        (CloudBatcher._rr), so a slow detector's back-to-back anchor
        rounds queue on G=1 and parallelize on G=4."""
        lats = {}
        for g in (1, 4):
            sess = api.Session(api.scenario(
                "smoke", n_streams=4, seed=0, policy="periodic(2)",
                cloud=CloudBatcherConfig(infer_s=0.35, n_gpus=g)))
            lats[g] = sess.run(16).mean_anchor_latency
        assert lats[4] < 0.6 * lats[1]


if __name__ == "__main__":
    pytest.main([__file__, "-v", "-x"])
