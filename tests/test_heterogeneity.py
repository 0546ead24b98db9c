"""Per-stream device heterogeneity tests.

Pillars of the ProfileVector layer (runtime.profiles -> fleet.engine):

* **Uniform-mix parity** — a fleet whose per-stream profile vector is a
  uniform broadcast of device D is *bitwise identical* to the scalar
  ``device=D`` path (the acceptance invariant guarding the
  scalar->vector refactor), and the S=1 fleet still reproduces the
  (scalar-only) MobyEngine.
* **Permutation equivariance** — relabeling streams (tape, device and
  PRNG seed together) permutes the per-stream outputs and leaves fleet
  aggregates unchanged, under the paper's ``fos`` policy and under the
  telemetry-driven ``adaptive`` one: no hidden dependence on stream
  order.
* **Model equivalence properties** (hypothesis, via hypothesis_compat) —
  the vectorized component/latency models agree elementwise with the
  scalar ones for any registered device and any mix spec.
"""
import dataclasses
import os

import jax
import numpy as np
import pytest

from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st  # noqa: F401

from repro.core import scheduler
from repro.data import scenes
from repro.fleet import FleetEngine
from repro.runtime import profiles
from repro.serving import engine as engine_lib
from repro.serving import tape as tape_lib
from repro.serving.common import nominal_transform_time

jax.config.update("jax_platform_name", "cpu")

# The heterogeneity surface is modeled latency — identical under every
# ops backend (backend parity itself is tests/test_backends.py). Skip the
# expensive engine builds on the pallas-interpret CI leg.
pytestmark = pytest.mark.skipif(
    os.environ.get("MOBY_BACKEND", "") == "pallas",
    reason="modeled-latency tier runs on the ref leg")

FRAMES = 10
EDGE_DEVICES = ("jetson_tx2", "jetson_orin")


def _cfg():
    return scenes.SceneConfig(max_obj=6, n_points=1024, img_h=48, img_w=160,
                              mean_objects=3, density_scale=4000.0, seed=5)


def _assert_reports_equal(a, b, msg=""):
    assert (a.kind == b.kind).all(), msg
    for col in ("latency_s", "onboard_s", "f1", "precision", "recall"):
        assert np.array_equal(getattr(a, col), getattr(b, col)), \
            f"{msg}: {col} differs"


class TestUniformMixParity:
    @pytest.mark.parametrize("device", EDGE_DEVICES)
    def test_uniform_vector_is_bitwise_scalar(self, device):
        """device=D == device=[D]*S == device={D: 1.0}, bitwise."""
        cfg = _cfg()
        reports = {}
        for label, spec in (("scalar", device),
                            ("list", [device] * 2),
                            ("mix", {device: 1.0})):
            eng = FleetEngine(cfg, "pointpillar", n_streams=2, seed=5,
                              device=spec)
            reports[label] = eng.run(FRAMES)
        for label in ("list", "mix"):
            _assert_reports_equal(reports["scalar"], reports[label],
                                  f"{device}/{label}")
            assert list(reports[label].device) == [device] * 2

    @pytest.mark.parametrize("device", EDGE_DEVICES)
    def test_s1_vector_fleet_matches_scalar_moby_engine(self, device):
        """The vectorized fleet path still reduces to the (scalar-profile)
        MobyEngine at S=1 on any device — the profile refactor did not
        drift the modeled numbers."""
        cfg = _cfg()
        tape = tape_lib.record_stream_tape(cfg, "pointpillar", FRAMES,
                                           seed=5)
        moby = engine_lib.MobyEngine(cfg, "pointpillar", seed=5, tape=tape,
                                     device=device).run(FRAMES)
        fleet = FleetEngine(cfg, "pointpillar", n_streams=1, seed=5,
                            tapes=[tape], device=[device]).run(FRAMES)
        assert [r.kind for r in moby.records] == fleet.kinds(0)
        np.testing.assert_allclose(
            [r.latency_s for r in moby.records], fleet.latency_s[0],
            atol=1e-6)
        np.testing.assert_allclose(
            [r.onboard_s for r in moby.records], fleet.onboard_s[0],
            atol=1e-6)


class TestPermutationEquivariance:
    @pytest.fixture(scope="class", params=["fos", "adaptive"])
    def fleet_runs(self, request):
        """A mixed S=3 fleet and its relabeling under a permutation, served
        under the policy ``request.param``."""
        cfg = _cfg()
        s_n = 3
        devices = ["jetson_tx2", "jetson_orin", "jetson_tx2"]
        tapes = tape_lib.record_fleet_tapes(cfg, "pointpillar", FRAMES, s_n,
                                            seed=5)
        perm = [2, 0, 1]
        sparams = scheduler.SchedulerParams(policy=request.param)
        base = FleetEngine(cfg, "pointpillar", n_streams=s_n, seed=5,
                           tapes=tapes, device=devices, sparams=sparams,
                           stream_seeds=list(range(s_n)))
        permuted = FleetEngine(cfg, "pointpillar", n_streams=s_n, seed=5,
                               tapes=[tapes[p] for p in perm],
                               device=[devices[p] for p in perm],
                               sparams=sparams, stream_seeds=perm)
        return perm, base.run(FRAMES), permuted.run(FRAMES)

    def test_outputs_permute(self, fleet_runs):
        perm, a, b = fleet_runs
        assert (b.kind == a.kind[perm]).all()
        assert list(b.device) == [a.device[p] for p in perm]
        for col in ("latency_s", "onboard_s", "f1", "precision", "recall"):
            np.testing.assert_allclose(
                getattr(b, col), getattr(a, col)[perm], atol=1e-6,
                err_msg=col)

    def test_aggregates_invariant(self, fleet_runs):
        _, a, b = fleet_runs
        assert b.mean_latency == pytest.approx(a.mean_latency, abs=1e-7)
        assert b.mean_f1 == pytest.approx(a.mean_f1, abs=1e-6)
        assert b.anchor_rate == a.anchor_rate
        assert b.offload_rate == a.offload_rate


class TestDeviceResolution:
    def test_mix_counts_largest_remainder(self):
        names = profiles.resolve_stream_devices(
            {"jetson_tx2": 0.75, "jetson_orin": 0.25}, 16)
        assert names.count("jetson_tx2") == 12
        assert names.count("jetson_orin") == 4

    def test_aliases_resolve_to_canonical_names(self):
        assert profiles.resolve_stream_devices("orin", 2) == \
            ("jetson_orin",) * 2
        assert profiles.resolve_stream_devices(["tx2", "orin"], 2) == \
            ("jetson_tx2", "jetson_orin")

    def test_unknown_device_raises_with_listing(self):
        with pytest.raises(KeyError, match="registered profiles"):
            profiles.resolve_stream_devices({"nope": 1.0}, 4)

    def test_negative_mix_weight_rejected(self):
        with pytest.raises(ValueError, match="negative"):
            profiles.resolve_stream_devices(
                {"jetson_tx2": 1.5, "jetson_orin": -0.5}, 16)

    def test_unregistered_profile_instance_passes_through(self):
        """A DeviceProfile never registered still works everywhere a spec
        is accepted — matching the scalar get_profile pass-through."""
        custom = profiles.DeviceProfile(name="bench-rig", peak_flops=5e12,
                                        hbm_bw=100e9)
        pv = profiles.profile_vector([custom, "tx2"], 2)
        assert pv[0] is custom and pv.names[0] == "bench-rig"
        assert profiles.resolve_stream_devices(custom, 3) == \
            ("bench-rig",) * 3
        ct = profiles.component_times_vector(pv)
        assert np.asarray(ct.seg_2d).shape == (2,)

    def test_length_mismatch_raises(self):
        with pytest.raises(ValueError, match="names 2 streams"):
            profiles.resolve_stream_devices(["tx2", "orin"], 3)
        with pytest.raises(ValueError, match="stream seeds"):
            FleetEngine(_cfg(), "pointpillar", n_streams=2,
                        stream_seeds=[0, 1, 2])

    def test_profile_vector_indexing(self):
        pv = profiles.profile_vector(["tx2", "orin"], 2)
        assert pv.n_streams == 2
        assert pv[1] is profiles.get_profile("jetson_orin")
        assert pv.effective_flops[1] > pv.effective_flops[0]


class TestMixedFleetSweep:
    def test_orin_streams_have_lower_p95_in_sweep_csv(self):
        """Acceptance: on fleet-64-mixed under the adaptive policy, the
        per-stream p95 modeled latency of Orin-class streams beats the
        TX2-class streams' — read back from the sweep CSV rows (device
        column + per-frame latency), the way the benchmark consumes it."""
        import csv
        import io

        from benchmarks import sweep as sweep_mod

        text, _ = sweep_mod.sweep(scenarios=("fleet-64-mixed",),
                                  policies=("adaptive",), frames=24)
        per_stream = {}
        for r in csv.DictReader(io.StringIO(text)):
            per_stream.setdefault((r["device"], r["stream"]),
                                  []).append(float(r["latency_s"]))
        by_dev = {}
        for (dev, _), lats in per_stream.items():
            by_dev.setdefault(dev, []).append(np.percentile(lats, 95))
        assert set(by_dev) == {"jetson_tx2", "jetson_orin"}
        assert len(per_stream) == 64
        assert np.mean(by_dev["jetson_orin"]) < np.mean(by_dev["jetson_tx2"])


# ---------------------------------------------------------------------------
# Model-equivalence properties (hypothesis; skipped when not installed)
# ---------------------------------------------------------------------------

_DEVICE_ST = st.sampled_from(EDGE_DEVICES + ("tpu_v5e",))


@settings(max_examples=25, deadline=None)
@given(device=_DEVICE_ST, n=st.integers(min_value=1, max_value=8))
def test_uniform_vector_models_match_scalar(device, n):
    """Uniform ProfileVector component/latency models == scalar models,
    bitwise, for every registered edge device and fleet size."""
    pv = profiles.profile_vector(device, n)
    ct_v = profiles.component_times_vector(pv)
    ct_s = profiles.component_times(device)
    for f in dataclasses.fields(profiles.ComponentTimes):
        assert np.all(np.asarray(getattr(ct_v, f.name))
                      == getattr(ct_s, f.name)), f.name
    lat_v = profiles.detector_latency("pointpillar", pv)
    assert np.all(np.asarray(lat_v)
                  == profiles.detector_latency("pointpillar", device))
    assert profiles.component_slice(ct_v, n - 1) == ct_s


@settings(max_examples=25, deadline=None)
@given(devices=st.lists(_DEVICE_ST, min_size=1, max_size=8),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_permuting_device_list_permutes_models(devices, seed):
    """resolve/stack is equivariant: permuting the spec permutes the
    stacked arrays, names and per-stream nominal costs."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(devices))
    pv = profiles.profile_vector(devices, len(devices))
    pv_p = profiles.profile_vector([devices[p] for p in perm],
                                   len(devices))
    assert pv_p.names == tuple(pv.names[p] for p in perm)
    np.testing.assert_array_equal(pv_p.effective_flops,
                                  pv.effective_flops[perm])
    cost = nominal_transform_time(profiles.component_times_vector(pv),
                                  use_tba=True, use_fos=True)
    cost_p = nominal_transform_time(profiles.component_times_vector(pv_p),
                                    use_tba=True, use_fos=True)
    np.testing.assert_array_equal(cost_p, np.asarray(cost)[perm])


if __name__ == "__main__":
    pytest.main([__file__, "-v", "-x"])
