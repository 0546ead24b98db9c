"""Sharded megafleet tier: the stream-axis device mesh through
FleetEngine / Session.

* **1-device-mesh parity** — ``mesh=1`` must reproduce the unsharded path
  BITWISE in both run modes and on both ops backends: the sharded code
  path (NamedSharding boundaries, shard_map + psum scan, donated carries)
  is a pure partitioning of the same math.
* **Donation** — the compiled HLO of both dispatches aliases every carry
  leaf (``input_output_alias``), so device memory stays flat in run
  length and fleet size (runtime.hlo_analysis).
* **Multi-device parity** — a subprocess with 4 virtual CPU devices
  (``--xla_force_host_platform_device_count`` must precede JAX init)
  checks sharded == unsharded bitwise with real cross-shard psum, and
  that an observed orchestrated run puts each round's inputs over the
  four chips (``fleet/inputs``' ``chips`` and ``bytes_per_chip``, the
  per-shard input-bytes counter) without a retrace.
* **API routing** — Scenario.mesh reaches the engine; "auto" degrades to
  the unsharded path on a 1-device host; per-shard observer metrics.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.data import scenes
from repro.fleet.engine import FleetEngine
from repro.obs import ObsConfig
from repro.obs.metrics import MetricsRegistry
from repro.runtime import hlo_analysis

jax.config.update("jax_platform_name", "cpu")

FRAMES = 6
STREAMS = 4


def _cfg():
    return scenes.SceneConfig(max_obj=6, n_points=1024, img_h=48, img_w=160,
                              mean_objects=3, density_scale=4000.0, seed=5)


def _engine(mesh=None, backend=None, **kw):
    kw.setdefault("n_streams", STREAMS)
    return FleetEngine(_cfg(), "pointpillar", seed=0, mesh=mesh,
                       backend=backend, **kw)


def _packed(report):
    return np.stack([report.latency_s, report.onboard_s, report.f1,
                     report.precision, report.recall])


class TestMesh1Parity:
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_run_bitwise(self, backend):
        a = _engine(None, backend).run(FRAMES)
        b = _engine(1, backend).run(FRAMES)
        assert np.array_equal(_packed(a), _packed(b))
        assert np.array_equal(a.kind, b.kind)

    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_run_scan_bitwise(self, backend):
        a = _engine(None, backend).run_scan(FRAMES)
        b = _engine(1, backend).run_scan(FRAMES)
        assert np.array_equal(_packed(a), _packed(b))
        assert np.array_equal(a.kind, b.kind)


class TestDonation:
    def _step_hlo(self, mesh):
        e = _engine(mesh)
        st = e._init_state()
        inp = e._frame_inputs(e._stacked(2), 0)
        return e._step.lower(st, inp, jnp.zeros((STREAMS,), bool),
                             jnp.int32(0)).compile().as_text(), st

    def test_step_donates_all_carry_leaves(self):
        for mesh in (None, 1):
            hlo, st = self._step_hlo(mesh)
            donated = hlo_analysis.donated_params(hlo)
            n_carry = len(jax.tree.leaves(st))
            # Every carry param aliases an output buffer: the per-frame
            # step reuses the state in place, flat in run length.
            assert len(donated) >= n_carry, (mesh, sorted(donated))
            assert all(i < n_carry for i in donated)

    def test_scan_donates_carry(self):
        e = _engine(1)
        st = e._init_state()
        fn, consts = e._scan_fn()
        hlo = fn.lower(consts, st, e._scan_inputs(4), 4).compile().as_text()
        donated = hlo_analysis.donated_params(hlo)
        n_carry = len(jax.tree.leaves(st))
        assert len(donated) >= n_carry, sorted(donated)

    def test_alias_parser_roundtrip(self):
        f = jax.jit(lambda x, y: (x * 2, y + x), donate_argnums=(1,))
        txt = f.lower(jnp.zeros((4,)), jnp.zeros((4,))).compile().as_text()
        assert hlo_analysis.donated_params(txt) == {1}
        assert hlo_analysis.input_output_aliases("HloModule nothing") == []


class TestMultiDevice:
    def test_sharded_matches_unsharded_bitwise(self):
        """Real 4-shard run (virtual devices) vs the unsharded path: the
        psum-coupled contention model must make them identical. The flag
        only takes effect before JAX initializes — hence a subprocess."""
        prog = textwrap.dedent("""
            import numpy as np, jax
            assert len(jax.devices()) == 4, jax.devices()
            from repro.data import scenes
            from repro.fleet.engine import FleetEngine
            cfg = scenes.SceneConfig(max_obj=6, n_points=1024, img_h=48,
                                     img_w=160, mean_objects=3,
                                     density_scale=4000.0, seed=5)
            def rep(mesh):
                e = FleetEngine(cfg, "pointpillar", n_streams=8, seed=0,
                                mesh=mesh)
                r = e.run_scan(5)
                return (np.stack([r.latency_s, r.onboard_s, r.f1,
                                  r.precision, r.recall]), r.kind)
            a, ka = rep(None)
            b, kb = rep(4)
            assert np.array_equal(a, b), np.abs(a - b).max()
            assert np.array_equal(ka, kb)
            print("SHARDED_PARITY_OK")

            # Observed orchestrated rounds: every put of a round is
            # split over the four chips, and the per-shard counter adds
            # up to what the rounds put.
            from repro.obs import MetricsRegistry, ObsConfig
            reg = MetricsRegistry()
            e = FleetEngine(cfg, "pointpillar", n_streams=8, seed=0, mesh=4,
                            obs=ObsConfig(trace=True, metrics=True,
                                          registry=reg))
            compiles = []
            jax.monitoring.register_event_duration_secs_listener(
                lambda ev, d, **kw: compiles.append(ev)
                if ev == "/jax/core/compile/backend_compile_duration"
                else None)
            runs = []
            for _ in range(2):
                compiles.clear()
                runs.append(e.run(3))
            spans = [s for r in runs for s in r.obs.measured]
            inputs = [s for s in spans if s["name"] == "fleet/inputs"]
            assert len(inputs) == 6
            for s in inputs:
                assert (s["puts"], s["chips"]) == (10, 4), s
                assert s["bytes_per_chip"] * 4 == s["bytes"], s
            assert all(s["chips"] == 4 for s in spans
                       if s["name"] == "fleet/fetch")
            # The committed, replicated round index matches the step's
            # P() in-sharding: a warm engine's run compiles nothing.
            assert compiles == [], compiles
            for r in runs:
                r.obs.flush_metrics(r)
            c = reg.counter("moby_fleet_input_bytes_total", labels=("shard",))
            total = sum(s["bytes"] for s in inputs)
            assert [k for k, _ in c.samples()] == [("0",), ("1",), ("2",),
                                                   ("3",)]
            assert all(v * 4 == total for _, v in c.samples())
            print("SHARDED_INPUTS_OK")
        """)
        env = dict(os.environ,
                   XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                              + " --xla_force_host_platform_device_count=4"
                              ).strip(),
                   PYTHONPATH="src")
        out = subprocess.run([sys.executable, "-c", prog], env=env,
                             capture_output=True, text=True, timeout=560,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-2000:]
        assert "SHARDED_PARITY_OK" in out.stdout
        assert "SHARDED_INPUTS_OK" in out.stdout


class TestApiRouting:
    def test_scenario_mesh_reaches_engine(self):
        scn = api.scenario("smoke", n_streams=2, mesh=1)
        sess = api.Session(scn)
        assert sess.engine.n_shards == 1
        assert sess.engine.mesh is not None
        assert sess.engine.mesh.axis_names == ("streams",)

    def test_auto_degrades_on_single_device(self):
        scn = api.scenario("smoke", n_streams=2, mesh="auto")
        eng = api.Session(scn).engine
        if len(jax.devices()) == 1:
            assert eng.mesh is None and eng.n_shards == 1
        else:
            assert eng.n_shards == 2

    def test_mesh_run_through_session(self):
        scn = api.scenario("smoke", n_streams=2, mesh=1,
                           n_points=512, img_h=32, img_w=104)
        base = api.scenario("smoke", n_streams=2,
                            n_points=512, img_h=32, img_w=104)
        a = api.Session(scn).run(4, scan=True)
        b = api.Session(base).run(4, scan=True)
        assert np.array_equal(_packed(a), _packed(b))

    def test_shard_metrics_labels(self):
        """Observer(n_shards>1) emits per-shard tail gauges — host-side
        labeling only, so it is testable on a 1-device host."""
        from repro.obs.observe import Observer

        reg = MetricsRegistry()
        obs = Observer(ObsConfig(metrics=True, registry=reg),
                       n_streams=4, n_shards=2)
        e = _engine(None, n_streams=4)
        report = e.run(2)
        report.scenario, report.policy = "smoke", "default"
        obs.finalize(report)
        obs.flush_metrics(report)
        text = reg.to_prometheus()
        assert "moby_shard_p95_latency_seconds" in text
        assert 'shard="1"' in text
        assert "moby_shard_streams" in text


if __name__ == "__main__":
    pytest.main([__file__, "-v", "-x"])
