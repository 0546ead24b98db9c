"""Unified ops backend: registry semantics + full-pipeline parity.

The kernel sweeps in test_kernels.py check each op against its oracle in
isolation; these tests check the *system-level* contract of ISSUE 2: the
entire transformation path (transform_step / fused_step / a multi-stream
FleetEngine slice) must produce identical frame treatments, boxes, and F1
under ``backend="pallas"`` (interpret off-TPU) and ``backend="ref"``.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.core import metrics, projection, scheduler, transform
from repro.data import scenes
from repro.fleet import FleetEngine
from repro.fleet import step as step_lib
from repro.runtime import hlo_analysis
from repro.serving import tape as tape_lib
from repro.serving import twotier

jax.config.update("jax_platform_name", "cpu")

FRAMES = 6


def _cfg():
    return scenes.SceneConfig(max_obj=6, n_points=1024, img_h=48, img_w=160,
                              mean_objects=3, density_scale=4000.0, seed=5)


@pytest.fixture(scope="module")
def shared_tape():
    return tape_lib.record_stream_tape(_cfg(), "pointpillar", FRAMES, seed=5)


class TestRegistry:
    def test_known_ops_registered(self):
        for name in ("point_proj", "iou2d", "ransac_score", "pillar_scatter",
                     "flash_attention", "decode_attention"):
            assert name in ops.list_ops()

    def test_resolution_order(self, monkeypatch):
        monkeypatch.delenv("MOBY_BACKEND", raising=False)
        platform_default = ops.default_backend()
        assert platform_default in ops.BACKENDS
        # Env overrides the platform default; explicit overrides env.
        monkeypatch.setenv("MOBY_BACKEND", "pallas")
        assert ops.resolve_backend(None) == "pallas"
        assert ops.resolve_backend("") == "pallas"
        assert ops.resolve_backend("ref") == "ref"
        # "auto" is the per-op autotuned mode, resolved by get_impl.
        assert ops.resolve_backend("auto") == "auto"
        monkeypatch.setenv("MOBY_BACKEND", "auto")
        assert ops.resolve_backend(None) == "auto"

    def test_unknown_backend_rejected(self, monkeypatch):
        with pytest.raises(ValueError, match="unknown backend"):
            ops.resolve_backend("cuda")
        monkeypatch.setenv("MOBY_BACKEND", "nope")
        with pytest.raises(ValueError, match="MOBY_BACKEND"):
            ops.resolve_backend(None)

    def test_unknown_op_rejected(self):
        with pytest.raises(KeyError, match="not registered"):
            ops.get_impl("does_not_exist")


class TestDifferentiability:
    def test_pallas_ops_grad_matches_ref(self):
        """Training paths differentiate through attention and pillar
        scatter; the pallas registrations carry a ref-backed custom VJP."""
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(1, 2, 64, 16)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 2, 64, 16)).astype(np.float32))

        def loss(be):
            return lambda x: jnp.sum(
                ops.flash_attention(x, k, k, True, backend=be) ** 2)

        g_pal = jax.grad(loss("pallas"))(q)
        g_ref = jax.grad(loss("ref"))(q)
        np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                                   rtol=2e-5, atol=2e-5)

        f = jnp.asarray(rng.normal(size=(256, 8)).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, 64, 256).astype(np.int32))
        val = jnp.asarray(rng.uniform(size=256) < 0.9)
        gs = [np.asarray(jax.grad(lambda x: jnp.sum(
            ops.pillar_scatter(x, idx, val, 64, backend=be)))(f))
            for be in ("pallas", "ref")]
        np.testing.assert_allclose(gs[0], gs[1], rtol=1e-6, atol=1e-6)


def _run_stream(tape, backend):
    """Anchor frame 0 then transform the rest; returns per-frame outputs."""
    cfg = _cfg()
    tr, p = scenes.make_calibration(cfg)
    calib = projection.Calibration(tr=jnp.asarray(tr), p=jnp.asarray(p),
                                   height=cfg.img_h, width=cfg.img_w)
    params = transform.TransformParams(backend=backend)
    state = transform.init_state(2 * cfg.max_obj, jax.random.key(0))
    outs = []
    for t in range(FRAMES):
        f = tape.frame(t)
        if t == 0:
            state, out = transform.anchor_step(
                state, jnp.asarray(f.det3d), jnp.asarray(f.val3d), calib,
                params)
        else:
            state, out = transform.transform_step(
                state, jnp.asarray(f.points), jnp.asarray(f.det2d),
                jnp.asarray(f.val2d), jnp.asarray(f.label_img), calib, params)
        f1 = metrics.f1_score(out.boxes3d, out.valid,
                              jnp.asarray(f.gt_boxes),
                              jnp.asarray(f.gt_visible))[0]
        outs.append((np.asarray(out.boxes3d), np.asarray(out.valid),
                     np.asarray(out.det_to_track), float(f1)))
    return outs


class TestTransformParity:
    def test_transform_step_identical(self, shared_tape):
        ref = _run_stream(shared_tape, "ref")
        pal = _run_stream(shared_tape, "pallas")
        for t, ((br, vr, dr, fr), (bp, vp, dp, fp)) in enumerate(zip(ref,
                                                                     pal)):
            np.testing.assert_array_equal(vr, vp, err_msg=f"frame {t} valid")
            np.testing.assert_array_equal(dr, dp, err_msg=f"frame {t} assoc")
            np.testing.assert_allclose(br[vr], bp[vp], rtol=1e-4, atol=1e-4,
                                       err_msg=f"frame {t} boxes")
            np.testing.assert_allclose(fr, fp, atol=1e-5,
                                       err_msg=f"frame {t} f1")

    @pytest.mark.parametrize("use_tba", [True, False])
    @pytest.mark.parametrize("anchors", [(True, False), (False, True),
                                         (True, True), (False, False)])
    def test_fused_step_both_branches(self, shared_tape, anchors, use_tba):
        """vmapped fused_step, each stream taking its own branch, under both
        backends, jitted: the same frame outputs and tracker state as each
        stream's own anchor_step / transform_step."""
        f = shared_tape.frame(1)
        mask = jnp.array(anchors)
        results = {}
        for backend in ("ref", "pallas"):
            params = transform.TransformParams(backend=backend,
                                               use_tba=use_tba)
            state = _seeded_pair(shared_tape, params)
            steps = _jitted_steps(params)
            fused = steps["fused"](state, jnp.asarray(f.points),
                                   jnp.asarray(f.det2d), jnp.asarray(f.val2d),
                                   jnp.asarray(f.label_img),
                                   jnp.asarray(f.det3d), jnp.asarray(f.val3d),
                                   mask)
            own = [steps["anchor" if a else "transform"](
                jax.tree.map(lambda x, s=s: x[s], state), f)
                for s, a in enumerate(anchors)]
            for s, (st, out) in enumerate(own):
                got = jax.tree.map(lambda x, s=s: x[s], fused)
                _assert_same_step(got, (st, out), f"{backend} stream {s}")
            results[backend] = tuple(np.asarray(x) for x in
                                     (fused[1].boxes3d, fused[1].valid))

        (b_ref, v_ref), (b_pal, v_pal) = results["ref"], results["pallas"]
        np.testing.assert_array_equal(v_ref, v_pal)
        np.testing.assert_allclose(b_ref[v_ref], b_pal[v_pal],
                                   rtol=1e-4, atol=1e-4)

    def test_fleet_step_runs_one_association(self, shared_tape):
        """The compiled fleet step holds the auction's while loops of one
        association: one per epsilon phase (0.1, 0.01, 1e-3, 1e-4), not
        one set for each branch of the anchor/transform cond."""
        n = 2
        step = step_lib.make_fleet_step(
            _calib(), transform.TransformParams(backend="ref"),
            scheduler.SchedulerParams())
        f = shared_tape.frame(0)
        inputs = step_lib.FrameInputs(*(
            jnp.stack([jnp.asarray(getattr(f, k))] * n)
            for k in ("points", "det2d", "val2d", "label_img", "det3d",
                      "val3d", "gt_boxes", "gt_visible")))
        state = step_lib.init_fleet_state(n, _cfg().max_obj)
        text = step.lower(state, inputs, jnp.zeros((n,), bool),
                          jnp.int32(0)).compile().as_text()
        auction = re.compile(r"(^|/)(vmap\()*%s\)*/while$"
                             % transform.STAGE_ASSOCIATE)
        loops = [name for name in hlo_analysis.while_loops(text)
                 if auction.search(name)]
        assert len(loops) == 4, loops


def _calib():
    cfg = _cfg()
    tr, p = scenes.make_calibration(cfg)
    return projection.Calibration(tr=jnp.asarray(tr), p=jnp.asarray(p),
                                  height=cfg.img_h, width=cfg.img_w)


@functools.lru_cache(maxsize=None)
def _jitted_steps(params):
    """Jitted anchor/transform steps on a FrameTape and the vmapped fused
    step, compiled once per params across the parametrised cases."""
    calib = _calib()
    anchor = jax.jit(lambda st, f: transform.anchor_step(
        st, jnp.asarray(f.det3d), jnp.asarray(f.val3d), calib, params))
    trans = jax.jit(lambda st, f: transform.transform_step(
        st, jnp.asarray(f.points), jnp.asarray(f.det2d),
        jnp.asarray(f.val2d), jnp.asarray(f.label_img), calib, params))
    fused = jax.jit(jax.vmap(
        lambda st, pts, b2, v2, li, b3, v3, anchor: transform.fused_step(
            st, pts, b2, v2, li, b3, v3, anchor, calib, params),
        in_axes=(0, None, None, None, None, None, None, 0)))
    return {"anchor": anchor, "transform": trans, "fused": fused}


def _seeded_pair(tape, params):
    """Two streams of different keys, each anchored on frame 0, so frame
    1's association matches live tracks."""
    cfg = _cfg()
    steps = _jitted_steps(params)
    states = []
    for s in range(2):
        st = transform.init_state(2 * cfg.max_obj, jax.random.key(s))
        states.append(steps["anchor"](st, tape.frame(0))[0])
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def _assert_same_step(got, want, msg):
    """Frame outputs and the whole per-stream state, leaf by leaf: integer
    and boolean leaves (matches, track slots, keys) exactly, float leaves
    to a few ulp (a vmapped box fit can round its last bit otherwise)."""
    def leaves(tree):
        return [np.asarray(jax.random.key_data(x)
                           if jnp.issubdtype(x.dtype, jax.dtypes.prng_key)
                           else x) for x in jax.tree.leaves(tree)]
    for i, (g, w) in enumerate(zip(leaves(got), leaves(want))):
        if np.issubdtype(w.dtype, np.floating):
            np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6,
                                       err_msg=f"{msg} leaf {i}")
        else:
            np.testing.assert_array_equal(g, w, err_msg=f"{msg} leaf {i}")


class TestFleetParity:
    @pytest.mark.parametrize("run_mode", ["run", "run_scan"])
    def test_s2_fleet_identical(self, run_mode):
        """An S>1 fleet slice must make the same frame-treatment decisions
        and reach the same F1 under either backend (orchestrated + scan)."""
        cfg = _cfg()
        tapes = tape_lib.record_fleet_tapes(cfg, "pointpillar", FRAMES, 2,
                                            seed=5)

        def run(backend):
            eng = FleetEngine(cfg, "pointpillar", n_streams=2, seed=5,
                              tapes=tapes, backend=backend)
            return getattr(eng, run_mode)(FRAMES)

        ref = run("ref")
        pal = run("pallas")
        for s in range(2):
            assert ref.kinds(s) == pal.kinds(s), s
        np.testing.assert_allclose(ref.f1, pal.f1, atol=1e-5)
        np.testing.assert_allclose(ref.onboard_s, pal.onboard_s, atol=1e-6)


class TestTwoTierBackend:
    def test_moby_tiers_run_both_backends(self, shared_tape):
        cfg = _cfg()
        tr, p = scenes.make_calibration(cfg)
        calib = projection.Calibration(tr=jnp.asarray(tr), p=jnp.asarray(p),
                                       height=cfg.img_h, width=cfg.img_w)
        xs = [tuple(np.asarray(a) for a in shared_tape.frame(t))
              for t in range(FRAMES)]

        def run(backend):
            cheap, anchor, quality = twotier.make_moby_tiers(
                calib, backend=backend)
            eng = twotier.TwoTierEngine(twotier.TwoTierConfig(n_t=2, q_t=0.2),
                                        cheap, anchor, quality)
            state = transform.init_state(2 * cfg.max_obj, jax.random.key(0))
            _, outs, traces = eng.run(state, xs)
            return outs, traces

        outs_r, traces_r = run("ref")
        outs_p, traces_p = run("pallas")
        assert [t.kind for t in traces_r] == [t.kind for t in traces_p]
        for o_r, o_p in zip(outs_r, outs_p):
            np.testing.assert_array_equal(np.asarray(o_r.valid),
                                          np.asarray(o_p.valid))


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
