"""Bring-up smoke of the fleet serving path on a TPU.

Drives ``api.Session(...).run`` — the orchestrated loop — for a 16-stream
``kitti-urban`` fleet at the KITTI sensor's size (an HDL-64E sweep of 120k
points and one 1242x375 camera), on the chip's default ops backend: the
Pallas kernels, compiled by Mosaic. The same session on the ``ref``
backend, once on the chip and once pinned to the host CPU, is the
reference: frame kinds must match exactly and F1/precision/recall within
the golden CSVs' tolerance.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the sharded phase: a 4-chip
                                     # fleet-256-congested vs one chip,
                                     # compared bit for bit

Everything is generated from seeds; nothing is read from disk but the
repo's own ``src/``. Compiles go to the persistent cache of
``repro.launch.compile_cache``. Exits non-zero, printing no result, when
JAX finds no TPU or a phase fails. The last line of stdout is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"

N_FRAMES = 8
KITTI_SENSOR = dict(n_streams=16, n_points=120000, img_h=375, img_w=1242)
SHARDED_PRESET = "fleet-256-congested"
# The golden CSVs' float tolerance (tests/test_goldens.py).
RTOL, ATOL = 1e-4, 1e-5
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_QUALITY = ("f1", "precision", "recall")
_ALL_COLS = ("latency_s", "onboard_s") + _QUALITY


def emit(record: dict) -> None:
    print(json.dumps(record, default=str), flush=True)


class CompileClock:
    """Seconds of XLA backend compiles while active (tracing and lowering
    excluded: their events nest)."""

    def __init__(self):
        self.seconds = 0.0

    def _on(self, event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def check_report(report, n_streams: int, n_frames: int, what: str) -> list:
    """Shape, finiteness and kind vocabulary of one RunReport."""
    problems = []
    for col in _ALL_COLS:
        a = getattr(report, col)
        if a.shape != (n_streams, n_frames):
            problems.append(f"{what}: {col} shape {a.shape}")
        elif not np.isfinite(a).all():
            problems.append(f"{what}: {col} not finite")
    if not set(np.unique(report.kind)) <= {"anchor", "test", "transform"}:
        problems.append(f"{what}: kinds {sorted(set(report.kind.flat))}")
    return problems


def compare(got, want, what: str, bitwise: bool = False) -> list:
    """Kinds identical; quality columns within the goldens' tolerance, or
    every column bit for bit with ``bitwise``."""
    problems = []
    n_kind = int((got.kind != want.kind).sum())
    if n_kind:
        problems.append(f"{what}: kind differs at {n_kind} of "
                        f"{got.kind.size} stream-frames")
    for col in _ALL_COLS if bitwise else _QUALITY:
        g, w = getattr(got, col), getattr(want, col)
        bad = g != w if bitwise else ~np.isclose(g, w, rtol=RTOL, atol=ATOL)
        if bad.any():
            problems.append(
                f"{what}: {col} differs at {int(bad.sum())} stream-frames, "
                f"max |diff| {float(np.abs(g - w).max())!r}, first at "
                f"(stream, frame) {np.argwhere(bad)[0].tolist()}")
    emit({"compare": what, "ok": not problems, "problems": problems})
    return problems


def serve(scn, n_frames: int, label: str, repeat: bool = True):
    """One Session through ``run``. With ``repeat`` it runs a second time:
    the steady (compiled, tape recorded) wall time, which must reproduce
    the first run bitwise. Returns (session, {"run": report}, problems)."""
    from repro import api
    sess = api.Session(scn)
    problems = []
    what = f"{label}/run"
    with CompileClock() as clock:
        t0 = time.perf_counter()
        first = sess.run(n_frames)
        t1 = time.perf_counter()
        rec = {"phase": what, "backend": sess.engine.tparams.backend,
               "streams": scn.n_streams, "frames": n_frames,
               "first_s": t1 - t0, "compile_s": clock.seconds}
        if repeat:
            again = sess.run(n_frames)
            rec["steady_s"] = time.perf_counter() - t1
            p = compare(again, first, f"{what} repeat", bitwise=True)
            rec["repeat_bitwise"] = not p
            problems += p
    emit(rec)
    problems += check_report(first, scn.n_streams, n_frames, what)
    return sess, {"run": first}, problems


def custom_calls(engine, n_frames: int) -> int:
    """``tpu_custom_call`` (Pallas kernel) count of the compiled fleet
    step."""
    import jax.numpy as jnp
    marker = 'custom_call_target="tpu_custom_call"'
    step = engine._step.lower(
        engine._init_state(),
        engine._frame_inputs(engine._stacked(n_frames), 0),
        jnp.zeros((engine.n_streams,), bool), jnp.int32(0)).compile()
    return step.as_text().count(marker)


def chip_checks(engine, n_frames: int) -> list:
    """The engine serves the Pallas backend, and its compiled step holds
    the kernels."""
    import jax
    backend = engine.tparams.backend
    calls = custom_calls(engine, n_frames)
    stats = jax.devices()[0].memory_stats() or {}
    emit({"phase": "A/chip-default", "backend": backend,
          "tpu_custom_calls": calls,
          "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    problems = []
    if backend != "pallas":
        problems.append(f"default backend resolved to {backend!r}, "
                        f"not 'pallas'")
    if not calls:
        problems.append("no Pallas kernel in the compiled step")
    return problems


def reference_phase(chip: dict, scn_ref, n_frames: int, cpu_device) -> list:
    """``scn_ref`` on the default device and pinned to ``cpu_device``; the
    ``chip`` reports must match both in kinds and quality."""
    import jax
    _, ref_chip, problems = serve(scn_ref, n_frames, "A/chip-ref")
    with jax.default_device(cpu_device):
        _, ref_cpu, p = serve(scn_ref, n_frames, "A/cpu-ref", repeat=False)
    problems += p
    problems += compare(chip["run"], ref_cpu["run"],
                        "A/run: chip-default vs cpu-ref")
    problems += compare(chip["run"], ref_chip["run"],
                        "A/run: chip-default vs chip-ref")
    return problems


def sharded_phase(scn, n_devices: int, n_frames: int) -> list:
    """The fleet sharded over ``n_devices`` against the same fleet on one
    device, through ``run``: bit for bit."""
    import dataclasses
    _, one, problems = serve(scn, n_frames, "S/mesh=None", repeat=False)
    _, many, p = serve(dataclasses.replace(scn, mesh=n_devices), n_frames,
                       f"S/mesh={n_devices}", repeat=False)
    problems += p
    problems += compare(many["run"], one["run"],
                        f"S/run: mesh={n_devices} vs mesh=None",
                        bitwise=True)
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase, on four chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run this script "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{platform!r} ({devices[0].device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro import api
    from repro.launch.compile_cache import use_compile_cache
    emit({"compile_cache": use_compile_cache(), "jax": jax.__version__,
          "platform": platform, "kind": devices[0].device_kind,
          "count": len(devices)})

    if args.chips == 4:
        problems = sharded_phase(api.scenario(SHARDED_PRESET, seed=0), 4,
                                 N_FRAMES)
    else:
        sess, chip, problems = serve(
            api.scenario("kitti-urban", seed=0, **KITTI_SENSOR), N_FRAMES,
            "A/chip-default")
        problems += chip_checks(sess.engine, N_FRAMES)
        del sess
        problems += reference_phase(
            chip, api.scenario("kitti-urban", seed=0, backend="ref",
                               **KITTI_SENSOR),
            N_FRAMES, jax.devices("cpu")[0])
    if problems:
        for p in problems:
            print(f"chip_smoke: FAILED: {p}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
