"""The benchmark harness: one cell, one seed, one run.

A run serves one cell of ``BENCHMARK.json``: a fleet configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``). It records the cell's tapes from the
seed with the benchmark's own generator, builds one ``FleetEngine`` from
an ``api.Scenario`` the way ``api.Session`` builds a fleet engine, on the
chip layout the configuration states (``"mesh": {"streams": M}``: the
stream axis sharded over M chips; absent, one chip), hands it the tapes,
warms it up with one drive, and then calls
``FleetEngine.run(W)`` ("a drive": W rounds, each one frame of every
vehicle) back to back until the window's seconds have passed. The loop is
closed: a round is due when the previous round's results are on the
host. Every drive replays the same tape from a fresh state, so every
drive does the same work.

After the window the drives' outputs are compared with the plain
reference (``bench.reference``) and the comparison's numbers are printed
beside their limits (``bench/limits/<cell>.json``). With ``trace`` the
same untraced window is followed by ``trace_drives`` drives under the JAX
profiler; the cell's per-layer metrics, each read by its own reader
``bench/metrics/<metric>.py``, take the program's spans from the
untraced window and the device's numbers from the trace.

Everything a cell needs is found by name, so a new cell, traffic mix or
metric is new files only.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
DRIVE_SPAN = "bench/drive"
# The reference computes every matrix product in the precision the
# configuration states; the program pins Precision.HIGHEST, so that is the
# one precision a configuration may state.
PRECISIONS = ("highest",)
# The harness drives rounds back to back: a round is due when the previous
# one's results are on the host. An open loop needs an arrival hook.
LOOPS = ("closed",)


class HarnessError(Exception):
    """The run cannot produce a result (missing files, wrong device)."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration, traffic
    mix, limits and metric names, all found by name."""

    def __init__(self, name: str, root: pathlib.Path = ROOT):
        bench_file = root / "BENCHMARK.json"
        if not bench_file.is_file():
            raise HarnessError(f"no BENCHMARK.json in {root}")
        spec = load_json(bench_file)
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise HarnessError(f"unknown workload {name!r}; cells: "
                               f"{sorted(cells)}")
        self.root = root
        entry = cells[name]
        configs = {c["name"]: c for c in spec["configs"]}
        self.config = load_json(root / configs[entry["config"]]["file"])
        self.traffic = load_json(
            root / "bench" / "traffic" / f"{entry['traffic']}.json")
        self.limits = load_json(root / "bench" / "limits" / f"{name}.json")
        if self.config["precision"] not in PRECISIONS:
            raise HarnessError(f"precision {self.config['precision']!r} is "
                               f"not run; the harness runs {PRECISIONS}")
        if self.traffic["loop"] not in LOOPS:
            raise HarnessError(f"loop {self.traffic['loop']!r} is not run; "
                               f"the harness runs {LOOPS}")
        self.chips = int(entry["chips"])
        mesh = self.config.get("mesh", {"streams": 1})
        if not isinstance(mesh, dict) or set(mesh) != {"streams"}:
            raise HarnessError(f"mesh {mesh!r} is not run; the harness "
                               f"shards the stream axis alone")
        self.mesh = int(mesh["streams"])
        if self.mesh != self.chips:
            raise HarnessError(f"the configuration lays the streams over "
                               f"{self.mesh} chip(s), the cell asks for "
                               f"{self.chips}")
        if self.streams % self.mesh:
            raise HarnessError(f"{self.streams} streams do not divide over "
                               f"a mesh of {self.mesh} chips")
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]

    @property
    def streams(self) -> int:
        return int(self.traffic["streams"])

    @property
    def rounds(self) -> int:
        return int(self.traffic["rounds_per_drive"])

    def scene_fields(self) -> dict:
        """The program's SceneConfig fields for this cell."""
        sensor, scene = self.config["sensor"], self.config["scene"]
        return dict(n_points=sensor["n_points"], img_h=sensor["img_h"],
                    img_w=sensor["img_w"], dt=sensor["dt"],
                    max_obj=scene["max_obj"],
                    density_scale=scene["density_scale"],
                    mean_objects=self.traffic["mean_objects"])


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

def record_tapes(cell: Cell, seed: int):
    """The cell's S tapes of W frames, from the benchmark's generator: the
    traffic's S vehicle streams (recorded from its ``scene_seed``), given
    to the fleet's slots in an order drawn from the run's seed. Every seed
    serves the same scenes, so every run does the same work."""
    from bench.gen import scenes, tape
    cfg = scenes.SceneConfig(**cell.scene_fields())
    tapes = tape.record_fleet_tapes(
        cfg, cell.config["deployment"]["detector"]["name"], cell.rounds,
        cell.streams, seed=int(cell.traffic["scene_seed"]))
    order = np.random.default_rng(seed).permutation(cell.streams)
    return [tapes[i] for i in order]


def build_engine(cell: Cell, seed: int, tapes):
    """One FleetEngine with the arguments ``api.Session`` gives a fleet
    scenario, plus the benchmark's tapes. A cell on M > 1 chips passes
    ``mesh=M``: a 1-D ``streams`` mesh, each chip stepping S/M streams."""
    from repro import api
    from repro.fleet.cloud import CloudBatcherConfig
    from repro.fleet.engine import FleetEngine
    from repro.obs import ObsConfig
    from repro.serving import tape as tape_lib
    dep = cell.config["deployment"]
    scn = api.scenario(
        "kitti-urban", n_streams=cell.streams, seed=seed,
        detector=dep["detector"]["name"], trace=dep["uplink"]["name"],
        device=dep["edge_device"], policy=cell.traffic["policy"],
        cloud=CloudBatcherConfig(n_gpus=dep["cloud"]["n_gpus"],
                                 marginal=dep["cloud"]["marginal"],
                                 max_batch=dep["cloud"]["max_batch"]),
        **({"mesh": cell.mesh} if cell.mesh > 1 else {}),
        **cell.scene_fields())
    return FleetEngine(
        scn.scene, scn.detector, n_streams=scn.n_streams, trace=scn.trace,
        mode=scn.mode, use_fos=scn.use_fos, use_tba=scn.use_tba,
        tparams=scn.tparams, sparams=scn.scheduler_params(), seed=scn.seed,
        comp=scn.comp, cloud_cfg=scn.cloud, backend=scn.backend,
        device=scn.device, obs=ObsConfig(trace=True), mesh=scn.mesh,
        tapes=[tape_lib.FrameTape(*t) for t in tapes])


class CompileCounter:
    """Backend compiles while active, from JAX's monitoring events."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _on(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------------------
# Drives and their rounds
# ---------------------------------------------------------------------------

def round_latencies(spans: List[dict], wall_s: float) -> np.ndarray:
    """Per-round latency of one drive from the program's spans: round
    k >= 1 ends when its ``fleet/fetch`` span ends and starts when round
    k-1's did; round 0 takes the rest of the drive's wall time (its
    prologue and epilogue), so the rounds add up to the wall time."""
    ends = [s["t0"] + s["dur"] for s in spans if s["name"] == "fleet/fetch"]
    lat = np.diff(np.asarray(ends, np.float64))
    return np.concatenate([[wall_s - lat.sum()], lat])


class Drive:
    """What one ``FleetEngine.run(W)`` returned and how long it took."""

    COLS = ("kind", "latency_s", "onboard_s", "f1", "precision", "recall")

    def __init__(self, report, wall_s: float):
        self.wall_s = wall_s
        self.out = {c: np.asarray(getattr(report, c)) for c in self.COLS}
        spans = report.obs.measured
        self.rounds = round_latencies(spans, wall_s)
        self.fetch_s = np.asarray([s["dur"] for s in spans
                                   if s["name"] == "fleet/fetch"])
        self.finite = all(np.isfinite(self.out[c]).all()
                          for c in self.COLS[1:])


def run_drive(engine, rounds: int) -> Drive:
    import jax
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(DRIVE_SPAN):
        report = engine.run(rounds)
    return Drive(report, time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# The comparison that decides `correct`
# ---------------------------------------------------------------------------

QUALITY = ("f1", "precision", "recall")
TIMING = ("latency_s", "onboard_s")


def inconsistent(out: Dict[str, np.ndarray]) -> np.ndarray:
    """Stream-frames whose answers contradict each other: F1 is not the
    harmonic mean of precision and recall (or, with both 0, not 0 or the
    empty frame's 1); an anchor with an on-board time; another frame whose
    latency is not its positive on-board time."""
    f1, p, r = out["f1"], out["precision"], out["recall"]
    s = p + r
    hm = np.where(s > 0, 2 * p * r / np.where(s > 0, s, 1), 0.0)
    bad = np.where(s > 0, np.abs(f1 - hm) > 1e-4,
                   (np.abs(f1) > 1e-4) & (np.abs(f1 - 1) > 1e-4))
    anchor = out["kind"] == "anchor"
    lat, onb = out["latency_s"], out["onboard_s"]
    bad |= anchor & (onb != 0)
    bad |= ~anchor & ((onb <= 0) | (lat != onb))
    return bad


def compare(drives: List[Dict[str, np.ndarray]],
            ref: Dict[str, np.ndarray]) -> Dict[str, float]:
    """In the worst drive, the number of stream-frames whose kind differs
    from the reference's, whose F1, precision or recall differs by more
    than 1e-4, whose latency or on-board time differs by more than 1e-5
    of the reference's, and whose answers contradict each other."""
    worst = {"kind_mismatch": 0, "quality_mismatch": 0,
             "timing_mismatch": 0, "answer_inconsistent": 0}
    for out in drives:
        qual = np.zeros(out["kind"].shape, bool)
        for c in QUALITY:
            qual |= ~np.isclose(out[c], ref[c], rtol=0.0, atol=1e-4)
        tim = np.zeros(out["kind"].shape, bool)
        for c in TIMING:
            tim |= ~np.isclose(out[c], ref[c], rtol=1e-5, atol=1e-9)
        got = {"kind_mismatch": int((out["kind"] != ref["kind"]).sum()),
               "quality_mismatch": int(qual.sum()),
               "timing_mismatch": int(tim.sum()),
               "answer_inconsistent": int(inconsistent(out).sum())}
        worst = {k: max(worst[k], got[k]) for k in worst}
    return {k: float(v) for k, v in worst.items()}


def reference_constants() -> dict:
    """The configuration's cluster size and RANSAC hypotheses."""
    from bench.reference import step
    d = step.Params._field_defaults
    return {"pts_per_obj": d["pts_per_obj"],
            "ransac_iters": d["ransac_iters"]}


def reference_drive(cell: Cell, tapes, seed: int,
                    precision: Optional[str] = None) -> Dict[str, np.ndarray]:
    """The plain reference over the same tapes (one drive), its matrix
    products in ``precision`` (by default the configuration's)."""
    from bench.gen import scenes
    from bench.gen.tape import stack_tapes
    from bench.reference import host, step
    cfg = scenes.SceneConfig(**cell.scene_fields())
    tr, p = scenes.make_calibration(cfg)
    prm = step.Params(img_h=cfg.img_h, img_w=cfg.img_w, max_obj=cfg.max_obj,
                      precision=precision or cell.config["precision"])
    return host.drive(stack_tapes(tapes), prm, tr, p,
                      cell.config["deployment"], cfg.dt, seed)


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    missing = set(limits) - set(numbers)
    if missing:
        raise HarnessError(f"no number for the limits {sorted(missing)}")
    return all(numbers[k] <= limits[k] for k in limits)


# ---------------------------------------------------------------------------
# Per-layer metric readers
# ---------------------------------------------------------------------------

def load_reader(root: pathlib.Path, name: str):
    """``bench/metrics/<name>.py``'s ``read(ctx)``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise HarnessError(f"no reader bench/metrics/{name}.py for the "
                           f"per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, ctx: dict) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        value = load_reader(cell.root, m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def step_args(engine, rounds: int) -> tuple:
    """The fleet step's arguments for round 0, laid out as the engine
    lays them out: under a mesh every (S, ...) one stream-sharded."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    return (engine._init_state(),
            engine._frame_inputs(engine._stacked(rounds), 0),
            engine._put(np.zeros(engine.n_streams, bool), P("streams")),
            jnp.int32(0))


def program_names(hlo_text: str) -> dict:
    """A compiled program's module name and, per Pallas kernel, the names
    its ``tpu_custom_call`` instructions carry in the trace (found by the
    kernel wrapper's ``jit`` scope in their metadata, which a ``shard_map``
    or ``named_scope`` around it leaves in place)."""
    import re
    module = re.search(r"HloModule (\S+?),", hlo_text)
    kernels: Dict[str, List[str]] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        inst = re.match(r"\s*(?:ROOT )?%?(\S+) = ", line)
        scope = re.search(r'op_name="[^"]*?jit\((\w+)\)\)*/pallas_call',
                          line)
        if inst and scope:
            kernels.setdefault(scope.group(1), []).append(inst.group(1))
    return {"step_module": module.group(1) if module else None,
            "kernel_ops": kernels}


def step_program_names(engine, rounds: int) -> dict:
    """:func:`program_names` of the engine's compiled fleet step."""
    return program_names(
        engine._step.lower(*step_args(engine, rounds)).compile().as_text())


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def device_info(n_chips: int, check: bool) -> dict:
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if check and info["platform"] != "tpu":
        raise HarnessError(f"needs a TPU, but JAX found platform "
                           f"{info['platform']!r} ({info['kind']})")
    if check and len(devices) < n_chips:
        raise HarnessError(f"the cell needs {n_chips} chips, JAX found "
                           f"{len(devices)}")
    return info


def profile_options():
    """No Python tracer, host tracer at level 1: the annotations are
    kept, and the runtime's per-chunk host events (millions per drive
    while the inputs are laid out for the device) are not."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def memory_peak_bytes(n_chips: int) -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:n_chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def use_compile_cache() -> str:
    """The program's persistent compilation cache
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache`` in the
    checkout). Every program is cached, however quick its compile: the
    fleet's many small eager programs would otherwise compile in every
    run's set-up."""
    import jax
    from repro.launch.compile_cache import use_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def reader_shapes(cell: Cell) -> dict:
    """The shapes one chip steps in a round: its S/M streams (a mesh of M
    chips shards the stream axis), the sensor's sizes and the reference's
    constants. The trace's op times are averaged over the chips, so a
    kernel's work at these shapes over its time is one chip's share."""
    return {"streams": cell.streams // cell.mesh, **cell.scene_fields(),
            **reference_constants()}


def rounds_in_trace(reduced: dict, step_module: Optional[str],
                    chips: int) -> int:
    """Rounds whose fleet step the trace holds: the step module's runs in
    the traced window, per chip. The per-layer readers divide the device
    time the trace recorded by these, not by the rounds driven: a v5e
    trace once held 26 of 32 (every op's time 26/32 of the usual)."""
    runs = sum(v[0] for k, v in reduced["modules"].items()
               if step_module and (k == step_module
                                   or k.startswith(step_module + "(")))
    return runs // chips


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, check_device: bool = True,
        root: pathlib.Path = ROOT, log=print) -> dict:
    """One run; returns the result object (the last line's JSON).

    With ``trace`` each per-layer reader gets a ``ctx`` holding the
    untraced window's drives and seconds, the reduced trace (device times
    averaged over the cell's chips), the step's module and kernel names,
    ``rounds_traced`` (:func:`rounds_in_trace`, or the rounds driven under
    the profiler where the trace holds no step) and ``shapes``
    (:func:`reader_shapes`): the work of one chip, so that work over time
    is a per-chip share on every layout."""
    cell = Cell(workload, root)
    if not (root / "src" / "repro").is_dir():
        raise HarnessError(f"no program under {root / 'src'}")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    device = device_info(cell.chips, check_device)
    import jax
    log(f"compile cache: {use_compile_cache()}")

    tapes = record_tapes(cell, seed)
    engine = build_engine(cell, seed, tapes)
    run_drive(engine, cell.rounds)          # warm-up: compiles every shape
    setup_s = time.perf_counter() - t_start

    drives: List[Drive] = []
    traced: List[Drive] = []
    profile_dir = root / "bench_out" / "profile"
    with CompileCounter() as compiles:
        w0 = time.perf_counter()
        while time.perf_counter() - w0 < seconds:
            drives.append(run_drive(engine, cell.rounds))
        window_s = time.perf_counter() - w0
        if trace:
            # The profiler slows the host's input path (a process's first
            # session most: a 2.6 s drive took 7.7 s and 9.3 s on a v5e),
            # so the traced drives give only device numbers; the spans and
            # the idle share's clock come from the untraced window above.
            shutil.rmtree(profile_dir, ignore_errors=True)
            jax.profiler.start_trace(str(profile_dir),
                                     profiler_options=profile_options())
            try:
                for _ in range(int(cell.traffic["trace_drives"])):
                    traced.append(run_drive(engine, cell.rounds))
            finally:
                jax.profiler.stop_trace()
            log(f"traced drives end at {time.perf_counter() - t_start:.1f} s")
    log(f"backend compiles inside the window: {compiles.count} "
        f"({compiles.seconds:.6f} s)")
    rounds = np.concatenate([d.rounds for d in drives])
    fetch = np.concatenate([d.fetch_s for d in drives])
    log(f"untraced window: {len(rounds)} rounds in {window_s!r} s, mean "
        f"round {1e3 * float(rounds.mean())!r} ms, of it in fleet/fetch "
        f"{1e3 * float(fetch.mean())!r} ms; drives (s): "
        f"{[round(d.wall_s, 4) for d in drives]}")
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
    served = drives + traced
    attempted = len(served) * cell.streams * cell.rounds
    failed = sum(cell.streams * cell.rounds for d in served if not d.finite)

    if trace:
        from bench import trace as trace_lib
        reduced = trace_lib.reduce(trace_lib.load_profile(profile_dir),
                                   n_chips=cell.chips)
        shutil.rmtree(profile_dir, ignore_errors=True)
        log(f"trace read at {time.perf_counter() - t_start:.1f} s")
        names = step_program_names(engine, cell.rounds)
        driven = len(traced) * cell.rounds
        found = rounds_in_trace(reduced, names["step_module"], cell.chips)
        log(f"the trace holds the steps of {found} of the {driven} rounds "
            f"driven under the profiler")
        metrics = read_per_layer(cell, {
            "cell": cell, "drives": drives,
            "window_s": window_s, "trace": reduced,
            "device_kind": device["kind"],
            "rounds_traced": found or driven,
            "shapes": reader_shapes(cell), **names})
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    else:
        metrics = end_to_end(cell, drives, window_s, setup_s)
    outs = [d.out for d in served]
    del engine, drives, traced, served
    gc.collect()

    log(f"reference starts at {time.perf_counter() - t_start:.1f} s")
    ref = reference_drive(cell, tapes, seed)
    log(f"reference ends at {time.perf_counter() - t_start:.1f} s")
    numbers = compare(outs, ref)
    numbers["compiles_in_window"] = float(compiles.count)
    correct = failed == 0 and verdict(numbers, cell.limits)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = {k: {"value": numbers[k],
                              "limit": cell.limits.get(k)}
                          for k in numbers}
    return result


def end_to_end(cell: Cell, drives: List[Drive], window_s: float,
               setup_s: float) -> Dict[str, dict]:
    """Stream-frames completed over the window's seconds; the 95th
    percentile of every round in the window; the set-up time."""
    rounds = np.concatenate([d.rounds for d in drives])
    values = {
        "setup_s": setup_s,
        "stream_frames_per_s": len(drives) * cell.streams * cell.rounds
        / window_s,
        "round_p95_ms": 1e3 * float(np.percentile(rounds, 95)),
    }
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    return {k: {"value": v, "unit": units[k]} for k, v in values.items()
            if k in units}
