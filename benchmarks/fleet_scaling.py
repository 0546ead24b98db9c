"""Fleet scaling — throughput and per-stream latency vs fleet size S.

No paper figure: this benchmarks the fleet serving subsystem (repro.fleet)
that extends Moby beyond the paper's single vehicle. For S in {1, 4, 16,
64} concurrent streams it reports:

* fleet frames/sec and per-stream-frame wall time of the device-resident
  ``lax.scan`` mode (one dispatch for the whole run) — batching amortizes
  dispatch + small-op overhead, so per-stream-frame time falls as S grows;
* mean anchor latency — shared-uplink fair-sharing plus cloud-batcher
  queueing make anchors slower for everyone as the fleet grows;
* a dispatch-overhead reference: the single-stream Python-loop MobyEngine
  on the same tape (~3 jit calls + a stats fetch per frame);
* a heterogeneity grid — S x device-mix x cloud-GPU-pool: per-device-class
  p95 modeled latency (Orin-class streams should beat TX2-class ones) and
  anchor latency vs pool size (queueing relief as G grows).

Sharded megafleet grid (``--sharded``): S in {256, 1024, 4096} through
``run_scan`` on a stream-axis device mesh, one CSV row per (S, devices)
point with a ``throughput_sf_per_s`` column — run it at several
``--devices N`` values (N virtual CPU devices via
``--xla_force_host_platform_device_count``, set before JAX initializes)
and the scaling curve lands in one CSV (``--csv``, append mode).
"""
from __future__ import annotations

import argparse
import csv
import os
import sys
import time

# --devices N virtualizes an N-device CPU host. XLA reads the flag when
# the backend initializes, which the imports below trigger — so it must
# land in the environment first, before any JAX-importing module.
if "--devices" in sys.argv:
    _n = int(sys.argv[sys.argv.index("--devices") + 1])
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count"
                                 f"={_n}").strip()

if __name__ == "__main__":      # direct `python benchmarks/fleet_scaling.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import jax

from benchmarks.common import emit, make_session
from repro import api
from repro.fleet import CloudBatcherConfig
from repro.launch.compile_cache import use_compile_cache
from repro.serving import engine as engine_lib
from repro.serving import tape as tape_lib

S_LIST = (1, 4, 16, 64)
FRAMES = 24
REPEATS = 3

# Heterogeneity grid: device mixes x cloud pool sizes at a fixed S.
HET_S = 16
MIXES = {
    "tx2": "jetson_tx2",
    "mixed-75-25": {"jetson_tx2": 0.75, "jetson_orin": 0.25},
    "orin": "jetson_orin",
}
G_LIST = (1, 4)

# Sharded megafleet grid (ISSUE #9): large-S run_scan on a streams mesh.
SHARD_S_LIST = (256, 1024, 4096)
SHARD_FRAMES = 8

# Lean scene so per-frame device work is dispatch/overhead-bound — the
# regime fleet batching targets (full-size scenes are exercised by
# fig13/fig14). Expressed as overrides on the smoke preset.
LEAN = dict(n_points=512, img_h=32, img_w=104, density_scale=2500.0)
# Megafleet scene: fleet-256-congested's ultra-lean frames, so S=4096
# fits comfortably and the per-frame math stays device-bound.
MEGA = dict(n_points=256, img_h=32, img_w=104, max_obj=4, mean_objects=2,
            density_scale=1500.0)

CSV_FIELDS = ("s", "devices", "frames", "wall_s", "throughput_sf_per_s",
              "per_stream_frame_ms", "mean_anchor_latency_ms", "mean_f1")


def _best_wall(fn, repeats: int = REPEATS) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def sharded_grid(s_list=SHARD_S_LIST, frames=SHARD_FRAMES, repeats=2,
                 csv_path=None):
    """One ``run_scan`` point per fleet size on a ``mesh="auto"`` streams
    mesh (unsharded when the host has 1 device, so the same command is the
    baseline leg). Returns the rows; ``csv_path`` appends them so curves
    across ``--devices`` values accumulate into one file."""
    n_dev = len(jax.devices())
    rows = []
    for s in s_list:
        sess = make_session("fleet-256-congested", n_streams=s, seed=3,
                            mesh="auto", **MEGA)
        sess.run(frames, scan=True)        # records tapes + compiles
        wall = _best_wall(lambda: sess.run(frames, scan=True),
                          repeats=repeats)
        rep = sess.run(frames, scan=True)
        row = {
            "s": s,
            "devices": sess.engine.n_shards if n_dev > 1 else 1,
            "frames": frames,
            "wall_s": round(wall, 4),
            "throughput_sf_per_s": round(s * frames / wall, 1),
            "per_stream_frame_ms": round(1e3 * wall / (s * frames), 4),
            "mean_anchor_latency_ms":
                round(1e3 * rep.mean_anchor_latency, 1),
            "mean_f1": round(rep.mean_f1, 3),
        }
        rows.append(row)
        emit(f"fleet_scaling/sharded/S{s}/D{row['devices']}"
             f"/throughput_sf_per_s", row["throughput_sf_per_s"],
             "stream-frames/sec on the streams mesh")
    if csv_path:
        new = not os.path.exists(csv_path)
        with open(csv_path, "a", newline="") as f:
            w = csv.DictWriter(f, fieldnames=CSV_FIELDS)
            if new:
                w.writeheader()
            w.writerows(rows)
    return rows


def run() -> None:
    per_sf_ms = {}
    cfg = api.scenario("smoke", seed=3, **LEAN).scene
    for s in S_LIST:
        sess = make_session("smoke", n_streams=s, seed=3, **LEAN)
        res = sess.run(FRAMES, scan=True)   # records tapes + compiles
        best = _best_wall(lambda: sess.run(FRAMES, scan=True))
        per_sf_ms[s] = 1e3 * best / (s * FRAMES)
        emit(f"fleet_scaling/S{s}/fleet_fps", round(s * FRAMES / best, 1))
        emit(f"fleet_scaling/S{s}/per_stream_frame_ms",
             round(per_sf_ms[s], 3))
        emit(f"fleet_scaling/S{s}/mean_f1", round(res.mean_f1, 3))
        emit(f"fleet_scaling/S{s}/mean_anchor_latency_ms",
             round(1e3 * res.mean_anchor_latency, 1),
             "grows with S: shared uplink + cloud queue")
    emit("fleet_scaling/amortization_speedup_s16_vs_s1",
         round(per_sf_ms[1] / per_sf_ms[16], 3), "accept: > 1.0")

    # Dispatch-overhead reference: same tape through the seed Python-loop
    # engine (per-frame jit calls + host sync) vs the one-dispatch fleet.
    tape = tape_lib.record_stream_tape(cfg, "pointpillar", FRAMES, seed=3)
    moby = engine_lib.MobyEngine(cfg, "pointpillar", seed=3, tape=tape)
    moby.run(FRAMES)                        # warm the jit caches
    best = _best_wall(lambda: moby.run(FRAMES), repeats=2)
    emit("fleet_scaling/moby_python_loop_per_frame_ms",
         round(1e3 * best / FRAMES, 3),
         "seed engine: ~3 dispatches + sync per stream-frame")

    run_heterogeneity()

    if len(jax.devices()) > 1:
        # Multi-device host (e.g. the CI leg's 8 virtual CPU devices):
        # add the sharded megafleet points at the small end of the grid.
        sharded_grid(s_list=(256,), frames=SHARD_FRAMES)


def run_heterogeneity() -> None:
    """S x device-mix x G: the per-stream profile vector and the cloud
    GPU pool, swept together (scan mode; adaptive policy so the
    per-stream offload budget is live). max_batch=4 makes the S=16
    anchor rounds span several chunks, so the pool actually queues."""
    for mix_name, spec in MIXES.items():
        for g in G_LIST:
            sess = make_session(
                "smoke", n_streams=HET_S, seed=3, policy="adaptive",
                device=spec, cloud=CloudBatcherConfig(n_gpus=g, max_batch=4),
                **LEAN)
            rep = sess.run(FRAMES, scan=True)
            tag = f"fleet_scaling/het/S{HET_S}/{mix_name}/G{g}"
            emit(f"{tag}/mean_anchor_latency_ms",
                 round(1e3 * rep.mean_anchor_latency, 1),
                 "non-increasing in G (pool relieves the cloud queue)")
            for dev, p95 in sorted(rep.device_p95_latency().items()):
                emit(f"{tag}/p95_latency_ms/{dev}", round(1e3 * p95, 2),
                     "per-device-class modeled tail")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=None,
                    help="virtualize an N-device CPU host (sets "
                         "--xla_force_host_platform_device_count before "
                         "JAX initializes)")
    ap.add_argument("--sharded", action="store_true",
                    help="run only the sharded megafleet grid")
    ap.add_argument("--s-list", type=int, nargs="+", default=None,
                    help=f"fleet sizes for --sharded "
                         f"(default {list(SHARD_S_LIST)})")
    ap.add_argument("--frames", type=int, default=SHARD_FRAMES)
    ap.add_argument("--csv", default=None,
                    help="append sharded-grid rows to this CSV")
    args = ap.parse_args(argv)
    use_compile_cache()
    print("name,value,derived")
    if args.sharded:
        sharded_grid(s_list=tuple(args.s_list or SHARD_S_LIST),
                     frames=args.frames, csv_path=args.csv)
    else:
        run()


if __name__ == "__main__":
    main()
