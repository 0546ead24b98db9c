"""Scenario x policy sweep: the accuracy/offload frontier in one CSV.

Crosses registered Scenario presets (sparse-lidar, dense-traffic,
lossy-uplink, ...) against registered scheduler policies (fos,
periodic(k), adaptive, ...) through the repro.api facade and concatenates
every run's per-frame rows — ``RunReport.to_csv()`` with the
scenario/policy provenance columns — into one CSV, plus one summary emit
row per (scenario, policy) cell.

    PYTHONPATH=src python -m benchmarks.sweep [--out sweep.csv]
        [--frames 32] [--scenarios A B ...] [--policies X Y ...] [--smoke]
        [--fleet] [--trace [PATH]] [--metrics [PATH]] [--audit [PATH]]

``--smoke`` is the CI entry point: one lean scenario, two policies, a
handful of frames. ``--fleet`` sweeps the fleet presets (S=16 congested,
S=64 heterogeneous) through ``Session.run`` and emits per-device
p95 modeled latency beside the per-frame CSV rows (whose ``device``
column carries each stream's profile). Also registered in
``benchmarks.run`` (module name ``sweep``) with a small default grid.
"""
from __future__ import annotations

import argparse
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.common import emit
from repro import api
from repro.launch.compile_cache import use_compile_cache

# Default grid: the scenario-diversity presets crossed against the paper
# policy, two periodic baselines bracketing its offload rate, and the
# profile-driven adaptive policy.
SCENARIOS = ("kitti-urban", "sparse-lidar", "dense-traffic", "lossy-uplink")
POLICIES = ("fos", "periodic(4)", "periodic(8)", "adaptive")

# Fleet grid (--fleet): S x device-mix x GPU-pool.
FLEET_SCENARIOS = ("fleet-16-congested", "fleet-64-mixed")
FLEET_POLICIES = ("fos", "adaptive")


def sweep(scenarios: Sequence[str] = SCENARIOS,
          policies: Sequence[str] = POLICIES, frames: int = 32,
          seed: int = 0, out: Optional[str] = None,
          obs: Optional[api.ObsConfig] = None) -> Tuple[str, List[Dict]]:
    """Run the grid; returns (csv_text, per-cell summary dicts) and
    optionally writes the CSV to ``out``. ``obs`` turns on repro.obs for
    every cell — each run exports its own trace/audit
    ({n}/{scenario}/{policy} path placeholders), metrics accumulate
    across the grid into one exposition."""
    parts: List[str] = []
    summaries: List[Dict] = []
    for scn_name in scenarios:
        for policy in policies:
            sess = api.Session(api.scenario(scn_name, policy=policy,
                                            seed=seed), obs=obs)
            rep = sess.run(frames)
            parts.append(rep.to_csv(header=not parts))
            s = rep.summary()
            summaries.append(s)
            emit(f"sweep/{scn_name}/{policy}/mean_f1",
                 round(s["mean_f1"], 4))
            emit(f"sweep/{scn_name}/{policy}/offload_rate",
                 round(s["offload_rate"], 4))
            emit(f"sweep/{scn_name}/{policy}/mean_latency_ms",
                 round(s["mean_latency_s"] * 1e3, 2))
            if rep.device is not None and len(set(rep.device)) > 1:
                # Heterogeneous fleet: the per-stream tail by device class
                # (slow streams should anchor on their own cadence, not
                # drag the fast class with them).
                for dev, p95 in sorted(rep.device_p95_latency().items()):
                    emit(f"sweep/{scn_name}/{policy}/p95_latency_ms/{dev}",
                         round(p95 * 1e3, 2))
    for scn_name in scenarios:
        cells = {s["policy"]: s for s in summaries
                 if s["scenario"] == scn_name}
        adap = cells.get("adaptive")
        if adap is None:
            continue
        dominated = [p for p, s in cells.items() if p != "adaptive"
                     and adap["mean_f1"] >= s["mean_f1"]
                     and adap["offload_rate"] <= s["offload_rate"]]
        emit(f"sweep/{scn_name}/adaptive_dominates",
             ";".join(dominated) or "none",
             "policies whose (accuracy, offload) point adaptive dominates")
    text = "".join(parts)
    if out:
        with open(out, "w") as f:
            f.write(text)
    return text, summaries


def run() -> None:
    """benchmarks.run entry point: a small default grid."""
    sweep(scenarios=("kitti-urban", "lossy-uplink"),
          policies=("fos", "periodic(4)", "adaptive"), frames=24)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="write the combined CSV here")
    ap.add_argument("--frames", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scenarios", nargs="*", default=list(SCENARIOS),
                    choices=api.list_scenarios())
    ap.add_argument("--policies", nargs="*", default=list(POLICIES))
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: lean scenario, two policies, 8 frames")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet grid: congested + heterogeneous presets, "
                         "per-device p95 emits")
    from benchmarks.common import add_obs_args, obs_from_args
    add_obs_args(ap)
    args = ap.parse_args()
    obs = obs_from_args(args)
    use_compile_cache()
    print("name,value,derived")
    if args.smoke:
        text, _ = sweep(scenarios=("smoke",), policies=("fos", "adaptive"),
                        frames=8, seed=args.seed, out=args.out, obs=obs)
    elif args.fleet:
        text, _ = sweep(scenarios=FLEET_SCENARIOS, policies=FLEET_POLICIES,
                        frames=args.frames, seed=args.seed,
                        out=args.out, obs=obs)
    else:
        text, _ = sweep(scenarios=args.scenarios, policies=args.policies,
                        frames=args.frames, seed=args.seed, out=args.out,
                        obs=obs)
    n_rows = len(text.strip().splitlines()) - 1
    print(f"# sweep CSV: {n_rows} frame rows"
          + (f" -> {args.out}" if args.out else ""))


if __name__ == "__main__":
    main()
