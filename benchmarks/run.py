"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,value,derived`` CSV rows (see benchmarks/common.emit).
Usage: PYTHONPATH=src python -m benchmarks.run [--scenario NAME]
                                               [--policy NAME] [module ...]

--scenario / --policy (backed by the repro.api registries) swap the
Scenario preset / scheduler policy every engine-driven benchmark runs
under, so sweeps like ``--scenario sparse-lidar --policy periodic(8)``
need no code edits. ``--list`` prints every module with its one-line
description; ``--trace`` / ``--metrics`` / ``--audit`` turn on repro.obs
observability for every Session the benchmarks build.
"""
from __future__ import annotations

import argparse
import time

from benchmarks import common
from repro.launch.compile_cache import use_compile_cache

MODULES = [
    "fig2_edge_only",
    "fig3_cloud_only",
    "table3_compression",
    "fig13_e2e",
    "fig14_acceleration",
    "table4_ablation",
    "fig15_breakdown",
    "fig16_sensitivity",
    "fig17_efficiency",
    "fleet_scaling",
    "kernel_backends",
    "sweep",
    "roofline",
]


def describe(name: str) -> str:
    """A module's one-line description: its docstring's first line."""
    import importlib
    mod = importlib.import_module(f"benchmarks.{name}")
    doc = (mod.__doc__ or "").strip()
    return doc.splitlines()[0].rstrip(".") if doc else "(no description)"


def main() -> None:
    import importlib
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("modules", nargs="*", metavar="module",
                    help=f"benchmark modules to run (default: all of "
                         f"{', '.join(MODULES)})")
    ap.add_argument("--list", action="store_true",
                    help="list available benchmark modules and exit")
    common.add_scenario_args(ap)
    common.add_obs_args(ap)
    args = ap.parse_args()
    if args.list:
        width = max(len(m) for m in MODULES)
        for m in MODULES:
            print(f"{m:<{width}}  {describe(m)}")
        return
    unknown = [m for m in args.modules if m not in MODULES]
    if unknown:
        ap.error(f"unknown module(s) {', '.join(unknown)}; available: "
                 f"{', '.join(MODULES)}")
    use_compile_cache()
    common.set_defaults(args.scenario, args.policy)
    common.set_obs(common.obs_from_args(args))

    wanted = args.modules or MODULES
    print("name,value,derived")
    for name in wanted:
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.time()
        mod.run()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)


if __name__ == '__main__':
    main()
