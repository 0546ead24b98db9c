"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload kitti-hdl64.fleet16 --seed 7 \\
        --seconds 40 --trace 0

From the root of a checkout that holds ``BENCHMARK.json``, ``bench/`` and
the program under ``src/``. The last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device`` and last
``compared``, each compared number beside its limit; the same numbers end
stderr. Exits 1 and prints no result without a TPU, with fewer chips
than the cell asks for, or without the program.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402

if __name__ == "__main__":
    # One fixed half of the cores, set before any thread starts so that
    # every thread inherits it. Unpinned, about one process in three on a
    # one-chip v5e host had some drives stalled on the host by 0.05-0.8 s
    # (about 3% off its throughput); pinned, none of five.
    _cpus = sorted(os.sched_getaffinity(0))
    os.sched_setaffinity(0, _cpus[:max(1, len(_cpus) // 2)])

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_START,
                             log=lambda m: print(m, file=sys.stderr,
                                                 flush=True))
    except harness.HarnessError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in result["compared"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
