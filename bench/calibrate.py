"""Read the numbers that set a cell's limits, on the chip.

    python3 bench/calibrate.py --workload kitti-hdl64.fleet16 \\
        --seeds 11 12 13 14 --control-seeds 3

For each seed: record the cell's tapes, build the engine as a run does,
warm it up, serve one drive through ``FleetEngine.run`` (the window's own
call at the window's sizes), free it, and compare that drive with the
plain reference: the program's readings, whose largest over the seeds is
each number's lower reading. For the first ``--control-seeds`` seeds the
reference computed with three-pass and with one-pass bfloat16 matrix
products (``bench.reference.step.dot``) is compared in the program's
place: the controls' readings, whose smallest is an upper reading. One
JSON object per seed, then a summary, on stdout. Needs a TPU, like a
run.
"""
import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

CONTROLS = ("high", "default")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    try:
        harness.device_info(cell.chips, check=True)
    except harness.HarnessError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    harness.use_compile_cache()
    program, controls = [], {c: [] for c in CONTROLS}
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        tapes = harness.record_tapes(cell, seed)
        engine = harness.build_engine(cell, seed, tapes)
        harness.run_drive(engine, cell.rounds)
        drive = harness.run_drive(engine, cell.rounds)
        out = drive.out
        del engine, drive
        ref = harness.reference_drive(cell, tapes, seed)
        rec = {"seed": seed, "program": harness.compare([out], ref)}
        program.append(rec["program"])
        if i < args.control_seeds:
            for c in CONTROLS:
                got = harness.reference_drive(cell, tapes, seed, c)
                rec[c] = harness.compare([got], ref)
                controls[c].append(rec[c])
        rec["seconds"] = time.perf_counter() - t0
        print(json.dumps(rec), flush=True)
    summary = {"lower": {k: max(p[k] for p in program) for k in program[0]}}
    for c, reads in controls.items():
        if reads:
            summary[f"upper_{c}"] = {k: min(r[k] for r in reads)
                                     for k in reads[0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
