"""Readings that set a cell's correctness limit, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... --seconds 10

For each seed it runs the cell as `run.py` does (set-up, a window at the
cell's own load, the sample of finished requests) and reads, over the same
sample, the widest gap of the served tokens and the widest gap of the
control: the reference computed with float8 weights, a precision below the
served bfloat16, put in the program's place.  Each row gives both readings
and the verdict each reaches through the run's own checks: the program's
has to be `correct`, the control's not.  The limit goes above the largest
served reading and below the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as bench_run
from benchlib import check
from benchlib.spec import Cell


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    cell = Cell(args.workload)
    rows = []
    for seed in args.seeds:
        try:
            res = bench_run.run(cell, seed, args.seconds, False, control=True)
        except bench_run.NoChip as e:
            print(f"FAIL: {e}", file=sys.stderr)
            return 3
        checks = {k: [c["value"], c["limit"]]
                  for k, c in res["checks"].items()}
        checks["served_gap_max"][0] = res["program_gap_max"]
        rows.append({"seed": seed,
                     "program_gap_max": res["program_gap_max"],
                     "control_gap_max": res["checks"]["served_gap_max"]
                     ["value"],
                     "compared_requests": checks["compared_requests"][0],
                     "program_correct": check.verdict(checks),
                     "control_correct": res["correct"]})
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "lower_program_max": max(r["program_gap_max"] for r in rows),
        "upper_control_min": min(r["control_gap_max"] for r in rows),
        "program_correct_all": all(r["program_correct"] for r in rows),
        "control_correct_any": any(r["control_correct"] for r in rows),
        "n_seeds": len(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
