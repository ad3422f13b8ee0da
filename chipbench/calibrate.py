"""Readings for the correctness limits: the program's numbers on many
seeds and the control's on some, each a full run of the cell in one
process.

    python3 chipbench/calibrate.py --workload <name> --seeds 11,12,13 \\
        --control-seeds 11,12 --seconds <s>

The control is the plain reference put in the program's place and
computed one precision below what the configuration states (see each
driver); the run's own comparison judges it by the cell's limits.  One
JSON line per run goes to standard output.  The exit code is 1 when a
program run reads not correct or a control run reads correct.  This is a
tool for setting limits, not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from chipbench import run as run_lib

    runs = [(int(s), False) for s in args.seeds.split(",") if s]
    runs += [(int(s), True) for s in args.control_seeds.split(",") if s]
    wrong = 0
    for seed, control in runs:
        t0 = time.perf_counter()
        res = run_lib.run_cell(args.workload, seed, args.seconds, False,
                               t_start=t0, control=control)
        wrong += res["correct"] == control
        print(json.dumps({
            "seed": seed,
            "control": control,
            "correct": res["correct"],
            "checks": res["checks"],
            "metrics": res["metrics"],
        }), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
