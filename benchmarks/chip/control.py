"""Readings of the control and of the planted faults on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --program control|stale|drop_half|altered --seeds <n> [<n> ...]

Runs the cell as ``run.py`` does, with the program replaced by a stand-in
from ``controls.py``, once per seed in this one process, and prints each
run's compared numbers.  The benchmark's own runs never do this; it gives
the upper readings from which the limits in the configurations were set.
"""

import json
import sys
import time

if __name__ == "__main__":
    import argparse

    import harness

    ap = argparse.ArgumentParser(prog="control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program", required=True,
                    choices=("control", "stale", "drop_half", "altered"))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    bench = harness.prepare()
    import controls

    make = controls.FAULTS.get(args.program, controls.Control)
    for seed in args.seeds:
        out = harness.run_cell(bench, args.workload, seed, args.seconds,
                               False, time.perf_counter(), program=make)
        print(json.dumps({"program": args.program, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "check": out["check"]}), flush=True)
    sys.exit(0)
