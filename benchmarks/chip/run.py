"""The chip benchmark's command: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for.  Prints one JSON result line last on standard output, and the
numbers of the correctness check beside their limits last on standard
error.  Exits non-zero, with no result, off a TPU, on a device kind
missing from ``peaks.json``, with fewer chips than the cell needs, or
where the program or ``BENCHMARK.json`` is missing.  See ``harness.py``.
"""

import sys
import time

if __name__ == "__main__":
    T_START = time.perf_counter()
    import harness

    sys.exit(harness.main(sys.argv[1:], T_START))
