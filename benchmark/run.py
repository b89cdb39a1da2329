"""The benchmark's command: one process, one cell, once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with as many chips as the cell asks for; anywhere else it says why
on standard error, prints no result and exits non-zero. The last line of
standard output is the result. See benchmark/README.md.
"""

import time

T0 = time.perf_counter()  # set-up is counted from here

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

if __name__ == "__main__":
    from benchmark.lib import harness

    sys.exit(harness.run_cell(sys.argv[1:], T0))
