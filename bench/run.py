#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers compared with the reference, each beside its limit, are the last
lines of standard error.  Without a TPU, or with fewer chips than the cell
needs, it exits with 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the checkout root, not this directory, leads the path: bench/trace.py
# must not shadow the standard library's trace module
sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
