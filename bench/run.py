#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the accelerator JAX finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object.
Without a TPU whose peaks ``bench/peaks.py`` knows, or with fewer chips
than the cell asks for, the run exits with code 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
