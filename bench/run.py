"""Run one cell of BENCHMARK.json on the chip this process holds.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets up, warms up every shape the cell uses, measures for ``--seconds``,
checks the output against the plain reference and prints one JSON line.
With no TPU, too few chips or interpreted kernels it exits non-zero and
prints no result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
