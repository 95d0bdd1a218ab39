"""Chip benchmark entry point.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
      --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU this process finds, and
prints one JSON line last on standard output (see ``harness.py``).  It
exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""

import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import main  # noqa: E402

if __name__ == "__main__":
    main(t_process=T_PROCESS)
