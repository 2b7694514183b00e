#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last stdout line.

  python3 bench/run.py --workload haswell.grid --seed 7 --seconds 40 --trace 0

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  See ``bench/lib/harness.py``.
"""
import time

T_PROCESS = time.monotonic()  # set-up is timed from here

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib.harness import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run(sys.argv[1:], T_PROCESS))
