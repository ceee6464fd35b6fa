#!/usr/bin/env python3
"""The chip benchmark's command: one run of one cell.

    python3 benchmarks/chip/run.py --workload NAME --seed N --seconds S --trace 0|1

Run from the root of a checkout on a machine with the cell's TPU chips; the
program under test is imported from ``src/``.  See ``chipbench/harness.py``.
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main())
