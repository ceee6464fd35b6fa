"""The benchmark's tests import its harness and the program under test."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parents[1] / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
