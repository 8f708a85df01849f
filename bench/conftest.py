"""Puts the benchmark's modules and the program beside it on the path for
the benchmark's own tests."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
