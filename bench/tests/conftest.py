"""Rehearsals of the benchmark on the CPU, at sizes a test run holds:

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
