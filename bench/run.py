#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

See ``bench/README.md`` and ``bench/harness/main.py``.  Exits non-zero,
and prints no result, without a TPU.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness.main import execute  # noqa: E402

if __name__ == "__main__":
    sys.exit(execute(sys.argv[1:], T_START))
