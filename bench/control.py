#!/usr/bin/env python3
"""Read the numbers ``correct`` compares, for the program and for its
control, on several seeds of one cell in one process.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

The control is the plain reference put in the program's place and
computed a precision lower than the configuration states.  Each seed
prints one run's line, with the program's numbers under ``checks`` and
the control's under ``control``: the limits are set between the two.
The benchmark's own runs never run it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness.main import execute  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    args = ap.parse_args()
    rc = 0
    for i, seed in enumerate(args.seeds.split(",")):
        t0 = T_START if i == 0 else time.perf_counter()
        rc |= execute(["--workload", args.workload, "--seed", seed,
                       "--seconds", args.seconds, "--trace", "0"], t0,
                      with_control=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
