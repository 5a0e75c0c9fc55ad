"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only substr]... [--json path]

``--only`` is repeatable; a bench runs when ANY given substring matches its
name (CI: ``--only cluster_engine --only storage_fabric --only
control_plane --only mc_batch --only mc_wavefront --only
detector_backend --only fault_taxonomy --only fault_topology --only
sweep_service``).  Prints
``name,us_per_call,derived`` CSV; ``--json`` additionally writes the rows
as a JSON document (the CI artifact, which ``benchmarks.check_regression``
gates against the committed baseline) stamped with the git SHA, an
ISO-8601 UTC timestamp, the best-of-K setting, and — where a bench
declares one — the backend each row ran on, so the archived
``BENCH_*.json`` perf trajectory stays attributable across PRs.
``--repeat K`` makes every default-configured timing best-of-K.  Set
REPRO_BENCH_FAST=1 for the abbreviated suite (CI).  The roofline table
(from the dry-run artifacts) is appended when
benchmarks/results/dryrun_baseline.json exists.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import traceback
from datetime import datetime, timezone


def git_sha() -> str:
    """HEAD commit of the repo this benchmark file lives in ("unknown"
    outside a git checkout — the payload is still valid)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)))
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", action="append", default=None,
                    help="run benches whose name contains this substring; "
                         "repeatable (any match runs the bench)")
    ap.add_argument("--json", default=None,
                    help="also write rows as JSON to this path")
    ap.add_argument("--repeat", type=int, default=None, metavar="K",
                    help="best-of-K timing for every `timed` call that "
                         "does not set its own best_of (the min over K "
                         "rounds strips runner noise; the gated CI "
                         "groups already run their measured paths at "
                         "best-of-3)")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import bench_kernels, bench_ops, common
    from benchmarks.common import FAST

    if args.repeat is not None:
        common.BEST_OF = max(args.repeat, 1)

    benches = bench_ops.all_benches() + bench_kernels.all_benches()
    print("name,us_per_call,derived")
    failures = 0
    rows = []
    for bench in benches:
        if args.only and not any(o in bench.__name__ for o in args.only):
            continue
        try:
            for row in bench():
                # rows are (name, us, derived[, backend[, n_seeds]]) —
                # the 4th element records which detection/kernel backend
                # produced the timing, the 5th how many Monte Carlo
                # seeds the timing covers (so per-seed cost stays
                # computable from the archived JSON trajectory)
                name, us, derived = row[:3]
                backend = row[3] if len(row) > 3 else None
                n_seeds = row[4] if len(row) > 4 else None
                rows.append({"name": name, "us_per_call": us,
                             "derived": derived, "backend": backend,
                             "n_seeds": n_seeds})
                print(f"{name},{us:.1f},\"{derived}\"", flush=True)
        except Exception as e:
            failures += 1
            traceback.print_exc()
            rows.append({"name": bench.__name__, "us_per_call": None,
                         "derived": f"ERROR: {e}", "backend": None,
                         "n_seeds": None})
            print(f"{bench.__name__},nan,\"ERROR: {e}\"", flush=True)

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"fast": FAST, "only": args.only,
                       "best_of": common.BEST_OF,
                       "git_sha": git_sha(),
                       "generated_at": datetime.now(
                           timezone.utc).isoformat(timespec="seconds"),
                       "failures": failures, "rows": rows}, f, indent=2)
        print(f"json written to {args.json}", file=sys.stderr)

    # roofline summary (if the dry-run has produced artifacts)
    try:
        from benchmarks import roofline
        rs = [r for r in roofline.rows() if r.get("status") == "OK"
              and "dominant" in r]
        if rs and not args.only:
            worst = min(rs, key=lambda r: r["roofline_fraction"])
            best = max(rs, key=lambda r: r["roofline_fraction"])
            print(f"roofline_cells,{len(rs)},\"best={best['arch']}/"
                  f"{best['shape']}={best['roofline_fraction']:.2f} "
                  f"worst={worst['arch']}/{worst['shape']}="
                  f"{worst['roofline_fraction']:.2f} "
                  f"(full table: EXPERIMENTS.md §Roofline)\"")
    except Exception:
        pass

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
