"""What-if service demo: the three answer layers, in process.

Builds a `WhatIfService` (no sockets — the HTTP front door is
``python -m repro.serve.http``), optionally precomputes the preset sweep
surface, then walks one query through each layer and shows the
provenance + latency waterfall:

    PYTHONPATH=src python examples/whatif_service.py
    PYTHONPATH=src python examples/whatif_service.py \
        --days 7 --seeds 32 --surface

With ``--surface``, near-miss queries (a node count / nvlink tilt /
checkpoint cadence inside the grid hull) answer by multilinear
interpolation in microseconds; everything off-grid runs a live stacked
engine pass, and repeats hit the canonical-key LRU.
"""
import argparse
import time

from repro.compile_cache import use_compile_cache
from repro.ops import get_scenario
from repro.serve import (ServiceConfig, SurfaceSpec, SweepSurface,
                         WhatIfService)


def show(label: str, answer) -> None:
    g = answer.distribution.get("goodput")
    dist = (f"goodput median {g['median']*100:.1f}% "
            f"[{g['q25']*100:.1f}, {g['q75']*100:.1f}]"
            if g else "(no goodput metric)")
    print(f"  {label:<34} source={answer.source:<8} "
          f"{answer.wall_s*1e3:>8.2f} ms  {dist}")


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--days", type=float, default=3.0,
                    help="campaign length for the demo queries (shorter "
                         "= faster engine passes)")
    ap.add_argument("--seeds", type=int, default=16,
                    help="Monte Carlo seeds per query")
    ap.add_argument("--surface", action="store_true",
                    help="precompute the preset sweep surface first and "
                         "demo the interpolated answer path")
    ap.add_argument("--window-ms", type=float, default=20.0,
                    help="request-coalescing window")
    args = ap.parse_args()

    base = get_scenario("paper-faithful").replace(duration_days=args.days)
    surface = None
    if args.surface:
        spec = SurfaceSpec(base=base, seeds=max(args.seeds, 8))
        print(f"building surface ({len(spec.n_nodes)}x{len(spec.tilts)}x"
              f"{len(spec.ckpt_hours)} grid x {spec.seeds} seeds)…")
        surface = SweepSurface(spec).build()
        print(f"  built in {surface.build_wall_s:.1f} s\n")

    svc = WhatIfService(ServiceConfig(window_s=args.window_ms / 1e3,
                                      default_seeds=args.seeds),
                        surface=surface)
    try:
        print(f"query waterfall ({args.seeds} seeds, "
              f"{args.days:g}-day campaigns):")
        show("first query (cold)", svc.query(base))
        show("repeat (cache or surface)", svc.query(base))
        tilted = base.replace(kind_weights={"nvlink": 2.5})
        show("nvlink x2.5", svc.query(tilted))
        if surface is not None:
            near = base.replace(n_nodes=71, job_nodes=68,
                                checkpoint_interval_h=3.0)
            show("71 nodes / 3.0 h (interpolated)", svc.query(near))
        off = base.replace(retry_policy="exp_backoff")
        show("exp-backoff retry (off-grid)", svc.query(off))

        # a concurrent burst of engine-path queries (mtbf is off every
        # surface axis): duplicates coalesce into shared passes
        burst = [base.replace(mtbf_h=m)
                 for m in (20.0, 20.0, 26.0, 26.0, 20.0, 26.0)]
        t0 = time.perf_counter()
        answers = [svc.query_async(sc) for sc in burst]
        answers = [a.result() for a in answers]
        wall = time.perf_counter() - t0
        n_engine = sum(1 for a in answers if a.source == "engine")
        print(f"\nburst of {len(burst)} concurrent queries "
              f"(2 distinct): {wall*1e3:.0f} ms total, "
              f"{n_engine} engine answers, "
              f"{svc.stats()['engine_configs']} engine passes overall")
        print("\nservice stats:", svc.stats()["cache"],
              svc.stats()["coalescer"])
    finally:
        svc.close()


if __name__ == "__main__":
    main()
