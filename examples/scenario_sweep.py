"""Batched what-if campaign sweeps over named operational scenarios.

Runs M scenarios x N seeds through the event-driven cluster simulation and
prints the F1-F4 findings side by side (plus the paper's published numbers
as the reference row).  The default set contrasts the paper's own campaign
with two §4.3.5 retry improvements; ``--scenarios all`` sweeps every preset.

    PYTHONPATH=src python examples/scenario_sweep.py
    PYTHONPATH=src python examples/scenario_sweep.py \
        --scenarios paper-faithful,flaky-fabric,storage-degraded \
        --seeds 0,1,2 --days 73 --telemetry-days 2 --report sweep.md

Distributional (Monte Carlo) sweeps route hundreds of seeds through the
seed-batched campaign engine in one stacked pass and add median/IQR/95%-CI
columns to the report:

    PYTHONPATH=src python examples/scenario_sweep.py \
        --scenarios paper-faithful,smart-retry --mc-seeds 256 \
        --report sweep_mc.md

Fleet-scale dense sweeps stack EVERY control-free (scenario, seed) lane
into one compiled XLA device pass — the whole campaign grid advances
inside a single jitted while-loop, with findings bitwise identical to
the numpy engines:

    PYTHONPATH=src python examples/scenario_sweep.py \
        --scenarios all --mc-seeds 10000 --grid --report sweep_grid.md
"""
import argparse
import warnings

from repro.compile_cache import use_compile_cache
from repro.ops import SweepRunner, get_scenario, list_scenarios


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios",
                    default="paper-faithful,no-auto-retry,smart-retry",
                    help="comma-separated preset names, or 'all' "
                         f"(available: {', '.join(list_scenarios())})")
    ap.add_argument("--seeds", default="0,1,2",
                    help="comma-separated campaign seeds")
    ap.add_argument("--days", type=float, default=None,
                    help="override campaign length (default: per-scenario, "
                         "73 for the paper campaign)")
    ap.add_argument("--telemetry-days", type=float, default=None,
                    help="run an F1 precursor sub-campaign of this length "
                         "per (scenario, seed); longer windows tighten the "
                         "F1 estimates; 0 skips F1 (fastest; default 2, "
                         "or 0 in --mc-seeds mode)")
    ap.add_argument("--workers", type=int, default=None,
                    help="process-pool width (default: one per campaign, "
                         "capped at the core count)")
    ap.add_argument("--executor", default="process",
                    choices=("process", "thread", "serial"))
    ap.add_argument("--report", default=None,
                    help="also write the full markdown report here")
    ap.add_argument("--preset", default=None,
                    help="proactive-vs-reactive quickstart: sweep the "
                         "reactive baseline against PRESET (e.g. "
                         "'proactive', 'proactive-aggressive' or "
                         "'log-fusion' — the latter also sweeps its "
                         "metric-only twin log-fusion-off) on identical "
                         "seeds; defaults --days to 14 and skips the F1 "
                         "sub-campaign")
    ap.add_argument("--mc-seeds", type=int, default=None,
                    help="Monte Carlo mode: run this many seeds per "
                         "scenario through the seed-batched campaign "
                         "engine (one stacked pass instead of one process "
                         "per seed) and add median/IQR/95%%-CI columns to "
                         "the report; overrides --seeds with range(N) and "
                         "skips the per-seed F1 sub-campaign unless "
                         "--telemetry-days is set explicitly")
    ap.add_argument("--grid", action="store_true",
                    help="whole-sweep wavefront: stack every control-free "
                         "(scenario, seed) lane into one compiled XLA "
                         "device pass (requires --mc-seeds; control-plane "
                         "scenarios fall back to the numpy engine; "
                         "findings are bitwise identical either way)")
    ap.add_argument("--wavefront-backend", default=None,
                    choices=("auto", "numpy", "xla", "pallas"),
                    help="Monte Carlo campaign backend: auto picks the "
                         "compiled device core when the lane count clears "
                         "its floor, numpy forces the stacked-numpy "
                         "wavefront, xla/pallas force the compiled core "
                         "(--grid implies xla unless set)")
    ap.add_argument("--detector-backend", default=None,
                    choices=("numpy", "xla", "pallas"),
                    help="streaming-detector pass-1 backend for control-"
                         "plane scenarios: numpy (reference), xla (fused "
                         "jitted XLA — the fast path off-TPU), pallas "
                         "(TPU kernel).  Alarm sets are identical across "
                         "backends; this trades wall-clock only")
    ap.add_argument("--list-presets", action="store_true",
                    help="print every scenario preset with its one-line "
                         "description and exit")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny deterministic CI sweep: paper-faithful + "
                         "storage-fabric + proactive + infra-faults, "
                         "1 seed, 3 days, serial, no F1, plus an mc_seeds "
                         "spot check")
    args = ap.parse_args()

    if args.list_presets:
        width = max(len(n) for n in list_scenarios())
        for name in list_scenarios():
            sc = get_scenario(name)
            print(f"{name:<{width}}  {sc.description}")
        return

    if args.smoke:
        args.scenarios = "paper-faithful,storage-fabric,proactive," \
                         "infra-faults"
        args.seeds = "0"
        args.days = 3.0
        args.telemetry_days = 0.0
        args.executor = "serial"
    elif args.preset:
        if args.preset == "log-fusion":
            # the log channel's deltas (TTD, false drains) are measured
            # against its metric-only twin on identical schedules
            args.scenarios = "reactive,log-fusion-off,log-fusion"
        else:
            args.scenarios = f"reactive,{args.preset}"
        if args.days is None:
            args.days = 14.0
        args.telemetry_days = 0.0
    if args.telemetry_days is None:
        args.telemetry_days = 0.0 if args.mc_seeds else 2.0
    if args.grid and not args.mc_seeds:
        ap.error("--grid needs --mc-seeds (it stacks the Monte Carlo "
                 "seed axis into the device pass)")
    wavefront = args.wavefront_backend or ("xla" if args.grid else "auto")
    if args.mc_seeds and wavefront != "numpy":
        # compiled lanes pad to the next power of two (>= 64): a
        # non-bucketed seed count pays for lanes it never reads
        from repro.kernels.common import next_pow2
        bucket = max(next_pow2(args.mc_seeds), 64)
        if bucket != args.mc_seeds:
            warnings.warn(
                f"--mc-seeds {args.mc_seeds} is not a power-of-two "
                "lane bucket: the compiled pass pads its lane axis "
                f"to the next bucket, so up to {bucket} seeds cost "
                "the same device wall clock (and every distinct "
                "count compiles its own program)", stacklevel=1)

    names = list_scenarios() if args.scenarios == "all" \
        else [s.strip() for s in args.scenarios.split(",") if s.strip()]
    scenarios = []
    for name in names:
        sc = get_scenario(name)
        if args.days is not None:
            sc = sc.replace(duration_days=args.days)
        if args.telemetry_days > 0:
            sc = sc.replace(telemetry_days=args.telemetry_days)
        if args.detector_backend:
            sc = sc.replace(detector_backend=args.detector_backend)
        scenarios.append(sc)
    seeds = [int(s) for s in args.seeds.split(",")]

    n_seeds = args.mc_seeds if args.mc_seeds else len(seeds)
    mode = "seed-batched Monte Carlo engine" if args.mc_seeds \
        else f"{args.executor} executor"
    print(f"sweeping {len(scenarios)} scenarios x {n_seeds} seeds "
          f"({mode})…")
    for sc in scenarios:
        print(f"  - {sc.name}: {sc.duration_days:.0f} d, {sc.n_nodes} nodes"
              + (f", F1 window {sc.telemetry_days:.0f} d"
                 if sc.telemetry_days else ""))

    res = SweepRunner(scenarios, seeds=seeds, max_workers=args.workers,
                      executor=args.executor, mc_seeds=args.mc_seeds,
                      wavefront_backend=wavefront).run()

    n = len(res.outcomes)
    print(f"\n{n} campaigns in {res.wall_s:.1f} s wall "
          f"({res.wall_s / n:.2f} s/campaign)\n")
    print(res.comparison_table())
    print("\n`—` = not applicable (F1 needs --telemetry-days > 0; downtime "
          "columns need at least one episode of that kind).")
    if args.report:
        res.write(args.report)
        print(f"\nfull report written to {args.report}")

    if args.smoke:
        # Monte Carlo spot check: the batched engine's findings must be
        # identical to the serial per-seed path on the same seeds — on the
        # paper mix and on the infra fault band (degradation ledger,
        # escalations and blind-window replay included)
        for name in ("paper-faithful", "infra-faults"):
            sc = get_scenario(name).replace(duration_days=3.0)
            mc = SweepRunner([sc], mc_seeds=4).run()
            ref = SweepRunner([sc], seeds=range(4), executor="serial").run()
            for a, b in zip(mc.outcomes, ref.outcomes):
                fa = {k: v for k, v in a.findings.items() if k != "wall_s"}
                fb = {k: v for k, v in b.findings.items() if k != "wall_s"}
                assert a.seed == b.seed and fa == fb, \
                    f"mc/serial findings diverged: {name} seed {a.seed}"
            print(f"mc_seeds smoke [{name}]: batched findings == per-seed "
                  "findings (4 seeds)")


if __name__ == "__main__":
    main()
