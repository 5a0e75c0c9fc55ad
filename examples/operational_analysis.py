"""Reproduce the paper's §4 operational analyses on a simulated campaign.

Runs the 63-node cluster simulation (failure injection seeded from the
paper's observed distribution), then executes the three analyses:
F1 precursor detection, F3 node-exclusion concentration, F4 auto-retry
chains — and prints them next to the paper's published numbers.

    PYTHONPATH=src python examples/operational_analysis.py [--days 20]
"""
import argparse

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.cluster import CampaignConfig, ClusterSim
from repro.core.precursor import DetectorConfig, PrecursorDetector, evaluate
from repro.core.retry import chain_stats


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--days", type=float, default=15.0,
                    help="campaign length (telemetry on; 73 for paper scale)")
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args()

    print(f"simulating {args.days:.0f}-day campaign (63 nodes, telemetry on)…")
    cfg = CampaignConfig(duration_h=args.days * 24.0, telemetry=True,
                         seed=args.seed)
    res = ClusterSim(cfg).run()

    print(f"\n— campaign: {len(res.failures)} failures, "
          f"{len(res.sessions)} sessions, {res.checkpoint_events} checkpoint "
          f"events, occupancy {res.training_occupancy()*100:.1f}% "
          f"(paper: 96.6%)")

    # F1: precursor detection
    xid_fails = [f for f in res.failures if f.kind == "xid"]
    alarms = PrecursorDetector(DetectorConfig()).scan(res.store)
    ev = evaluate(alarms, xid_fails, res.duration_h)
    print(f"\nF1 precursor detection ({ev.n_failures} XID failures):")
    print(f"   detection {ev.detected}/{ev.n_failures} (paper 10/10), "
          f"pre-XID {ev.pre_xid}/{ev.n_failures} (paper 2/10), "
          f"FP/day {ev.fp_per_day:.2f} (paper ~0.84)")

    # F3: exclusion concentration
    summ = res.exclusions.summary()
    print(f"\nF3 node exclusion: top-3 share {summ['top3_share']*100:.0f}% "
          f"(paper >50%), deliberate fraction "
          f"{summ['deliberate_fraction']*100:.0f}%")

    # F4: retry chains
    st = chain_stats(res.retry_chains())
    auto = [d["hours"] for d in res.downtimes if d["auto"]]
    man = [d["hours"] for d in res.downtimes if not d["auto"]]
    print(f"\nF4 auto-retry: {st['n_chains']} chains / {st['n_attempts']} "
          f"attempts; success {st['chain_success_rate']*100:.0f}% "
          f"(paper 33.3%); gap median {st['gap_median_min']:.0f} min "
          f"(paper 11)")
    if auto and man:
        print(f"   downtime median auto {np.median(auto):.1f} h vs manual "
              f"{np.median(man):.1f} h (paper 1.9 vs 3.3)")


if __name__ == "__main__":
    main()
