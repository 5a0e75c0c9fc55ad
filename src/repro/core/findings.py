"""The findings fold: one lane's totals -> the F2-F4 findings dict.

Every engine fills a `LaneTotals` and calls `findings`: the scalar path
(`repro.ops.sweep.compute_findings` over a `CampaignResult`), the numpy
wavefront (`BatchedCampaignEngine._findings`) and the compiled grid's
host replay (`repro.kernels.wavefront.ops._lane_findings`).
`CampaignResult`'s goodput and occupancy call the same helpers, so each
finding is written once and a new one is one edit here.

The float-order rule, on which the engines' bitwise parity rests: the
running hours of a lane's sessions are added left to right with ``+=``,
in the order the sessions close (`fold_left`).  Never add floats with
builtin ``sum()`` (compensated, Neumaier, since Python 3.12) nor with
``math.fsum``: either rounds differently from the engines' running
``+=``.  Lost and degraded hours are lists every engine keeps whole;
all of them add those with ``np.sum``.
"""
from __future__ import annotations

from typing import Iterable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np


class LaneTotals(NamedTuple):
    """What one campaign lane's findings are computed from."""
    duration_h: float
    run_h: float                     # multi-node sessions' running hours,
                                     #   folded left to right
    lost_h: Sequence[float]          # lost work, per failure that lost it
    ckpt_events: int
    save_s: float                    # one scheduled save, seconds
    urgent_h: float                  # control plane's urgent saves
    degraded_h: Sequence[float]      # per session, hours lost to degrade-
                                     #   band windows
    n_failures: int
    n_sessions: int
    excl_counts: np.ndarray          # exclusion intervals, per node
    n_deliberate: int                # deliberate exclusion intervals
    n_intervals: int                 # all exclusion intervals
    f4: Tuple[int, int, int]         # retry chains, attempts, successes
    gaps_min: Sequence[float]        # retry gaps, minutes
    auto_down_h: Sequence[float]     # recovery downtimes, drains left out
    manual_down_h: Sequence[float]
    infra_n: int                     # infra-band events
    corr_n: int                      # correlated-band events
    switch_ids: Sequence[int]        # switch of each switch_degrade event
    control: Optional[dict]          # `ControlStats.summarize`, or None
    drain_excl: int                  # "predictive drain" exclusions


def fold_left(values: Iterable[float]) -> float:
    """Left-to-right float sum: the one order running hours are added in."""
    acc = 0.0
    for v in values:
        acc += v
    return acc


def occupancy(run_h: float, duration_h: float) -> float:
    return min(run_h / duration_h, 1.0)


def goodput_h(run_h: float, lost_h: float, ckpt_events: int,
              save_s: float, urgent_h: float, deg_h: float) -> float:
    """Productive training hours: running hours less the summed lost
    work, scheduled saves, urgent saves and degraded hours, subtracted
    in that order."""
    return run_h - lost_h - ckpt_events * save_s / 3600.0 - urgent_h \
        - deg_h


def goodput(goodput_hours: float, duration_h: float) -> float:
    """Goodput as a fraction of the campaign wall clock."""
    return max(goodput_hours, 0.0) / duration_h


def top_k_share(counts, k: int = 3) -> float:
    """Share of all exclusion events on the ``k`` most-excluded nodes."""
    c = np.asarray(counts, dtype=float)
    total = c.sum()
    return float(np.sort(c)[::-1][:k].sum() / total) if total else 0.0


def median(values: Sequence[float]) -> Optional[float]:
    return float(np.median(values)) if len(values) else None


def split_downtimes(downtimes: Iterable[dict]
                    ) -> Tuple[List[float], List[float]]:
    """Automatic and manual recovery-episode hours, in episode order.
    Drains are controlled handoffs, not recovery downtime: leaving them
    out keeps the F4 medians comparable with the paper's reactive
    measurements."""
    autos: List[float] = []
    mans: List[float] = []
    for d in downtimes:
        if d.get("kind") != "drain":
            (autos if d["auto"] else mans).append(d["hours"])
    return autos, mans


def findings(t: LaneTotals) -> dict:
    """F2-F4 findings (plus campaign health) of one lane."""
    duration = t.duration_h
    lost = t.lost_h
    deg_h = float(np.sum(t.degraded_h))
    good_h = goodput_h(t.run_h, float(np.sum(lost)), t.ckpt_events,
                       t.save_s, t.urgent_h, deg_h)
    n_chains, n_attempts, succ = t.f4
    sw = t.switch_ids
    out = {
        "occupancy": occupancy(t.run_h, duration),
        "goodput": goodput(good_h, duration),
        "n_failures": float(t.n_failures),
        "n_sessions": float(t.n_sessions),
        "ckpt_events": float(t.ckpt_events),
        "mean_lost_h": float(np.mean(lost)) if len(lost) else 0.0,
        "f3_top3_share": top_k_share(t.excl_counts, 3),
        "f3_deliberate_fraction": float(t.n_deliberate
                                        / max(t.n_intervals, 1)),
        "f4_n_chains": float(n_chains),
        "f4_n_attempts": float(n_attempts),
        "f4_success_rate": succ / n_chains if n_chains else 0.0,
        "f4_gap_median_min": median(t.gaps_min),
        "f4_auto_downtime_h": median(t.auto_down_h),
        "f4_manual_downtime_h": median(t.manual_down_h),
        # infra fault band: degrade-don't-kill events and the effective
        # hours their windows ate (0.0 without the band)
        "infra_n_events": float(t.infra_n),
        "infra_degraded_h": deg_h,
        # correlated fault band: event count and the share of
        # switch_degrade events on the busiest leaf switch (F3 at rack
        # granularity; 0.0 without the band)
        "corr_n_events": float(t.corr_n),
        "corr_top_switch_share": float(np.bincount(sw).max() / len(sw))
        if len(sw) else 0.0,
    }
    if t.control is not None:
        out.update({f"ctrl_{k}": v for k, v in t.control.items()})
        out["ctrl_drain_excl_events"] = float(t.drain_excl)
    return out
