"""Young/Daly checkpoint-interval optimisation — paper §4.2.2 (Tables 10-11).

T_opt = sqrt(2 * delta * M)   (Young's first-order approximation [19])

cost(T) = delta/T  (save overhead)  +  T/(2M)  (expected lost work fraction)

The paper's operational lesson: delta is small (18-31.7 s), so short
intervals are cheap — the 100K phase's 81.5-minute interval landed within
0.10 pp of the theoretical optimum.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

MTBF_H_PAPER = 56.2


@dataclass(frozen=True)
class PhaseProfile:
    """One training phase (paper Table 10/11)."""
    name: str
    delta_s: float                 # checkpoint save duration
    interval_min: float            # actual checkpoint interval
    episodes: int = 0


# paper Table 10/11 rows
PAPER_PHASES = [
    PhaseProfile("4K sequence", 18.0, 133.5, 466),
    PhaseProfile("32K sequence", 31.7, 199.0, 36),
    PhaseProfile("100K sequence", 30.0, 81.5, 21),
]


def t_opt_s(delta_s: float, mtbf_h: float = MTBF_H_PAPER) -> float:
    return math.sqrt(2.0 * delta_s * mtbf_h * 3600.0)


def cost_fraction(interval_s: float, delta_s: float,
                  mtbf_h: float = MTBF_H_PAPER) -> float:
    """Expected overhead fraction: save overhead + expected lost work."""
    m_s = mtbf_h * 3600.0
    return delta_s / interval_s + interval_s / (2.0 * m_s)


def save_overhead_fraction(interval_s: float, delta_s: float) -> float:
    return delta_s / interval_s


def phase_table(mtbf_h: float = MTBF_H_PAPER):
    """Reproduce paper Table 11."""
    rows = []
    for ph in PAPER_PHASES:
        interval_s = ph.interval_min * 60.0
        rows.append({
            "phase": ph.name,
            "delta_s": ph.delta_s,
            "actual_interval_min": ph.interval_min,
            "t_opt_min": t_opt_s(ph.delta_s, mtbf_h) / 60.0,
            "save_overhead_pct": 100 * save_overhead_fraction(interval_s, ph.delta_s),
            "total_cost_pct": 100 * cost_fraction(interval_s, ph.delta_s, mtbf_h),
            "optimal_cost_pct": 100 * cost_fraction(
                t_opt_s(ph.delta_s, mtbf_h), ph.delta_s, mtbf_h),
        })
    return rows


def estimate_delta_from_spikes(n_samples_mean: float,
                               scrape_interval_s: float = 30.0) -> float:
    """Paper Table 10 method: delta ~= (N_bar - 0.5) * scrape interval, from
    the mean number of consecutive scrape samples an NFS write spike spans.
    (N_bar samples cover between (N_bar-1) and N_bar intervals; the paper
    uses a point estimate consistent with delta = (N_bar - 1 + 0.5) * 30 s.)
    """
    return (n_samples_mean - 0.5) * scrape_interval_s


def empirical_lost_time(failure_times_h: np.ndarray,
                        interval_h: float) -> np.ndarray:
    """Lost work per failure given uniform checkpoint grid (for MC
    validation of the T/2M expectation)."""
    return failure_times_h % interval_h


def mc_cost_fraction(interval_s: float, delta_s: float, mtbf_h: float,
                     n: int = 100_000, seed: int = 0) -> float:
    """Monte-Carlo estimate of the total overhead fraction under
    exponential failures (validates the analytic model; used by the
    hypothesis tests)."""
    rng = np.random.default_rng(seed)
    m_s = mtbf_h * 3600.0
    # time between failures
    uptimes = rng.exponential(m_s, n)
    lost = uptimes % interval_s
    # overhead = (saves during uptime * delta + lost) / uptime
    saves = np.floor(uptimes / interval_s)
    return float((saves * delta_s + lost).sum() / uptimes.sum())
