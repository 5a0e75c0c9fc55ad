"""Auto-retry chains — paper F4 / §4.3.2-4.3.5.

Paper-faithful policy: fixed retry delay (10 min) + teardown/restart
overhead -> 11-minute median inter-session gap (IQR 10-11).  Chain success
(reaching RUNNING at least once after a retry) was 33.3% vs 12.5% for manual
one-shot restarts (2.7x), with median downtime 1.9 h vs 3.3 h.

Beyond-paper policies implemented from the paper's §4.3.5 improvement list:
* exponential backoff (10 -> 20 -> 40 min, capped),
* XID-based branching (RESTART_APP: retry immediately; RESET_GPU: retry
  after device-reset delay; RESTART_BM/CONTACT_SUPPORT: stop and page),
* structural-failure detection: stop retrying when the free pool cannot
  satisfy the gang requirement (the paper's chains burned 30 consecutive
  failed attempts / ~35 GPU-hours on exactly this).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from enum import Enum
from typing import Collection, List, Optional, Sequence

from refsim.core.xid import XID_TABLE, Resolution


class RetryPolicy(Enum):
    FIXED = "fixed"                  # paper-faithful
    EXP_BACKOFF = "exp_backoff"      # §4.3.5 improvement 1
    XID_BRANCH = "xid_branch"        # §4.3.5 improvement 2


@dataclass
class RetryConfig:
    enabled: bool = True
    max_retries: int = 30
    delay_min: float = 10.0          # minutes (paper setting)
    teardown_min: float = 1.0        # observed teardown+restart overhead
    policy: RetryPolicy = RetryPolicy.FIXED
    backoff_factor: float = 2.0
    backoff_cap_min: float = 80.0
    gpu_reset_min: float = 6.0       # device reset before retry (XID branch)
    # §4.3.5 improvement 3: when the healthy pool cannot satisfy the gang
    # requirement, hand off to the operator immediately instead of burning
    # attempts (the paper's chains lacked this and burned 30 in a row)
    structural_stop: bool = False


@dataclass
class Attempt:
    start_h: float
    end_h: Optional[float] = None
    reached_training: bool = False
    failure_kind: Optional[str] = None   # xid | unreachable | alloc_fail | None
    xid: Optional[int] = None


@dataclass
class Chain:
    task_name: str
    attempts: List[Attempt] = field(default_factory=list)
    stopped_reason: Optional[str] = None

    @property
    def n_retries(self) -> int:
        return max(len(self.attempts) - 1, 0)

    @property
    def success(self) -> bool:
        """Paper definition: training reached after at least one retry."""
        return any(a.reached_training for a in self.attempts[1:])

    @property
    def first_reached(self) -> bool:
        return bool(self.attempts) and self.attempts[0].reached_training

    def classify(self) -> str:
        """Paper Table 14 buckets."""
        if self.success:
            return "SUCCESS"
        if self.first_reached:
            return "FAIL_AFTER_TRAINING"
        return "FAIL_START"

    def gaps_min(self) -> List[float]:
        out = []
        for prev, nxt in zip(self.attempts, self.attempts[1:]):
            if prev.end_h is not None:
                out.append((nxt.start_h - prev.end_h) * 60.0)
        return out


class RetryEngine:
    """Decides when (and whether) the next attempt starts."""

    def __init__(self, config: RetryConfig):
        self.config = config

    def next_delay_min(self, attempt_idx: int,
                       xid: Optional[int] = None) -> Optional[float]:
        """Minutes to wait before attempt ``attempt_idx`` (1-based retry
        index); None = stop retrying (operator action required)."""
        c = self.config
        if not c.enabled or attempt_idx > c.max_retries:
            return None
        if c.policy is RetryPolicy.FIXED:
            return c.delay_min + c.teardown_min
        if c.policy is RetryPolicy.EXP_BACKOFF:
            d = c.delay_min * (c.backoff_factor ** (attempt_idx - 1))
            return min(d, c.backoff_cap_min) + c.teardown_min
        if c.policy is RetryPolicy.XID_BRANCH:
            if xid is None:
                return c.delay_min + c.teardown_min
            res = XID_TABLE[xid].resolution
            if res is Resolution.RESTART_APP:
                return c.teardown_min                  # immediate
            if res is Resolution.RESET_GPU:
                return c.gpu_reset_min + c.teardown_min
            return None                                # RESTART_BM: page operator
        raise ValueError(c.policy)

    @staticmethod
    def is_structural(free_nodes: int, required: int) -> bool:
        """Gang requirement cannot be met — retrying is futile (§4.3.5)."""
        return free_nodes < required

    @staticmethod
    def placement_order(nodes: Sequence[int],
                        avoid: Collection[int]) -> List[int]:
        """Alarm-informed retry placement: order candidate nodes so that
        recently-alarmed ones are chosen last.  The ordering is stable, so
        the scheduler's own preference is preserved within each group, and
        the gang requirement still wins — avoided nodes ARE used when the
        pool is tight (a degraded gang beats no gang)."""
        return sorted(nodes, key=lambda idx: idx in avoid)


# ---------------------------------------------------------------------------
# chain-level statistics (Table 14 / Fig 16 / Fig 17)
# ---------------------------------------------------------------------------

def chain_stats(chains: List[Chain]) -> dict:
    import numpy as np
    n = len(chains)
    classes = [c.classify() for c in chains]
    gaps = [g for c in chains for g in c.gaps_min()]
    succ = sum(1 for c in classes if c == "SUCCESS")
    return {
        "n_chains": n,
        "n_attempts": sum(len(c.attempts) for c in chains),
        "n_retries": sum(c.n_retries for c in chains),
        "success": succ,
        "fail_after_training": sum(1 for c in classes
                                   if c == "FAIL_AFTER_TRAINING"),
        "fail_start": sum(1 for c in classes if c == "FAIL_START"),
        "chain_success_rate": succ / n if n else 0.0,
        "gap_median_min": float(np.median(gaps)) if gaps else None,
        "gap_iqr_min": (float(np.percentile(gaps, 25)),
                        float(np.percentile(gaps, 75))) if gaps else None,
    }
