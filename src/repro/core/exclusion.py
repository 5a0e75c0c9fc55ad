"""Node-exclusion pattern tracking — paper F3 / §4.3.1.

Two exclusion mechanisms coexist:
* deliberate isolation — operators pre-allocate a single-node session on a
  suspect node so the gang scheduler cannot pick it (paper: gpu074 100%,
  gpu086 97%, gpu116 99.6% overlap with single-node occupancy);
* natural non-selection — the scheduler picks 60 of 63, so some healthy
  nodes simply miss the draw (gpu085: 4% overlap).

The tracker records per-node exclusion intervals tagged with the mechanism
and computes the concentration statistics of Fig 11-13.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from repro.core.findings import top_k_share


@dataclass
class ExclusionInterval:
    node: int
    t0_h: float
    t1_h: float
    deliberate: bool          # overlaps single-node occupancy
    reason: str = ""

    @property
    def hours(self) -> float:
        return self.t1_h - self.t0_h


@dataclass
class ExclusionTracker:
    n_nodes: int = 63
    intervals: List[ExclusionInterval] = field(default_factory=list)

    def record_session(self, t0_h: float, t1_h: float,
                       participating: List[int],
                       isolated: Dict[int, str]):
        """One multi-node session: every non-participating node is excluded
        for its duration; ``isolated`` maps node -> reason for nodes under
        deliberate single-node occupancy."""
        part = set(participating)
        for node in range(self.n_nodes):
            if node in part:
                continue
            self.intervals.append(ExclusionInterval(
                node=node, t0_h=t0_h, t1_h=t1_h,
                deliberate=node in isolated,
                reason=isolated.get(node, "not selected")))

    # -- statistics (Fig 11-13) ---------------------------------------------

    def exclusion_hours(self) -> np.ndarray:
        out = np.zeros(self.n_nodes)
        for iv in self.intervals:
            out[iv.node] += iv.hours
        return out

    def exclusion_counts(self) -> np.ndarray:
        out = np.zeros(self.n_nodes, dtype=int)
        for iv in self.intervals:
            out[iv.node] += 1
        return out

    def top_k_share(self, k: int = 3) -> float:
        """Fraction of all exclusion events on the k most-excluded nodes."""
        return top_k_share(self.exclusion_counts(), k)

    def by_reason(self) -> Dict[str, dict]:
        """Exclusion events grouped by reason — separates the injected
        mechanisms (fail-slow isolation, hardware down, not-selected) from
        detector-driven ones ("predictive drain"), so control-plane
        campaigns can show F3 concentration *emerging* from alarms."""
        out: Dict[str, dict] = {}
        for iv in self.intervals:
            g = out.setdefault(iv.reason, {"count": 0, "hours": 0.0,
                                           "nodes": set()})
            g["count"] += 1
            g["hours"] += iv.hours
            g["nodes"].add(iv.node)
        return {reason: {"count": g["count"], "hours": g["hours"],
                         "nodes": sorted(g["nodes"])}
                for reason, g in out.items()}

    def deliberate_overlap(self) -> Dict[int, float]:
        """Per node: fraction of exclusion hours that were deliberate."""
        total = np.zeros(self.n_nodes)
        delib = np.zeros(self.n_nodes)
        for iv in self.intervals:
            total[iv.node] += iv.hours
            if iv.deliberate:
                delib[iv.node] += iv.hours
        return {n: float(delib[n] / total[n])
                for n in range(self.n_nodes) if total[n] > 0}

    def summary(self) -> dict:
        counts = self.exclusion_counts()
        hours = self.exclusion_hours()
        order = np.argsort(counts)[::-1]
        return {
            "top3_nodes": [int(i) for i in order[:3]],
            "top3_share": self.top_k_share(3),
            "max_hours": float(hours.max(initial=0.0)),
            "n_intervals": len(self.intervals),
            "deliberate_fraction": float(
                sum(iv.deliberate for iv in self.intervals)
                / max(len(self.intervals), 1)),
        }
