"""Multi-signal failure (precursor) detection — paper F1 / §4.1.

Because all N nodes execute the same SPMD program, anomaly detection is
framed as deviation from the peer distribution: at each scrape tick, for each
metric, compute a robust z-score of every node against the other N-1 nodes
(median/MAD — resistant to the faulty node polluting the baseline).  A node
alarms when >= ``min_signals`` metrics exceed ``z_threshold`` simultaneously
for ``persistence`` consecutive ticks.

The paper's result with this family of detectors: 10/10 detection at the XID
point, 2/10 pre-XID, ~0.84 false positives/day — and *no single metric is
consistently dominant*, which is why the vote is across the whole metric set.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

from refsim.telemetry.registry import TimeSeriesStore


@dataclass(frozen=True)
class DetectorConfig:
    z_threshold: float = 6.0
    min_signals: int = 4          # metrics that must agree (multi-signal vote)
    persistence: int = 1          # consecutive ticks before alarming
    exclude_metrics: tuple = ("DCGM_FI_DEV_XID_ERRORS",)  # no label leakage
    # peer cohort: only nodes actively running the same SPMD workload are
    # comparable (paper: "the remaining 59 healthy nodes"); idle spares and
    # operator-isolated nodes would otherwise alarm constantly.
    activity_metric: str = "DCGM_FI_DEV_GPU_UTIL"
    activity_threshold: float = 30.0


@dataclass
class Alarm:
    tick: int
    time_h: float
    node: int
    n_signals: int
    top_metrics: List[Tuple[str, float]]   # (metric, |z|) strongest first


def robust_peer_z(values: np.ndarray) -> np.ndarray:
    """Per-node robust z-score vs the peer distribution at one tick.

    values: (n_nodes,).  Uses median/MAD of all nodes (the faulty node is
    <=1/N of the sample, so median/MAD are stable).
    """
    med = np.median(values)
    mad = np.median(np.abs(values - med))
    scale = 1.4826 * mad
    if scale < 1e-12:
        scale = max(1e-12, 1e-6 * max(abs(med), 1.0))
    return (values - med) / scale


class PrecursorDetector:
    def __init__(self, config: Optional[DetectorConfig] = None,
                 backend: str = "numpy"):
        # per-instance default: a shared default-argument instance would
        # alias every detector's config
        self.config = config if config is not None else DetectorConfig()
        self.backend = backend

    def scan(self, store: TimeSeriesStore) -> List[Alarm]:
        """Run detection over a full telemetry store; returns alarms.

        Delegates to the streaming core (`refsim.control.streaming`) with a
        single push of the whole store, so the offline and online paths
        share one implementation: a chunked online feed of the same store
        reproduces this alarm list exactly (see the control-plane parity
        test).
        """
        from refsim.control.streaming import StreamingDetector
        det = StreamingDetector(self.config, backend=self.backend)
        return det.push(store.times(),
                        {name: store.series(name) for name in store.names})


@dataclass
class EvalResult:
    n_failures: int
    detected: int
    pre_xid: int
    false_positives: int
    fp_per_day: float
    detection_lead_h: List[float]
    per_failure: List[dict] = field(default_factory=list)
    # indices (into the scored alarm sequence) that matched a failure —
    # the control plane uses this to split urgent-checkpoint spend into
    # justified (true positive) vs wasted (false positive)
    matched_alarm_ids: set = field(default_factory=set)

    @property
    def detection_rate(self) -> float:
        return self.detected / max(self.n_failures, 1)

    @property
    def pre_xid_rate(self) -> float:
        return self.pre_xid / max(self.n_failures, 1)


def evaluate(alarms: Sequence[Alarm], failures, duration_h: float,
             match_window_h: float = 0.5) -> EvalResult:
    """Score alarms against ground-truth failure events.

    detected  : an alarm on the failing node within +-match_window of the event
    pre_xid   : the alarm strictly precedes the event time
    false pos : alarms on healthy nodes / outside any event window, deduped
                per (node, hour) so a persisting anomaly counts once
    """
    detected = pre = 0
    leads: List[float] = []
    per_failure = []
    matched_alarm_ids = set()
    for ev in failures:
        window = [(i, a) for i, a in enumerate(alarms)
                  if a.node == ev.node
                  and ev.time_h - max(match_window_h, ev.precursor_lead_h + 0.1)
                  <= a.time_h <= ev.time_h + match_window_h]
        ok = len(window) > 0
        first = min((a.time_h for _, a in window), default=None)
        is_pre = ok and first < ev.time_h - 1e-9
        detected += ok
        pre += is_pre
        if ok:
            leads.append(ev.time_h - first)
            matched_alarm_ids.update(i for i, _ in window)
        per_failure.append({
            "node": ev.node, "time_h": ev.time_h, "xid": getattr(ev, "xid", None),
            "detected": ok, "pre_xid": bool(is_pre),
            "lead_h": (ev.time_h - first) if ok else None,
        })

    fp_keys = set()
    for i, a in enumerate(alarms):
        if i in matched_alarm_ids:
            continue
        fp_keys.add((a.node, int(a.time_h)))   # dedupe per node-hour
    n_fp = len(fp_keys)
    return EvalResult(
        n_failures=len(list(failures)), detected=detected, pre_xid=pre,
        false_positives=n_fp, fp_per_day=n_fp / max(duration_h / 24.0, 1e-9),
        detection_lead_h=leads, per_failure=per_failure,
        matched_alarm_ids=matched_alarm_ids)
