"""Prometheus-style metric registry (counters + gauges) with a scrape loop.

The production pipeline in the paper scrapes 4 exporters x 63 nodes at 30 s
intervals into VictoriaMetrics (~751 unique metric names).  This module is
the in-process stand-in: exporters write samples, the registry scrapes into
the time-series store, and the precursor detector reads windows back.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

SCRAPE_INTERVAL_S = 30.0


@dataclass
class MetricMeta:
    name: str
    kind: str            # "counter" | "gauge"
    exporter: str        # dcgm | node | all_smi | backendai
    help: str = ""


class MetricRegistry:
    """Holds current values per (metric, node) and scrapes them into a store."""

    def __init__(self, n_nodes: int):
        self.n_nodes = n_nodes
        self.meta: Dict[str, MetricMeta] = {}
        self.values: Dict[str, np.ndarray] = {}

    def register(self, meta: MetricMeta):
        if meta.name in self.meta:
            return
        self.meta[meta.name] = meta
        self.values[meta.name] = np.zeros(self.n_nodes, dtype=np.float64)

    def set(self, name: str, node: int, value: float):
        self.values[name][node] = value

    def add(self, name: str, node: int, delta: float):
        self.values[name][node] += delta

    def set_all(self, name: str, values: np.ndarray):
        self.values[name][:] = values

    def snapshot(self) -> Dict[str, np.ndarray]:
        return {k: v.copy() for k, v in self.values.items()}

    @property
    def n_metrics(self) -> int:
        return len(self.meta)


class TimeSeriesStore:
    """Column store: metric -> (n_ticks, n_nodes) array.  VictoriaMetrics
    stand-in; everything the precursor analysis needs is window queries.

    Internally each metric holds a list of 2-D chunks — one row per
    single-tick ``append``, one multi-row block per ``append_batch`` — and
    ``series`` consolidates lazily, so batched producers never pay a
    per-tick Python cost."""

    def __init__(self, n_nodes: int):
        self.n_nodes = n_nodes
        self.ticks: List[float] = []
        self.data: Dict[str, List[np.ndarray]] = {}   # name -> 2-D chunks

    def append(self, t: float, snapshot: Dict[str, np.ndarray]):
        self.ticks.append(t)
        for name, vals in snapshot.items():
            arr = np.asarray(vals)
            self.data.setdefault(name, []).append(arr.reshape(1, -1))

    def append_batch(self, ts: np.ndarray, snapshot: Dict[str, np.ndarray]):
        """Append a whole span at once: ``ts`` (T,), values (T, n_nodes)."""
        if len(ts) == 0:
            return
        self.ticks.extend(float(t) for t in ts)
        for name, vals in snapshot.items():
            arr = np.asarray(vals)
            self.data.setdefault(name, []).append(arr)

    def series(self, name: str) -> np.ndarray:
        chunks = self.data[name]
        if len(chunks) > 1:                         # consolidate + cache
            self.data[name] = chunks = [np.concatenate(chunks, axis=0)]
        return chunks[0]                            # (n_ticks, n_nodes)

    def window(self, name: str, t0: float, t1: float) -> np.ndarray:
        ts = np.asarray(self.ticks)
        m = (ts >= t0) & (ts < t1)
        return self.series(name)[m]

    def times(self) -> np.ndarray:
        return np.asarray(self.ticks)

    @property
    def names(self):
        return list(self.data)

    def nbytes(self) -> int:
        return sum(c.nbytes for v in self.data.values() for c in v)
