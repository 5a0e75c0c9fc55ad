"""Cluster network topology: the rack / leaf-switch tree behind the nodes.

The paper's 63-node campaign runs behind a leaf-spine fabric; failures
that live in the *fabric* (a leaf switch degrading, a service-discovery
flap) hit every node attached to the same switch at once — the blast
radius the per-node fault model structurally cannot express.  This
module is the single source of truth for the node → switch mapping, so
the injector (sampling a switch event's member set), the telemetry
overlays (co-degrading gang members), the control plane (attributing a
gang-wide alarm burst to the shared switch) and the sweep columns all
agree on who sits behind what.

The mapping is deterministic and draw-free: node ``n`` sits behind leaf
switch ``n // fanout``.  The paper-shaped default (63 nodes, fanout 8)
yields 8 leaf switches — seven full racks of 8 and one of 7 — matching
the repo's hot-node skew granularity without consuming any randomness
(docs/PARITY.md rule 1: deterministic lookups cannot perturb rng
streams).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import numpy as np

#: paper-shaped default: 63 nodes in racks of 8 behind one leaf each
DEFAULT_FANOUT = 8


@dataclass(frozen=True)
class ClusterTopology:
    """Leaf-switch tree over ``n_nodes`` with configurable ``fanout``."""
    n_nodes: int = 63
    fanout: int = DEFAULT_FANOUT

    def __post_init__(self):
        if self.n_nodes < 1:
            raise ValueError("topology needs at least one node")
        if self.fanout < 1:
            raise ValueError(f"fanout must be >= 1, got {self.fanout}")

    @property
    def n_switches(self) -> int:
        return -(-self.n_nodes // self.fanout)

    def switch_of(self, node: int) -> int:
        """Leaf switch the node hangs off (deterministic, no draws)."""
        if not 0 <= node < self.n_nodes:
            raise ValueError(f"node {node} outside [0, {self.n_nodes})")
        return node // self.fanout

    def members(self, switch: int) -> Tuple[int, ...]:
        """All nodes attached to ``switch`` — the blast radius of a
        switch-level event."""
        if not 0 <= switch < self.n_switches:
            raise ValueError(
                f"switch {switch} outside [0, {self.n_switches})")
        lo = switch * self.fanout
        return tuple(range(lo, min(lo + self.fanout, self.n_nodes)))

    def switch_map(self) -> np.ndarray:
        """(n_nodes,) int64 node → switch lookup (vectorized callers)."""
        return np.arange(self.n_nodes, dtype=np.int64) // self.fanout
