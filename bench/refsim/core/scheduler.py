"""Sokovan-style GPU-first gang scheduler — paper §3.3.

Two-level scheduling: cluster level (pending sessions vs resource pool) and
node level (NUMA-aware placement).  The property that matters for the
failure analyses is GANG (all-or-nothing) allocation: a 60-node job either
gets all 60 slots at once or the whole request queues — partial allocation
would deadlock NCCL init and fragment the pool.  This constraint is the
structural cause of auto-retry failures when the healthy pool drops below
the job size (paper §4.3.5).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set

from refsim.core.retry import RetryEngine
from refsim.core.session import Session, SessionState


@dataclass
class Node:
    idx: int
    healthy: bool = True
    excluded: bool = False            # operator isolation (single-node occupancy)
    allocated_to: Optional[int] = None  # session id
    numa_nodes: int = 2
    gpus: int = 8

    @property
    def free(self) -> bool:
        return self.healthy and not self.excluded and self.allocated_to is None


@dataclass
class NumaPlacement:
    """Node-level placement decision (paper Fig 1)."""
    node: int
    policy: str                       # prefer-single-node | interleaving
    numa_map: Dict[int, int] = field(default_factory=dict)  # gpu -> numa node


class GangScheduler:
    def __init__(self, n_nodes: int = 63, spares: int = 3):
        self.nodes = [Node(i) for i in range(n_nodes)]
        self.n_spares = spares
        self.queue: List[Session] = []
        self.log: List[dict] = []

    # -- pool state ---------------------------------------------------------

    def free_nodes(self) -> List[Node]:
        return [n for n in self.nodes if n.free]

    def exclude(self, idx: int, t_h: float, reason: str):
        self.nodes[idx].excluded = True
        self.log.append({"t": t_h, "event": "exclude", "node": idx,
                         "reason": reason})

    def readmit(self, idx: int, t_h: float):
        self.nodes[idx].excluded = False
        self.nodes[idx].healthy = True
        self.log.append({"t": t_h, "event": "readmit", "node": idx})

    def mark_down(self, idx: int, t_h: float, reason: str):
        self.nodes[idx].healthy = False
        self.log.append({"t": t_h, "event": "down", "node": idx,
                         "reason": reason})

    # -- gang allocation ----------------------------------------------------

    def try_allocate(self, session: Session, t_h: float,
                     avoid: Optional[Set[int]] = None) -> bool:
        """All-or-nothing: allocate session.n_nodes nodes or nothing.

        ``avoid``: soft preference (alarm-informed retry placement) —
        those nodes are picked last but still used when the gang cannot be
        met without them."""
        free = self.free_nodes()
        if len(free) < session.n_nodes:
            self.log.append({"t": t_h, "event": "alloc_fail",
                             "session": session.session_id,
                             "want": session.n_nodes, "free": len(free)})
            return False
        if avoid:
            order = RetryEngine.placement_order([n.idx for n in free], avoid)
            rank = {idx: pos for pos, idx in enumerate(order)}
            free = sorted(free, key=lambda n: rank[n.idx])
        chosen = free[:session.n_nodes]
        for n in chosen:
            n.allocated_to = session.session_id
        session.nodes = [n.idx for n in chosen]
        session.transition(SessionState.SCHEDULED, t_h)
        self.log.append({"t": t_h, "event": "alloc",
                         "session": session.session_id,
                         "nodes": session.nodes})
        return True

    def release(self, session: Session, t_h: float):
        for idx in session.nodes:
            if self.nodes[idx].allocated_to == session.session_id:
                self.nodes[idx].allocated_to = None
        self.log.append({"t": t_h, "event": "release",
                         "session": session.session_id})

    # -- NUMA placement (node level) ----------------------------------------

    @staticmethod
    def numa_place(gpus_requested: int, policy: str = "prefer-single-node",
                   numa_nodes: int = 2, gpus_per_node: int = 8) -> NumaPlacement:
        """Paper Fig 1: prefer-single-node packs one NUMA domain; interleaving
        spreads.  Co-location avoids cross-NUMA access (up to 1.30x)."""
        per_numa = gpus_per_node // numa_nodes
        numa_map: Dict[int, int] = {}
        if policy == "prefer-single-node" and gpus_requested <= per_numa:
            for g in range(gpus_requested):
                numa_map[g] = 0
        else:
            for g in range(gpus_requested):
                numa_map[g] = g % numa_nodes
        return NumaPlacement(node=-1, policy=policy, numa_map=numa_map)

    # -- elastic allocation (beyond-paper: 1000+-node operation) -------------

    def try_allocate_elastic(self, session: Session, t_h: float,
                             min_nodes: int) -> bool:
        """Gang-allocate up to session.n_nodes but accept >= min_nodes.

        The paper's cluster hard-required 60/60 (structural retry failures
        when the pool dipped below — §4.3.5).  At 1000+-node scale the DP
        group must instead re-form at n-k: HSDP makes this cheap (drop a
        replica), so the scheduler offers a degraded-width allocation."""
        free = self.free_nodes()
        if len(free) < min_nodes:
            self.log.append({"t": t_h, "event": "alloc_fail",
                             "session": session.session_id,
                             "want": session.n_nodes, "min": min_nodes,
                             "free": len(free)})
            return False
        width = min(len(free), session.n_nodes)
        chosen = free[:width]
        for n in chosen:
            n.allocated_to = session.session_id
        session.nodes = [n.idx for n in chosen]
        session.n_nodes = width
        session.transition(SessionState.SCHEDULED, t_h)
        self.log.append({"t": t_h, "event": "alloc_elastic",
                         "session": session.session_id, "width": width})
        return True

    # -- priority preemption (paper §4.3.5 improvement) ----------------------

    def preempt_single_node_sessions(self, needed: int, t_h: float,
                                     single_sessions: List[Session]) -> int:
        """Free nodes held by lower-priority single-node sessions so a gang
        job can meet its requirement.  Returns number of nodes freed."""
        freed = 0
        for s in sorted(single_sessions, key=lambda s: s.created_h,
                        reverse=True):
            if freed >= needed:
                break
            if s.state in (SessionState.RUNNING, SessionState.SCHEDULED) \
                    and len(s.nodes) == 1:
                idx = s.nodes[0]
                node = self.nodes[idx]
                if node.healthy:
                    s.transition(SessionState.TERMINATING, t_h)
                    s.transition(SessionState.TERMINATED, t_h)
                    node.allocated_to = None
                    node.excluded = False
                    freed += 1
                    self.log.append({"t": t_h, "event": "preempt",
                                     "session": s.session_id, "node": idx})
        return freed
