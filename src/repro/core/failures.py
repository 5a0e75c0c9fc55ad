"""Failure injection: fail-stop (XID) + fail-slow events with precursor
signatures, seeded from the paper's observed 55-day distribution.

Paper evidence (Tables 2, 9-11):
* 17 failure events / 55 days; NVLink (XID 145/149) dominant at 29.4%.
* MTBF 56.2 h estimated from 1,294 training hours / 23 abnormal ends.
* Most signals emerge ABRUPTLY at the XID time point (pre-XID detection was
  only 2/10); a minority show gradual precursors (e.g. accelerating
  correctable row-remap on gpu124).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

# paper Table 2 mix (XID-detectable part) -----------------------------------
XID_MIX = [
    (145, 0.20), (149, 0.094),      # NVLink errors, 29.4% combined
    (94, 0.118),                    # ECC errors
    (79, 0.118),                    # GPU card dropout
    (119, 0.059),                   # GPU execution errors (GSP RPC timeout)
    (31, 0.03), (43, 0.03),         # app-level page fault / halt
]
P_MACHINE_UNREACHABLE = 0.118
P_FAIL_SLOW = 0.233                 # "Others": perf degradation etc.

MTBF_HOURS = 56.2                   # paper Table 11

# cluster-infrastructure fault band (degrade-don't-kill; opt-in via
# ``kind_weights`` — the paper's Table 2 mix carries zero weight for these,
# calibration anchors are Meta's research-cluster category rates):
# base rates relative to the Table 2 mix mass, scaled by w[name] (default 0)
P_NET_DEGRADE = 0.08                # network latency/loss windows
P_RESOURCE_EXHAUST = 0.06           # host memory / ephemeral-disk pressure
P_CTRL_BLIND = 0.03                 # scheduler / control-plane outages
P_RESOURCE_ESCALATE = 0.35          # pressure windows that end in a crash

# correlated fault band (opt-in via ``kind_weights``, like the infra band;
# calibration anchors are the switch/network category rates in "Revisiting
# Reliability"): failures that live in the *fabric*, not a node
P_SWITCH_DEGRADE = 0.05             # leaf switch degrades its whole rack
P_DNS_FLAP = 0.04                   # service-discovery flap: partial gang
                                    #   loses connectivity to specific peers

# dedicated stream for dns_flap member-subset draws; constructed lazily and
# consumed only when a dns_flap event exists, so band-off schedules never
# touch it (docs/PARITY.md)
RNG_STREAM_CORR = 7039

# scenario-facing failure categories (ops/scenario.py tilts these weights)
CATEGORY_OF_XID = {
    145: "nvlink", 149: "nvlink",
    94: "ecc",
    79: "dropout",
    119: "exec",
    31: "app", 43: "app",
}
FAILURE_CATEGORIES = frozenset(CATEGORY_OF_XID.values()) \
    | {"unreachable", "fail_slow",
       "net_degrade", "resource_exhaust", "ctrl_blind",
       "switch_degrade", "dns_flap"}

# the degrade-don't-kill band: faults that open a window instead of
# killing a session outright
DEGRADE_KINDS = frozenset({"net_degrade", "resource_exhaust"})
# the correlated band: fabric faults whose blast radius spans several
# nodes at once (a rack behind one leaf switch, a flapping peer's gang)
CORRELATED_KINDS = frozenset({"switch_degrade", "dns_flap"})
INFRA_KINDS = DEGRADE_KINDS | {"ctrl_blind"} | CORRELATED_KINDS


@dataclass
class FailureEvent:
    time_h: float                   # hours since campaign start
    node: int
    kind: str                       # KIND_NAMES entry
    xid: Optional[int] = None
    # precursor signature
    precursor_lead_h: float = 0.0   # >0: signals degrade before the XID
    slow_factor: float = 1.0        # fail-slow / degrade severity multiplier
    # infra fault band: degradation / outage window geometry
    window_h: float = 0.0           # >0: event opens a [t, t+window_h) window
    onset: str = ""                 # "" | "gradual" | "spike"
    escalate: bool = False          # resource window ends in a process crash
    # correlated fault band: blast-radius geometry
    switch: int = -1                # switch_degrade: the degraded leaf switch
    members: tuple = ()             # nodes inside the blast radius
    peers: tuple = ()               # dns_flap: the unreachable peer(s)

    @property
    def is_hardware(self) -> bool:
        from repro.core.xid import XID_TABLE
        return self.kind == "unreachable" or (
            self.xid is not None and XID_TABLE[self.xid].hardware)

    @property
    def is_degrade(self) -> bool:
        return self.kind in DEGRADE_KINDS

    @property
    def is_correlated(self) -> bool:
        return self.kind in CORRELATED_KINDS


@dataclass
class FailureInjector:
    """Samples a failure schedule for an N-node campaign.

    Inter-failure times ~ Exponential(MTBF); node selection is *skewed*
    (paper F3: exclusions concentrate — a few nodes are repeat offenders).
    ``hot_nodes``: fraction of nodes carrying ``hot_weight`` of the hazard.
    """
    n_nodes: int = 63
    mtbf_h: float = MTBF_HOURS
    hot_fraction: float = 0.05
    hot_weight: float = 0.55
    pre_xid_fraction: float = 0.2   # paper: 2/10 failures had precursors
    seed: int = 0
    # multiplicative tilts on the paper mix, keyed by category
    # ("nvlink" | "ecc" | "dropout" | "exec" | "app" | "unreachable" |
    #  "fail_slow"); the mix is renormalised after tilting
    kind_weights: Optional[Dict[str, float]] = None
    # leaf-switch fanout for the correlated band's blast radius
    # (core/topology.py; only consulted when correlated events exist)
    topology_fanout: int = 8

    def node_hazard(self) -> np.ndarray:
        return self.node_hazard_for(self.seed)

    def sample(self, duration_h: float) -> List[FailureEvent]:
        """Sample this injector's schedule (one seed).  Delegates to the
        batched drawer so the per-seed and campaign-batched paths share one
        implementation — `sample_batch(d, [seed]).events(0)` is the
        definition, not an approximation."""
        return self.sample_batch(duration_h, [self.seed]).events(0)

    def node_hazard_for(self, seed: int) -> np.ndarray:
        """`node_hazard` for an explicit seed (the batch drawer's form)."""
        rng = np.random.default_rng(seed + 1)
        n_hot = max(int(round(self.n_nodes * self.hot_fraction)), 1)
        hot = rng.choice(self.n_nodes, size=n_hot, replace=False)
        w = np.full(self.n_nodes,
                    (1 - self.hot_weight) / (self.n_nodes - n_hot))
        w[hot] = self.hot_weight / n_hot
        return w

    def sample_batch(self, duration_h: float,
                     seeds: Sequence[int]) -> "FailureBatch":
        """Draw S independent failure schedules as one stacked structure.

        Every seed consumes its own ``default_rng(seed)`` stream with the
        exact call sequence of the historical scalar ``sample`` (gap blocks,
        node choice, mix assignment, precursor/slow draws), so column ``i``
        is bit-identical to ``FailureInjector(seed=seeds[i]).sample(...)``.
        The mix tables, category lookup arrays and hazard shaping are
        computed once and shared across seeds; per-event python objects are
        only materialized on demand (``events(i)``)."""
        kinds, probs = self._mix()
        kind_is_xid = np.array([k[0] == "xid" for k in kinds])
        kind_is_slow = np.array([k[0] == "fail_slow" for k in kinds])
        kind_is_net = np.array([k[0] == "net_degrade" for k in kinds])
        kind_is_res = np.array([k[0] == "resource_exhaust" for k in kinds])
        kind_is_blind = np.array([k[0] == "ctrl_blind" for k in kinds])
        kind_is_switch = np.array([k[0] == "switch_degrade" for k in kinds])
        kind_is_dns = np.array([k[0] == "dns_flap" for k in kinds])
        kind_xid = np.array([k[1] if k[1] is not None else -1
                             for k in kinds], dtype=np.int64)
        from repro.core.xid import XID_TABLE
        kind_hw = np.array([k[0] == "unreachable"
                            or (k[1] is not None and XID_TABLE[k[1]].hardware)
                            for k in kinds])
        kind_code = np.array([_KIND_CODES[k[0]] for k in kinds],
                             dtype=np.int8)

        # blast-radius lookup for the correlated band — deterministic and
        # draw-free, so building it cannot perturb any rng stream
        from repro.core.topology import ClusterTopology
        topo = ClusterTopology(self.n_nodes, self.topology_fanout)
        node_switch = topo.switch_map()

        block = max(int(duration_h / self.mtbf_h * 1.5) + 8, 16)
        cols = []
        for seed in seeds:
            rng = np.random.default_rng(seed)
            hazard = self.node_hazard_for(seed)
            times = np.empty(0)
            total = 0.0
            while total < duration_h:
                gaps = rng.exponential(self.mtbf_h, block)
                times = np.concatenate([times, total + np.cumsum(gaps)])
                total = float(times[-1])
            times = times[times < duration_h]
            k = len(times)
            if k == 0:
                cols.append((times, np.empty(0, np.int64),
                             np.empty(0, np.int64), np.empty(0),
                             np.empty(0), np.empty(0),
                             np.empty(0, np.int8), np.empty(0, bool),
                             np.empty(0, np.int64), [], []))
                continue
            nodes = rng.choice(self.n_nodes, size=k, p=hazard)
            kind_idx = rng.choice(len(kinds), size=k, p=probs)
            is_xid = kind_is_xid[kind_idx]
            is_slow = kind_is_slow[kind_idx]
            leads = np.where(is_xid & (rng.random(k) < self.pre_xid_fraction),
                             rng.uniform(0.25, 2.0, k),
                             0.0)
            slows = np.where(is_slow,
                             rng.uniform(1.15, 1.6, k),
                             1.0)
            # infra fault band draws — appended AFTER the historical draw
            # sequence so pre-existing schedules stay bit-identical
            win_u = rng.random(k)
            sev_u = rng.random(k)
            onset_u = rng.random(k)
            esc_u = rng.random(k)
            is_net = kind_is_net[kind_idx]
            is_res = kind_is_res[kind_idx]
            is_blind = kind_is_blind[kind_idx]
            windows = np.where(
                is_net, 0.5 + 1.5 * win_u,
                np.where(is_res, 1.0 + 2.0 * win_u,
                         np.where(is_blind, 0.25 + 0.75 * win_u, 0.0)))
            slows = np.where(is_net, 1.2 + 0.6 * sev_u,
                             np.where(is_res, 1.3 + 0.7 * sev_u, slows))
            onset = np.where(is_res, np.where(onset_u < 0.5, 1, 2),
                             np.where(is_net, 2, 0)).astype(np.int8)
            escalate = is_res & (esc_u < P_RESOURCE_ESCALATE)
            # correlated band geometry REUSES the win_u / sev_u uniforms
            # drawn above — zero extra draws on the main stream, so
            # band-off schedules stay bit-identical (docs/PARITY.md)
            is_switch = kind_is_switch[kind_idx]
            is_dns = kind_is_dns[kind_idx]
            windows = np.where(
                is_switch, 1.0 + 3.0 * win_u,
                np.where(is_dns, 0.1 + 0.3 * win_u, windows))
            slows = np.where(
                is_switch, 1.2 + 0.6 * sev_u,
                np.where(is_dns, 1.05 + 0.25 * sev_u, slows))
            onset = np.where(is_switch | is_dns, 2, onset).astype(np.int8)
            # switch identity is a deterministic lookup on the already-
            # sampled node — no draw
            switch = np.where(is_switch, node_switch[nodes], -1)
            windows = self._clip_windows(times, nodes, windows,
                                         is_net | is_res, is_blind,
                                         duration_h,
                                         is_switch, switch, is_dns)
            members = [()] * k
            peers = [()] * k
            corr_idx = np.nonzero(is_switch | is_dns)[0]
            if corr_idx.size:
                # dns member subsets go on a dedicated stream, consumed
                # in schedule order and only when correlated events exist
                rng_corr = np.random.default_rng([seed, RNG_STREAM_CORR])
                for j in corr_idx:
                    if is_switch[j]:
                        members[j] = topo.members(int(switch[j]))
                    else:
                        peer = int(nodes[j])
                        size = int(rng_corr.integers(2, 7))
                        cand = np.delete(np.arange(self.n_nodes), peer)
                        pick = rng_corr.choice(len(cand),
                                               size=min(size, len(cand)),
                                               replace=False)
                        members[j] = tuple(sorted(int(cand[p])
                                                  for p in pick))
                        peers[j] = (peer,)
            cols.append((times, nodes, kind_idx, leads, slows,
                         windows, onset, escalate, switch, members, peers))

        counts = [len(c[0]) for c in cols]
        offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        if offsets[-1] == 0:
            empty_f = np.empty(0)
            return FailureBatch(
                seeds=list(seeds), offsets=offsets, times=empty_f,
                nodes=np.empty(0, np.int64), kind=np.empty(0, np.int8),
                xid=np.empty(0, np.int64), hardware=np.empty(0, bool),
                leads=empty_f, slows=empty_f, windows=np.empty(0),
                onset=np.empty(0, np.int8), escalate=np.empty(0, bool),
                switch=np.empty(0, np.int64), members=[], peers=[])
        times = np.concatenate([c[0] for c in cols if len(c[0])])
        nodes = np.concatenate([c[1] for c in cols if len(c[0])])
        kind_idx = np.concatenate([c[2] for c in cols if len(c[0])])
        leads = np.concatenate([c[3] for c in cols if len(c[0])])
        slows = np.concatenate([c[4] for c in cols if len(c[0])])
        windows = np.concatenate([c[5] for c in cols if len(c[0])])
        onset = np.concatenate([c[6] for c in cols if len(c[0])])
        escalate = np.concatenate([c[7] for c in cols if len(c[0])])
        switch = np.concatenate([c[8] for c in cols if len(c[0])])
        members = [m for c in cols if len(c[0]) for m in c[9]]
        peers = [p for c in cols if len(c[0]) for p in c[10]]
        return FailureBatch(
            seeds=list(seeds), offsets=offsets, times=times,
            nodes=nodes.astype(np.int64), kind=kind_code[kind_idx],
            xid=kind_xid[kind_idx], hardware=kind_hw[kind_idx],
            leads=leads, slows=slows, windows=windows,
            onset=onset.astype(np.int8), escalate=escalate.astype(bool),
            switch=switch.astype(np.int64), members=members, peers=peers)

    @staticmethod
    def _clip_windows(times, nodes, windows, is_deg, is_blind, duration_h,
                      is_switch=None, switch_ids=None, is_dns=None):
        """Deterministic (draw-free) window clipping: a degradation window
        ends no later than the next window-bearing event on the same node
        (per-node non-overlap), a blind window no later than the next blind
        window (the control plane is a single global resource), a switch
        window no later than the next event on the same switch, a dns flap
        no later than the next flap of the same peer, and every window ends
        by the campaign horizon."""
        deg_idx = np.nonzero(is_deg)[0]
        for a, j in enumerate(deg_idx):
            for j2 in deg_idx[a + 1:]:
                if nodes[j2] == nodes[j]:
                    windows[j] = min(windows[j], times[j2] - times[j])
                    break
        blind_idx = np.nonzero(is_blind)[0]
        for a, b in zip(blind_idx, blind_idx[1:]):
            windows[a] = min(windows[a], times[b] - times[a])
        if is_switch is not None:
            sw_idx = np.nonzero(is_switch)[0]
            for a, j in enumerate(sw_idx):
                for j2 in sw_idx[a + 1:]:
                    if switch_ids[j2] == switch_ids[j]:
                        windows[j] = min(windows[j], times[j2] - times[j])
                        break
            dns_idx = np.nonzero(is_dns)[0]
            for a, j in enumerate(dns_idx):
                for j2 in dns_idx[a + 1:]:
                    if nodes[j2] == nodes[j]:
                        windows[j] = min(windows[j], times[j2] - times[j])
                        break
        return np.where(windows > 0,
                        np.minimum(windows, duration_h - times), 0.0)

    def _mix(self):
        kinds = []
        probs = []
        w = self.kind_weights or {}
        for xid, p in XID_MIX:
            kinds.append(("xid", xid))
            probs.append(p * w.get(CATEGORY_OF_XID[xid], 1.0))
        kinds.append(("unreachable", None))
        probs.append(P_MACHINE_UNREACHABLE * w.get("unreachable", 1.0))
        kinds.append(("fail_slow", None))
        probs.append(P_FAIL_SLOW * w.get("fail_slow", 1.0))
        # infra fault band: zero-weight by default (appending zero-mass
        # entries does not perturb `Generator.choice` draws, so existing
        # seeds keep their exact schedules)
        kinds.append(("net_degrade", None))
        probs.append(P_NET_DEGRADE * w.get("net_degrade", 0.0))
        kinds.append(("resource_exhaust", None))
        probs.append(P_RESOURCE_EXHAUST * w.get("resource_exhaust", 0.0))
        kinds.append(("ctrl_blind", None))
        probs.append(P_CTRL_BLIND * w.get("ctrl_blind", 0.0))
        # correlated band: zero-weight by default, same zero-mass-append
        # guarantee as the infra band above
        kinds.append(("switch_degrade", None))
        probs.append(P_SWITCH_DEGRADE * w.get("switch_degrade", 0.0))
        kinds.append(("dns_flap", None))
        probs.append(P_DNS_FLAP * w.get("dns_flap", 0.0))
        probs = np.asarray(probs)
        return kinds, probs / probs.sum()


# kind codes used by the stacked schedule (FailureBatch.kind); codes >= 3
# are the degrade-don't-kill infra band, codes >= 6 its correlated subset
KIND_NAMES = ("xid", "unreachable", "fail_slow",
              "net_degrade", "resource_exhaust", "ctrl_blind",
              "switch_degrade", "dns_flap")
_KIND_CODES = {name: i for i, name in enumerate(KIND_NAMES)}
ONSET_NAMES = ("", "gradual", "spike")


@dataclass
class FailureBatch:
    """S stacked failure schedules (struct-of-arrays).

    Column ``i`` (rows ``offsets[i]:offsets[i+1]``) is the schedule for
    ``seeds[i]``, bit-identical to the scalar ``sample`` draw for that
    seed.  ``hardware`` pre-resolves ``FailureEvent.is_hardware`` so the
    batched campaign engine never touches the XID table in its hot loop.
    """
    seeds: List[int]
    offsets: np.ndarray            # (S+1,) int64
    times: np.ndarray              # (K,) hours
    nodes: np.ndarray              # (K,) int64
    kind: np.ndarray               # (K,) int8 — index into KIND_NAMES
    xid: np.ndarray                # (K,) int64, -1 = none
    hardware: np.ndarray           # (K,) bool
    leads: np.ndarray              # (K,) precursor lead hours
    slows: np.ndarray              # (K,) fail-slow / degrade severity
    windows: np.ndarray            # (K,) degradation/outage window hours
    onset: np.ndarray              # (K,) int8 — index into ONSET_NAMES
    escalate: np.ndarray           # (K,) bool — window ends in a crash
    switch: np.ndarray             # (K,) int64 — degraded switch, -1 = none
    members: List[tuple]           # (K,) blast-radius node tuples
    peers: List[tuple]             # (K,) dns_flap unreachable peer tuples
    _cache: Dict[int, List[FailureEvent]] = field(default_factory=dict,
                                                  repr=False)

    @property
    def n_seeds(self) -> int:
        return len(self.seeds)

    def count(self, i: int) -> int:
        return int(self.offsets[i + 1] - self.offsets[i])

    def events(self, i: int) -> List[FailureEvent]:
        """Materialize seed ``i``'s schedule as FailureEvent objects."""
        if i not in self._cache:
            a, b = int(self.offsets[i]), int(self.offsets[i + 1])
            self._cache[i] = [
                FailureEvent(time_h=float(self.times[j]),
                             node=int(self.nodes[j]),
                             kind=KIND_NAMES[self.kind[j]],
                             xid=int(self.xid[j]) if self.xid[j] >= 0
                             else None,
                             precursor_lead_h=float(self.leads[j]),
                             slow_factor=float(self.slows[j]),
                             window_h=float(self.windows[j]),
                             onset=ONSET_NAMES[self.onset[j]],
                             escalate=bool(self.escalate[j]),
                             switch=int(self.switch[j]),
                             members=self.members[j],
                             peers=self.peers[j])
                for j in range(a, b)]
        return self._cache[i]


# ---------------------------------------------------------------------------
# shared window geometry — the single source of truth both campaign engines
# (scalar ClusterSim and BatchedCampaignEngine) evaluate, so their degraded-
# hours ledgers and escalation/blind timelines are bit-identical
# ---------------------------------------------------------------------------

def onset_progress(ts, t0: float, t1: float, onset: str) -> np.ndarray:
    """Severity progress in [0, 1] on the half-open window [t0, t1).

    ``gradual`` ramps linearly over the first half of the window then
    plateaus (monotone nondecreasing within the window); ``spike`` jumps
    straight to 1.  Outside the window the progress is 0."""
    ts = np.asarray(ts, dtype=float)
    active = (ts >= t0) & (ts < t1)
    if onset == "gradual":
        ramp = max((t1 - t0) * 0.5, 1e-9)
        prog = np.minimum((ts - t0) / ramp, 1.0)
    else:
        prog = np.ones_like(ts)
    return np.where(active, prog, 0.0)


def degradation_windows(events: Sequence[FailureEvent]):
    """(node, t0, t1, severity, kind, onset) per degrade-band event, plus
    the per-member expansion of every correlated blast radius — so both
    engines' degraded-hours ledgers charge fabric faults to every affected
    node through the one helper they already share.

    ``events`` may be empty (or a zero-event seed's slice); the result is
    then simply ``[]`` — callers never need to special-case it."""
    wins = [(ev.node, ev.time_h, ev.time_h + ev.window_h, ev.slow_factor,
             ev.kind, ev.onset)
            for ev in events if ev.kind in DEGRADE_KINDS]
    wins.extend(blast_radius_windows(events))
    return wins


def blast_radius_windows(events: Sequence[FailureEvent]):
    """Per-node expansion of correlated (fabric) events: one entry
    ``(node, t0, t1, severity, kind, onset)`` per affected node per event,
    truncated deterministically so no node carries two overlapping
    correlated entries.  Empty input round-trips to ``[]``."""
    out = []
    last_end: Dict[int, float] = {}
    for ev in events:
        if ev.kind not in CORRELATED_KINDS or ev.window_h <= 0.0:
            continue
        t0, t1 = ev.time_h, ev.time_h + ev.window_h
        for node in sorted(set(ev.members) | set(ev.peers)):
            a0 = max(t0, last_end.get(node, 0.0))
            if a0 >= t1:
                continue
            out.append((node, a0, t1, ev.slow_factor, ev.kind, ev.onset))
            last_end[node] = t1
    return out


def flap_pairs(ev: FailureEvent) -> frozenset:
    """Symmetric pairwise connectivity mask for a dns_flap event: the
    (a, b) node pairs that cannot reach each other during the window.
    A flap is a *link* property, so the mask always contains both
    directions; non-flap events yield the empty mask."""
    pairs = set()
    for a in ev.members:
        for b in ev.peers:
            if a != b:
                pairs.add((a, b))
                pairs.add((b, a))
    return frozenset(pairs)


def escalation_events(events: Sequence[FailureEvent]):
    """(crash_time_h, node), time-sorted, for escalating pressure windows.
    Empty input round-trips to ``[]``."""
    return sorted((ev.time_h + ev.window_h, ev.node)
                  for ev in events
                  if ev.kind == "resource_exhaust" and ev.escalate)


def blind_windows(events: Sequence[FailureEvent]):
    """(t0, t1) per control-plane outage, in schedule order.  Empty input
    round-trips to ``[]``."""
    return [(ev.time_h, ev.time_h + ev.window_h)
            for ev in events if ev.kind == "ctrl_blind"]


def has_correlated_band(kind_weights: Optional[Dict[str, float]]) -> bool:
    """True when the weight dict gives any correlated kind positive mass —
    the wavefront eligibility check (kernels/wavefront) and the engines'
    fast paths key off this."""
    if not kind_weights:
        return False
    return any(kind_weights.get(k, 0.0) > 0.0 for k in CORRELATED_KINDS)


def degraded_overlap_h(windows, t0: float, t1: float, nodes) -> float:
    """Effective training hours lost to degradation windows overlapping a
    session's [t0, t1) run span on its gang nodes: overlap * (1 - 1/sev)
    at plateau severity (the ramp is a telemetry shape, not an accounting
    term — keeping the ledger a closed form both engines share).

    ``nodes`` is the gang: a collection of node ids, or a boolean row
    over the pool's nodes (one lookup a window, whatever the gang's
    size)."""
    in_gang = nodes.__getitem__ if isinstance(nodes, np.ndarray) \
        and nodes.dtype == bool else nodes.__contains__
    total = 0.0
    for node, w0, w1, sev, _kind, _onset in windows:
        if in_gang(node):
            ov = min(t1, w1) - max(t0, w0)
            if ov > 0.0:
                total += ov * (1.0 - 1.0 / sev)
    return total
