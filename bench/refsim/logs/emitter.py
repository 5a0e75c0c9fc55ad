"""Structured synthetic log emitter driven by the sim's failure schedule.

The paper's operators diagnosed failure clusters from 73 days of
operational logs *jointly* with Prometheus metrics; the repro's telemetry
layer only modelled the metric side.  This emitter produces the log side:
every failure kind in the taxonomy gets a characteristic line mix (XID
bursts, NCCL watchdog timeouts on the peers, NFS/RPC storage-stall spam,
memory-pressure ramps, scheduler-outage markers), interleaved with benign
per-node background noise and session-lifecycle heartbeats.

Determinism contract (the batch==scalar parity hinge):

* the emitter owns a **dedicated rng stream** (``RNG_STREAM_LOGS``) seeded
  as ``default_rng([seed, RNG_STREAM_LOGS])`` — consuming it can never
  perturb the engines' existing draw order, and nothing else consumes it;
* failure-specific draws happen at **registration time**, in schedule
  order (identical in both engines); window-level draws (noise) happen at
  **emission time**, in chunk order (chunk boundaries are mirrored
  chunk-for-chunk between the scalar batcher and the batched engine);
* gang-wide symptom lines ("peer node-K unreachable" on every other gang
  member) are materialised draw-free at emission from the current gang.

Lines are ``(time_h, node, text)``; the first token of ``text`` is the
level (INFO/WARN/ERROR) and node references are spelled ``node-<id>`` so
the analyzer can recover cross-node attribution edges by parsing, not by
privileged access to ground truth.  Controller-scoped lines carry
``node == -1``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Sequence

import numpy as np

# dedicated rng stream id for the log emitter (see RNG_STREAM_MANUAL /
# RNG_STREAM_STRUCT in core/cluster.py for the pattern; PARITY.md for why
# streams are append-only)
RNG_STREAM_LOGS = 7027

# scrape tick, mirrors core.cluster.TICK_H (policy.py cannot import the
# engine module without a cycle)
_TICK_H = 30.0 / 3600.0

# benign background chatter; {v} is the masked-out variable slot.  Noise is
# INFO/WARN only — ERROR is reserved for genuine fault programs, which is
# what lets the analyzer treat rare ERROR templates as a rarity signal.
NOISE_TEMPLATES = (
    "INFO trainer: dataloader prefetch depth {v} ok",
    "INFO sshd: accepted publickey for ops from 10.0.{v}.7",
    "INFO systemd: run-docker-runtime scope for job {v} succeeded",
    "WARN systemd-journald: missed {v} kernel messages",
    "INFO smartd: device sda SMART ok, temperature {v} C",
    "INFO dcgm: health watch ok on gpu {v}",
    "INFO chronyd: clock offset {v} us from ntp pool",
    "WARN kubelet: image garbage collection freed {v} bytes",
    "INFO launcher: heartbeat ok, retry queue depth {v}",
    "INFO node-exporter: scrape completed in {v} ms",
)

# session-lifecycle heartbeat cadence (rank-0 progress line)
_HEARTBEAT_H = 0.5


@dataclass(frozen=True)
class LogLine:
    """One synthetic log line.  ``node == -1`` is the controller."""
    time_h: float
    node: int
    text: str

    @property
    def level(self) -> str:
        return self.text.split(" ", 1)[0]


class LogEmitter:
    """Turns a failure schedule + chunk windows into a log stream.

    Usage (both engines follow the same order):

    1. construct with the campaign's ``(n_nodes, seed)``;
    2. ``register_failure(ev)`` for every scheduled event, in schedule
       (time) order — all fault-program draws happen here;
    3. ``emit_window(t0, t1, gang)`` once per emitted telemetry chunk,
       with contiguous ``[t0, t1)`` windows — noise draws happen here.
    """

    def __init__(self, n_nodes: int, seed: int,
                 noise_per_node_h: float = 1.0):
        self.n_nodes = n_nodes
        self.noise_per_node_h = noise_per_node_h
        self.rng = np.random.default_rng([seed, RNG_STREAM_LOGS])
        # (time_h, node, text, gang_wide); for gang_wide entries ``node``
        # is the *referenced* root cause and the line materialises on every
        # other current gang member at emission
        self._prog: List[tuple] = []
        self._cursor = 0
        self._sealed = False

    # -- registration (schedule order; all fault draws live here) ----------

    def register_failure(self, ev) -> None:
        if self._sealed:
            raise RuntimeError("register_failure after first emit_window")
        kind = getattr(ev, "kind", "xid")
        handler = getattr(self, f"_reg_{kind}", None)
        if handler is not None:
            handler(ev)

    def _add(self, t: float, node: int, text: str, gang: bool = False):
        self._prog.append((max(float(t), 0.0), int(node), text, gang))

    def _spread(self, t0: float, width: float, rate_h: float) -> np.ndarray:
        """Jittered stall-cluster times across a degradation window, with
        the first cluster pinned near the window's onset."""
        n = max(3, int(round(width * rate_h)))
        ts = t0 + width * np.sort(self.rng.uniform(0.0, 1.0, n))
        ts[0] = t0 + min(0.02, 0.3 * width)
        return ts

    def _reg_xid(self, ev) -> None:
        rng = self.rng
        t, node = float(ev.time_h), int(ev.node)
        lead = max(float(getattr(ev, "precursor_lead_h", 0.0)), 0.0)
        if lead > 0:
            # a couple of *rare* correctable-ECC errors right after onset
            # (the gpu124 row-remap story) — the analyzer's rarity signal
            n_early = 2 + int(rng.integers(0, 2))
            for dt in rng.uniform(0.0, min(0.2 * lead + 0.02, lead),
                                  n_early):
                self._add(t - lead + float(dt), node,
                          "ERROR dcgm: gpu 0: row remap pending, "
                          "correctable ECC error count rising")
            # warn ramp accelerating toward the failure point
            n_ramp = max(4, int(round(lead * 10.0)))
            for u in rng.uniform(0.0, 1.0, n_ramp):
                self._add(t - lead + lead * float(math.sqrt(u)), node,
                          f"WARN dcgm: volatile sbe retired pages "
                          f"{int(rng.integers(1, 64))} on gpu 0")
        xid = int(ev.xid) if getattr(ev, "xid", None) is not None else 79
        for j in range(3 + int(rng.integers(0, 3))):
            self._add(t + 1e-4 * (j + 1), node,
                      f"ERROR NVRM: Xid (PCI:0000:b1:00): {xid}, "
                      f"pid={int(rng.integers(2000, 32768))}, "
                      f"name=trainer, GPU fault detected")
        self._add(t + 8e-4, node,
                  "ERROR trainer: CUDA error: uncorrectable ECC or "
                  "device-side fault, aborting rank")
        self._add(t + 2e-3, node,
                  f"WARN NCCL: watchdog timeout on collective, peer rank "
                  f"on node-{node} unresponsive", gang=True)
        self._add(t + 0.03, -1,
                  f"INFO launcher: session abort attributed to "
                  f"node-{node}, retry chain scheduled")

    def _reg_unreachable(self, ev) -> None:
        t, node = float(ev.time_h), int(ev.node)
        # the node itself goes silent; only the peers speak (the Mycroft
        # setting: attribution must come from cross-node references)
        self._add(t + 1e-3, node,
                  f"ERROR NCCL: connect to node-{node} failed: "
                  f"Connection timed out", gang=True)
        self._add(t + 2e-3, node,
                  f"WARN gang: heartbeat lost for node-{node}, "
                  f"evicting from ring", gang=True)
        self._add(t + 0.03, -1,
                  f"INFO launcher: node-{node} unreachable, "
                  f"session restart queued")

    def _reg_fail_slow(self, ev) -> None:
        rng = self.rng
        t, node = float(ev.time_h), int(ev.node)
        pre = min(0.5, t)
        for u in rng.uniform(0.0, 1.0, 3 + int(rng.poisson(2.0))):
            self._add(t - pre + pre * float(u), node,
                      "WARN trainer: kernel launch latency high on gpu 0, "
                      "step time degraded")
        self._add(t + 1e-3, node,
                  f"WARN NCCL: rank on node-{node} lagging collective, "
                  f"allreduce stalled", gang=True)
        self._add(t + 0.03, -1,
                  f"INFO launcher: slow rank report filed for node-{node}")

    def _reg_net_degrade(self, ev) -> None:
        rng = self.rng
        t, node = float(ev.time_h), int(ev.node)
        w = max(float(getattr(ev, "window_h", 0.0)), 0.1)
        # correlated storage-stall clusters: each RPC stall produces the
        # kernel NFS line plus transport symptoms within milliseconds
        for tt in self._spread(t, w, rate_h=10.0):
            tt = float(tt)
            self._add(tt, node,
                      "ERROR nfs: server storage-0 not responding, "
                      "still trying")
            self._add(tt + 1e-4, node,
                      f"WARN rpc: retransmit threshold exceeded on mount "
                      f"/ckpt, {int(rng.integers(10, 400))} ops queued")
            self._add(tt + 2e-4, node,
                      "WARN net: tcp transport backlog rising on bond0")
        self._add(t + w + 1e-3, node,
                  "INFO nfs: server storage-0 OK, operations resumed")

    def _reg_resource_exhaust(self, ev) -> None:
        rng = self.rng
        t, node = float(ev.time_h), int(ev.node)
        w = max(float(getattr(ev, "window_h", 0.0)), 0.1)
        for tt in self._spread(t, w, rate_h=10.0):
            tt = float(tt)
            self._add(tt, node,
                      f"ERROR kernel: page allocation stall for "
                      f"{int(rng.integers(1000, 30000))} ms in kswapd0")
            self._add(tt + 1e-4, node,
                      "WARN mm: available memory low, "
                      "reclaim pressure rising")
            self._add(tt + 2e-4, node,
                      f"WARN cgroup: memory usage "
                      f"{int(rng.integers(90, 100))} percent of limit "
                      f"on trainer slice")
        if bool(getattr(ev, "escalate", False)):
            for j in range(3):
                self._add(t + w + 1e-4 * (j + 1), node,
                          f"ERROR oom-killer: invoked, killed trainer "
                          f"pid {int(rng.integers(2000, 32768))}")
        else:
            self._add(t + w + 1e-3, node,
                      "INFO mm: memory pressure cleared, reclaim idle")

    def _reg_switch_degrade(self, ev) -> None:
        rng = self.rng
        t = float(ev.time_h)
        w = max(float(getattr(ev, "window_h", 0.0)), 0.1)
        members = [int(m) for m in getattr(ev, "members", ())]
        sw = int(getattr(ev, "switch", -1))
        # the correlated shape a per-node program cannot produce: every
        # member of the rack logs transport symptoms inside the same
        # stall cluster, because the fault lives in the shared leaf
        for tt in self._spread(t, w, rate_h=8.0):
            tt = float(tt)
            for i, node in enumerate(members):
                self._add(tt + 1e-4 * i, node,
                          f"ERROR net: uplink errors via leaf switch, tcp "
                          f"retransmit storm on bond0, "
                          f"{int(rng.integers(50, 900))} segments resent")
        self._add(t + 1e-3, -1,
                  f"WARN fabric: leaf switch {sw} reporting degraded "
                  f"links on {len(members)} ports")
        self._add(t + w + 1e-3, -1,
                  f"INFO fabric: leaf switch {sw} link quality restored")

    def _reg_dns_flap(self, ev) -> None:
        rng = self.rng
        t = float(ev.time_h)
        w = max(float(getattr(ev, "window_h", 0.0)), 0.05)
        peers = [int(p) for p in getattr(ev, "peers", ())]
        members = [int(m) for m in getattr(ev, "members", ())]
        if not peers:
            return
        peer = peers[0]
        # partial-gang connectivity loss: only the flapped members speak,
        # and they all name the same unreachable peer (the Mycroft
        # setting again — the analyzer indicts the peer from references)
        for i, node in enumerate(members):
            self._add(t + 1e-4 * (i + 1), node,
                      f"ERROR rpc: name resolution for node-{peer} "
                      f"failed, transport reset after "
                      f"{int(rng.integers(1, 30))} retries")
        self._add(t + w + 1e-3, -1,
                  f"INFO dns: record for node-{peer} restored, "
                  f"flap cleared")

    def _reg_ctrl_blind(self, ev) -> None:
        t = float(ev.time_h)
        w = max(float(getattr(ev, "window_h", 0.0)), 0.0)
        self._add(t + 1e-3, -1,
                  "ERROR scheduler: control plane heartbeat missed, "
                  "decisions suspended")
        self._add(t + w, -1,
                  "INFO scheduler: control plane recovered, "
                  "replaying queued decisions")

    # -- emission (chunk order; noise draws live here) ----------------------

    def emit_window(self, t0: float, t1: float,
                    gang: Sequence[int]) -> List[LogLine]:
        """All log lines with ``t0 <= time < t1``; ``gang`` is the node set
        of the currently-running session (empty when idle)."""
        if not self._sealed:
            self._prog.sort(key=lambda p: p[0])
            self._sealed = True
        if t1 <= t0:
            return []
        gang_sorted = sorted(int(g) for g in gang) if len(gang) else []
        lines: List[LogLine] = []
        # 1) fault-program lines (registered; cursor over the sorted list)
        n = len(self._prog)
        while self._cursor < n and self._prog[self._cursor][0] < t1:
            t, node, text, gang_wide = self._prog[self._cursor]
            self._cursor += 1
            if t < t0:
                continue          # pre-campaign precursor tail, clamped out
            if gang_wide:
                for i, nd in enumerate(gang_sorted):
                    if nd == node:
                        continue  # the root cause does not report itself
                    lines.append(LogLine(t + 3e-5 * i, nd, text))
            else:
                lines.append(LogLine(t, node, text))
        # 2) lifecycle heartbeat: rank 0 reports progress on a fixed grid
        if gang_sorted:
            k = int(math.ceil(t0 / _HEARTBEAT_H - 1e-9))
            rank0 = gang_sorted[0]
            while k * _HEARTBEAT_H < t1 - 1e-12:
                tk = k * _HEARTBEAT_H
                if tk >= t0:
                    lines.append(LogLine(
                        tk, rank0,
                        f"INFO trainer: global step {k * 1800} complete, "
                        f"loss curve nominal"))
                k += 1
        # 3) background noise (window-level draws, chunk order)
        rng = self.rng
        span = t1 - t0
        count = int(rng.poisson(self.noise_per_node_h * self.n_nodes * span))
        if count:
            times = t0 + span * rng.uniform(0.0, 1.0, count)
            nodes = rng.integers(0, self.n_nodes, count)
            idxs = rng.integers(0, len(NOISE_TEMPLATES), count)
            vals = rng.integers(0, 100000, count)
            for j in range(count):
                lines.append(LogLine(
                    float(times[j]), int(nodes[j]),
                    NOISE_TEMPLATES[idxs[j]].format(v=int(vals[j]))))
        lines.sort(key=lambda ln: ln.time_h)   # stable: ties keep build order
        return lines
