"""Per-node exporter models (dcgm-exporter / node_exporter / all-smi /
Backend.AI scheduler metrics).

Each exporter emits the metric vocabulary the paper's analysis actually used
(§4.1 figures) with realistic healthy baselines, plus failure-signature hooks
that the failure injector drives:

* NVLink/Bus fault (XID 79/145/149): node_intr_total 30s-increment collapses
  ~300K -> 70-100K; node_procs_running -> 0 (paper Fig 2).
* ECC (XID 94): NFS GETATTR response-time and pgpgout surge (paper Fig 3);
  DCGM uncorrectable row-remap counter steps up (paper Fig 4).
* Gradual precursors (the 2/10 pre-XID cases): accelerating correctable
  row-remaps and creeping temperature before the XID fires.
* Fail-slow: GPU util dips + per-step time inflation without any XID.

Generation is batched: ``tick_batch`` produces (n_ticks, n_nodes) arrays for
a whole span of scrape ticks in one set of numpy draws, which is what makes
the event-driven cluster simulation fast (the per-tick ``tick`` wrapper is
kept for single-scrape callers and tests).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from refsim.core.failures import FailureEvent, onset_progress
from refsim.storage.fabric import StorageFabric
from refsim.telemetry.registry import MetricMeta, MetricRegistry

# The full production pipeline carries ~751 metric names, ~305 analysis-
# relevant (paper §3.4).  We model the ~30 the analyses actually read and
# pad the registry with inert extras so detector cost/FP behaviour is
# realistic at the true metric count.  Sweeps that only need F3/F4 can
# shrink the pad (``n_pad``) to bound the time-series store footprint.
N_PAD_METRICS = 275

CORE_METRICS = [
    # node_exporter
    ("node_intr_total", "counter", "node"),
    ("node_procs_running", "gauge", "node"),
    ("node_procs_blocked", "gauge", "node"),
    ("node_vmstat_pgpgout", "counter", "node"),
    ("node_vmstat_pgpgin", "counter", "node"),
    ("node_memory_MemAvailable_bytes", "gauge", "node"),
    ("node_memory_Dirty_bytes", "gauge", "node"),
    ("node_memory_Writeback_bytes", "gauge", "node"),
    ("node_mountstats_nfs_operations_response_time_seconds_total:GETATTR",
     "counter", "node"),
    ("node_mountstats_nfs_operations_queue_time_seconds_total:WRITE",
     "counter", "node"),
    ("node_mountstats_nfs_read_bytes_total", "counter", "node"),
    ("node_mountstats_nfs_write_bytes_total", "counter", "node"),
    # storage-fabric F2 signals: RPC queue depth and transport backlog
    # rise together during save/load bursts (paper §4.2.5)
    ("node_mountstats_nfs_rpc_queue_depth", "gauge", "node"),
    ("node_netstat_Tcp_transport_backlog_bytes", "gauge", "node"),
    ("node_network_transmit_bytes_total", "counter", "node"),
    ("node_network_receive_bytes_total", "counter", "node"),
    ("node_infiniband_port_data_transmitted_bytes_total", "counter", "node"),
    ("node_infiniband_port_data_received_bytes_total", "counter", "node"),
    ("node_sockstat_TCP_alloc", "gauge", "node"),
    ("node_context_switches_total", "counter", "node"),
    # dcgm-exporter
    ("DCGM_FI_DEV_GPU_UTIL", "gauge", "dcgm"),
    ("DCGM_FI_DEV_GPU_TEMP", "gauge", "dcgm"),
    ("DCGM_FI_DEV_POWER_USAGE", "gauge", "dcgm"),
    ("DCGM_FI_DEV_FB_USED", "gauge", "dcgm"),
    ("DCGM_FI_DEV_SM_CLOCK", "gauge", "dcgm"),
    ("DCGM_FI_DEV_ROW_REMAP_UNCORRECTABLE", "counter", "dcgm"),
    ("DCGM_FI_DEV_ROW_REMAP_CORRECTABLE", "counter", "dcgm"),
    ("DCGM_FI_DEV_XID_ERRORS", "gauge", "dcgm"),
    ("DCGM_FI_DEV_NVLINK_BANDWIDTH_TOTAL", "counter", "dcgm"),
    # all-smi
    ("all_smi_gpu_power_watts", "gauge", "all_smi"),
    ("all_smi_sys_memory_used_bytes", "gauge", "all_smi"),
    # Backend.AI scheduler
    ("backendai_rpc_latency_ms", "gauge", "backendai"),
    ("backendai_active_sessions", "gauge", "backendai"),
    ("backendai_async_task_count", "gauge", "backendai"),
    ("backendai_agent_heartbeat_age_s", "gauge", "backendai"),
]


@dataclass
class NodeState:
    """What the simulated node is doing right now (drives exporter values)."""
    training: bool = True
    checkpointing: bool = False
    loading: bool = False
    down: bool = False
    slow_factor: float = 1.0


@dataclass
class NodeStateBatch:
    """Node activity over a span of scrape ticks, as (n_ticks, n_nodes)
    arrays.  Within a span between discrete events the per-node role is
    constant, so callers usually broadcast a single (n_nodes,) row."""
    training: np.ndarray
    checkpointing: np.ndarray
    loading: np.ndarray
    down: np.ndarray
    slow: np.ndarray

    @classmethod
    def from_states(cls, states: Sequence[NodeState]) -> "NodeStateBatch":
        """One tick (T=1) from a list of per-node states — a single pass
        over the states into one (5, n) block, then unstacked."""
        block = np.array([(s.training, s.checkpointing, s.loading,
                           s.down, s.slow_factor) for s in states],
                         dtype=float).T.reshape(5, 1, -1)
        return cls(training=block[0], checkpointing=block[1],
                   loading=block[2], down=block[3], slow=block[4])

    @classmethod
    def constant(cls, n_ticks: int, n_nodes: int, *,
                 training=None, checkpointing=None, loading=None,
                 down=None, slow=None) -> "NodeStateBatch":
        """Broadcast per-node rows (or tick-varying arrays) to (T, n)."""
        def expand(x, fill=0.0):
            if x is None:
                return np.full((n_ticks, n_nodes), fill)
            x = np.asarray(x, dtype=float)
            return np.broadcast_to(x, (n_ticks, n_nodes)).copy() \
                if x.ndim < 2 else x.astype(float)
        return cls(training=expand(training),
                   checkpointing=expand(checkpointing),
                   loading=expand(loading),
                   down=expand(down),
                   slow=expand(slow, fill=1.0))


class ExporterSuite:
    """Generates scrape ticks of all metrics for all nodes."""

    def __init__(self, n_nodes: int, seed: int = 0,
                 n_pad: int = N_PAD_METRICS,
                 storage_levels: Optional[Dict[str, float]] = None):
        self.n = n_nodes
        self.n_pad = n_pad
        # characteristic RPC queue depth / transport backlog while a
        # save/load is in flight, from the shared storage fabric at the
        # campaign's gang fanin (paper-default fabric when not supplied)
        self.storage_levels = storage_levels \
            or StorageFabric().telemetry_levels(60)
        self.rng = np.random.default_rng(seed)
        self.reg = MetricRegistry(n_nodes)
        for name, kind, exp in CORE_METRICS:
            self.reg.register(MetricMeta(name, kind, exp))
        for i in range(n_pad):
            self.reg.register(MetricMeta(f"aux_metric_{i:03d}", "gauge", "node"))
        # persistent per-node counters
        self.remap_corr = np.zeros(n_nodes)
        self.remap_uncorr = np.zeros(n_nodes)
        self.accel_nodes: Dict[int, tuple] = {}   # node -> (onset_h, until_h)
        # infra fault band windows (registered at campaign setup)
        self.degradations: List[tuple] = []   # (node, t0, t1, sev, kind,
                                              #  onset)
        self.outages: List[tuple] = []        # (t0, t1) control-plane blind

    # -- failure signature hooks (called by the cluster sim) ---------------

    def begin_gradual_precursor(self, node: int, t_h: float,
                                until_h: float = float("inf")):
        self.accel_nodes[node] = (t_h, until_h)

    def begin_degradation(self, node: int, t0_h: float, t1_h: float,
                          severity: float, kind: str, onset: str):
        """Register a degrade-band window ([t0, t1), net/resource kind)."""
        self.degradations.append((node, t0_h, t1_h, severity, kind, onset))

    def begin_link_degradation(self, nodes, t0_h: float, t1_h: float,
                               severity: float, onset: str = "spike"):
        """Correlated fault band: one fabric event (switch degradation or
        a dns flap's affected links) degrades *every* listed node for the
        same window.  Registers the shared window per node through the
        net-degrade overlay — deterministic and RNG-free, so gang members
        co-degrade with the exact correlated timing the detector's
        cross-node pass keys on."""
        for node in nodes:
            self.begin_degradation(int(node), t0_h, t1_h, severity,
                                   "net_degrade", onset)

    def begin_outage(self, t0_h: float, t1_h: float):
        """Register a control-plane blind window (scheduler outage)."""
        self.outages.append((t0_h, t1_h))

    # -- single-tick compatibility wrapper ---------------------------------

    def tick(self, t_h: float, states: List[NodeState],
             failures_now: List[FailureEvent]) -> Dict[str, np.ndarray]:
        """Produce one 30-second scrape snapshot at time ``t_h`` (hours)."""
        batch = NodeStateBatch.from_states(states)
        out = self.tick_batch(np.array([t_h]), batch,
                              [(0, ev) for ev in failures_now])
        return {k: v[0] for k, v in out.items()}

    # -- batched generation -------------------------------------------------

    def tick_batch(self, ts: np.ndarray, batch: NodeStateBatch,
                   failure_rows: Sequence[Tuple[int, FailureEvent]] = ()
                   ) -> Dict[str, np.ndarray]:
        """Produce ``len(ts)`` scrape snapshots at once.

        ``ts``: (T,) scrape times in hours; ``batch``: (T, n) activity masks;
        ``failure_rows``: (row_index, event) pairs pinning each failure's
        abrupt signature to the scrape tick it lands on.  Returns
        metric -> (T, n) arrays.  Persistent counters (row-remaps) advance
        by cumulative sums so per-tick semantics match the serial loop.
        """
        n = self.n
        r = self.rng
        ts = np.asarray(ts, dtype=float)
        T = len(ts)
        up = 1.0 - np.asarray(batch.down, dtype=float)
        training = np.asarray(batch.training, dtype=float) * up
        ckpt = np.asarray(batch.checkpointing, dtype=float)
        load = np.asarray(batch.loading, dtype=float)
        slow = np.asarray(batch.slow, dtype=float)
        shape = (T, n)

        v: Dict[str, np.ndarray] = {}
        # host interrupts: ~300K/30s while the GPUs generate work
        v["node_intr_total"] = (300e3 * training / slow + 40e3 * up
                                + r.normal(0, 8e3, shape)) * up
        v["node_procs_running"] = (34 * training + 2 * up
                                   + r.integers(0, 3, shape)) * up
        v["node_procs_blocked"] = (r.integers(0, 2, shape) + 30 * ckpt) * up
        v["node_vmstat_pgpgout"] = (2e4 + 3e6 * ckpt
                                    + r.normal(0, 5e3, shape)) * up
        v["node_vmstat_pgpgin"] = (2e4 + 5e6 * load
                                   + r.normal(0, 5e3, shape)) * up
        v["node_memory_MemAvailable_bytes"] = \
            (1.9e12 - 1e11 * training + r.normal(0, 2e10, shape)) * up
        v["node_memory_Dirty_bytes"] = (1e8 + 2.4e10 * ckpt
                                        + r.normal(0, 3e7, shape)) * up
        v["node_memory_Writeback_bytes"] = (5e6 + 1.2e10 * ckpt
                                            + r.normal(0, 1e6, shape)) * up
        v["node_mountstats_nfs_operations_response_time_seconds_total:GETATTR"] = \
            (0.05 + 0.4 * load + r.exponential(0.01, shape)) * up
        v["node_mountstats_nfs_operations_queue_time_seconds_total:WRITE"] = \
            (0.01 + 45.0 * ckpt + r.exponential(0.005, shape)) * up
        v["node_mountstats_nfs_read_bytes_total"] = \
            (1e6 + 4.2e9 * 30 * load + r.normal(0, 1e5, shape)).clip(0) * up
        v["node_mountstats_nfs_write_bytes_total"] = \
            (1e5 + 0.6e9 * 30 * ckpt + r.normal(0, 1e4, shape)).clip(0) * up
        # fabric F2 signals: queue depth and backlog rise TOGETHER during
        # save/load bursts; fail-slow nodes sit above their peers (slow >= 1)
        lv = self.storage_levels
        v["node_mountstats_nfs_rpc_queue_depth"] = \
            ((2.0 + lv["save_queue_depth"] * ckpt
              + lv["load_queue_depth"] * load
              + r.exponential(1.0, shape)) * slow) * up
        v["node_netstat_Tcp_transport_backlog_bytes"] = \
            ((1e4 + lv["save_backlog_bytes"] * ckpt
              + lv["load_backlog_bytes"] * load
              + r.exponential(5e3, shape)) * slow) * up
        v["node_network_transmit_bytes_total"] = \
            (2e8 + r.normal(0, 1e7, shape)) * up
        v["node_network_receive_bytes_total"] = \
            (2e8 + r.normal(0, 1e7, shape)) * up
        ib = 30 * 100e9 * training / slow         # ~100 GB/s sustained DP traffic
        v["node_infiniband_port_data_transmitted_bytes_total"] = \
            (ib + r.normal(0, 1e10, shape)).clip(0) * up
        v["node_infiniband_port_data_received_bytes_total"] = \
            (ib + r.normal(0, 1e10, shape)).clip(0) * up
        v["node_sockstat_TCP_alloc"] = (180 + 40 * load
                                        + r.integers(-10, 10, shape)) * up
        v["node_context_switches_total"] = (8e5 * training / slow + 1e5 * up
                                            + r.normal(0, 2e4, shape)) * up
        v["DCGM_FI_DEV_GPU_UTIL"] = \
            (99.3 * training / slow - 60 * ckpt - 80 * load
             + r.normal(0, 0.4, shape)).clip(0, 100) * up
        v["DCGM_FI_DEV_GPU_TEMP"] = (62 * training + 35
                                     + r.normal(0, 1.5, shape)) * up
        v["DCGM_FI_DEV_POWER_USAGE"] = (950 * training / slow + 120
                                        + r.normal(0, 25, shape)) * up
        v["DCGM_FI_DEV_FB_USED"] = (1.66e11 * training + 2e9) * up
        v["DCGM_FI_DEV_SM_CLOCK"] = (1980 * training + 210
                                     + r.normal(0, 20, shape)) * up
        v["DCGM_FI_DEV_NVLINK_BANDWIDTH_TOTAL"] = \
            (30 * 4.5e11 * training / slow + r.normal(0, 1e11, shape)).clip(0) * up
        v["all_smi_gpu_power_watts"] = v["DCGM_FI_DEV_POWER_USAGE"] * 1.02
        v["all_smi_sys_memory_used_bytes"] = (2.1e11 + 2.4e10 * ckpt
                                              + r.normal(0, 5e9, shape)) * up
        v["backendai_rpc_latency_ms"] = (3 + r.exponential(1.5, shape)) * up
        v["backendai_active_sessions"] = training
        v["backendai_async_task_count"] = (12 + 30 * ckpt
                                           + r.integers(0, 5, shape)) * up
        v["backendai_agent_heartbeat_age_s"] = r.uniform(0, 35, shape) \
            + 600 * (1 - up)

        # persistent counters: per-tick increments, then a cumulative sum so
        # every tick of the span observes the running value
        corr_inc = (r.random(shape) < 0.001).astype(float)
        uncorr_inc = np.zeros(shape)

        # gradual precursors (accelerating correctable remaps + thermal /
        # clock / latency drift, paper Fig 4): multiple metrics deviate so
        # the multi-signal vote can fire BEFORE the XID for long-lead cases
        for node, (onset, until) in self.accel_nodes.items():
            active = (ts >= onset) & (ts < until)
            if not active.any():
                continue
            # clamp dt at 0 outside the window: a negative base under the
            # fractional power would give NaN, and NaN * 0-mask is still NaN
            dt = np.where(active, ts - onset, 0.0)
            prog = np.minimum(dt / 0.5, 4.0) * active
            corr_inc[:, node] += 0.4 * (1 + dt) ** 1.5 * active
            v["DCGM_FI_DEV_GPU_TEMP"][:, node] += 5.0 * prog
            v["DCGM_FI_DEV_POWER_USAGE"][:, node] += 60.0 * prog
            v["DCGM_FI_DEV_SM_CLOCK"][:, node] -= 30.0 * prog
            v["backendai_rpc_latency_ms"][:, node] += 4.0 * prog

        # degrade-band windows: deterministic overlays on the drawn arrays
        # (no extra RNG, so campaigns without infra faults stay bit-
        # identical).  Each kind deviates >= 5 node-local metrics so the
        # detector's min_signals vote can fire; gang-wide components are
        # uniform across nodes, which peer z-scoring is deliberately
        # silent on (attribution needs the node-local signals)
        for node, d0, d1, sev, kind, onset in self.degradations:
            prog = onset_progress(ts, d0, d1, onset)
            if not prog.any():
                continue
            sevx = (sev - 1.0) * prog * up[:, node]
            if kind == "net_degrade":
                qd = lv.get("degrade_queue_depth", 60.0)
                bb = lv.get("degrade_backlog_bytes", 2e7)
                v["node_mountstats_nfs_rpc_queue_depth"][:, node] += \
                    qd * sevx
                v["node_netstat_Tcp_transport_backlog_bytes"][:, node] += \
                    bb * sevx
                v["backendai_rpc_latency_ms"][:, node] += 50.0 * sevx
                v["node_sockstat_TCP_alloc"][:, node] += 400.0 * sevx
                v["node_mountstats_nfs_operations_response_time_seconds_total:GETATTR"][:, node] += 1.5 * sevx
                # collective step time inflates for the whole gang: every
                # node's transport backlog rises with the degraded peer
                v["node_netstat_Tcp_transport_backlog_bytes"] += \
                    (0.01 * bb * (sev - 1.0) * prog)[:, None] * up
            else:                              # resource_exhaust
                v["node_memory_MemAvailable_bytes"][:, node] -= 9e11 * sevx
                v["all_smi_sys_memory_used_bytes"][:, node] += 1.5e11 * sevx
                v["node_vmstat_pgpgout"][:, node] += 3e5 * sevx
                v["node_context_switches_total"][:, node] += 5e5 * sevx
                v["DCGM_FI_DEV_GPU_UTIL"][:, node] -= 15.0 * sevx
        for o0, o1 in self.outages:
            mask = ((ts >= o0) & (ts < o1)).astype(float)
            if mask.any():
                # scheduler outage: agent heartbeats age out gang-wide
                # (uniform -> no per-node alarm; the control plane itself
                # is what goes dark)
                v["backendai_agent_heartbeat_age_s"] += \
                    (300.0 * mask)[:, None] * up

        # abrupt failure signatures, pinned to their scrape tick
        xid_now = np.zeros(shape)
        for row, ev in failure_rows:
            node = ev.node
            if ev.kind == "xid":
                xid_now[row, node] = ev.xid
                if ev.xid in (79, 145, 149):          # NVLink / bus fault
                    v["node_intr_total"][row, node] = r.uniform(70e3, 100e3)
                    v["node_procs_running"][row, node] = 0.0
                    v["DCGM_FI_DEV_NVLINK_BANDWIDTH_TOTAL"][row, node] = 0.0
                    v["DCGM_FI_DEV_GPU_UTIL"][row, node] = 0.0
                elif ev.xid == 94:                     # ECC
                    v["node_mountstats_nfs_operations_response_time_seconds_total:GETATTR"][row, node] += 3.0
                    v["node_vmstat_pgpgout"][row, node] += 4e6
                    uncorr_inc[row, node] += r.integers(1, 3)
                    v["node_procs_running"][row, node] = 0.0
                elif ev.xid == 119:                    # GSP RPC timeout
                    v["backendai_rpc_latency_ms"][row, node] += 500
                    v["DCGM_FI_DEV_SM_CLOCK"][row, node] = 210
                    v["DCGM_FI_DEV_GPU_UTIL"][row, node] = 0.0
                else:                                  # 31/43 app-level
                    # dead worker: host stops generating device-driven load
                    v["node_procs_running"][row, node] = 0.0
                    v["DCGM_FI_DEV_GPU_UTIL"][row, node] = 0.0
                    v["node_intr_total"][row, node] = r.uniform(90e3, 130e3)
                    v["node_context_switches_total"][row, node] = \
                        r.uniform(1e5, 2e5)
                    v["DCGM_FI_DEV_POWER_USAGE"][row, node] = r.uniform(120, 180)
                    v["DCGM_FI_DEV_NVLINK_BANDWIDTH_TOTAL"][row, node] = 0.0
            elif ev.kind == "unreachable":
                for key in v:
                    v[key][row, node] = 0.0
                v["backendai_agent_heartbeat_age_s"][row, node] = 600.0

        v["DCGM_FI_DEV_XID_ERRORS"] = xid_now
        corr_series = self.remap_corr[None, :] + np.cumsum(corr_inc, axis=0)
        uncorr_series = self.remap_uncorr[None, :] + np.cumsum(uncorr_inc,
                                                              axis=0)
        self.remap_corr = corr_series[-1].copy()
        self.remap_uncorr = uncorr_series[-1].copy()
        v["DCGM_FI_DEV_ROW_REMAP_CORRECTABLE"] = corr_series
        v["DCGM_FI_DEV_ROW_REMAP_UNCORRECTABLE"] = uncorr_series

        # inert padding metrics (white noise — detector must not alarm on
        # them); one float32 draw for the whole pad block (the detector's
        # robust z-scores don't need float64 on ~N(50,5) noise)
        if self.n_pad:
            pads = 5.0 * r.standard_normal((self.n_pad, T, n),
                                           dtype=np.float32) + np.float32(50.0)
            pads *= up[None].astype(np.float32)
            for i in range(self.n_pad):
                v[f"aux_metric_{i:03d}"] = pads[i]
        return v
