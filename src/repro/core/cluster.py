"""End-to-end cluster campaign simulation.

Drives a training campaign through: the gang scheduler, session lifecycle,
failure injection, telemetry scraping, XID-classified recovery, auto-retry
chains, node exclusion, and checkpoint timing — everything the paper's §4
measures.

Failure semantics (paper §4.3):
* transient failures (most XID hardware events with spares available, app
  errors) — the next gang allocation succeeds and the chain recovers;
* structural failures (software/NCCL-level, license/pool exhaustion) —
  restarts fail repeatedly at PREPARING until an operator intervenes; this
  is what made 8/12 of the paper's chains fail and burned a 30-attempt
  chain (§4.3.5).

Two engines share one campaign state machine (``_CampaignState``):

* ``engine="event"`` (default) — discrete-event loop.  Time jumps straight
  between state-changing events (failure arrivals, retry timers, PREPARING
  completions, repairs); checkpoint ticks are accounted analytically and
  telemetry for the constant-state span between events is generated in one
  batched numpy call (`ExporterSuite.tick_batch`).  This is what makes
  campaign sweeps cheap: a 73-day campaign is a few hundred events instead
  of ~210k 30-second ticks.
* ``engine="tick"`` — the original serial 30 s-tick loop, kept as the
  reference for the speedup benchmark and engine-parity tests.

Used by: benchmarks (taxonomy / precursor / retry / exclusion / downtime),
the scenario sweep runner (`repro.ops`), the fault-tolerant training
example, and the integration tests.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.checkpoint.youngdaly import MTBF_H_PAPER
from repro.control.policy import ControlConfig, ControlPlane, ControlStats
from repro.core import findings
from repro.core.exclusion import ExclusionTracker
from repro.storage.fabric import FabricConfig, StorageFabric
from repro.core.failures import (CORRELATED_KINDS, DEGRADE_KINDS,
                                 FailureEvent, FailureInjector, INFRA_KINDS,
                                 blind_windows, degradation_windows,
                                 degraded_overlap_h, escalation_events)
from repro.core.retry import Attempt, Chain, RetryConfig, RetryEngine
from repro.core.scheduler import GangScheduler
from repro.core.session import Session, SessionState
from repro.core.xid import XID_TABLE
from repro.telemetry.exporters import (ExporterSuite, N_PAD_METRICS,
                                       NodeState, NodeStateBatch)
from repro.telemetry.registry import SCRAPE_INTERVAL_S, TimeSeriesStore

TICK_H = SCRAPE_INTERVAL_S / 3600.0

# batched telemetry emission: cap span chunks so transient (T, n_nodes)
# buffers stay modest even when the campaign runs uninterrupted for days
_MAX_SPAN_TICKS = 2048

# Dedicated rng streams (seeded ``default_rng([seed, salt])``) for the two
# exponential-draw families.  Keeping them off the main ``default_rng(seed)``
# stream leaves that stream consuming *only* ``random()`` uniforms, which
# makes it materializable up front as a flat draw tape (``rng.random(N)``
# equals N sequential ``rng.random()`` calls positionally) — the compiled
# wavefront core (kernels/wavefront) depends on this.  Ziggurat
# exponentials consume a variable number of raw draws per sample, so they
# can only be tape-ified from streams of their own.
RNG_STREAM_MANUAL = 7001      # operator manual-response delays
RNG_STREAM_STRUCT = 7013      # structural-fix (root-cause) durations


@dataclass
class CampaignConfig:
    n_nodes: int = 63
    job_nodes: int = 60
    duration_h: float = 73 * 24.0
    mtbf_h: float = MTBF_H_PAPER
    retry: RetryConfig = field(default_factory=RetryConfig)
    checkpoint_interval_h: float = 2.23      # 4K phase median
    checkpoint_save_s: float = 18.0
    loading_time_h: float = 31.0 / 60.0      # warm-cache restart loading
    loading_cold_h: float = 58.0 / 60.0      # cold cache (node replaced /
                                             #   full reboot; paper §4.2.4)
    # shared-NFS storage fabric: when set, checkpoint_save_s and the two
    # loading times above are REPLACED by fabric queries at the gang fanin
    # (save: the ckpt_pack bf16 wire volume bursting from job_nodes
    # writers; load: restore_bytes_per_node read by the whole gang on top
    # of the non-storage loading overhead)
    storage: Optional[FabricConfig] = None
    storage_slots: int = 128                 # client RPC slot table (loads
                                             #   run over nconnect=2 -> 2x)
    ckpt_bytes_per_node: int = 20 << 30
    ckpt_wire_ratio: float = 0.5             # fp32 -> bf16 ckpt_pack payload
    restore_bytes_per_node: int = 200 << 30
    loading_overhead_h: float = 29.5 / 60.0  # container/NCCL/dataset init
    loading_overhead_cold_h: float = 56.5 / 60.0
    # failure-class behaviour
    p_software_failure: float = 0.5          # NCCL/driver-level (structural)
    p_transient_retry_fail: float = 0.4      # residual issue on early retries
    structural_fix_mean_h: float = 5.0       # time until root cause fixed
    operator_notice_mean_h: float = 1.2      # failing chain noticed & stopped
    p_manual_misfix: float = 0.4             # operator fix incomplete ->
                                             #   next chain fails from start
    manual_response_h_day: float = 0.3
    manual_response_h_night: float = 1.5
    repair_time_h: float = 12.0              # node repair turnaround
    slow_isolation_h: float = 400.0          # fail-slow deliberate isolation
    p_pressure_readmit: float = 0.01         # per failed gang attempt: chance
                                             #   the operator readmits an
                                             #   isolated healthy node; at one
                                             #   attempt per ~11 min this is a
                                             #   mean ~18 h response (paper:
                                             #   the license case took hours)
    # failure-mix shaping (passed through to FailureInjector)
    hot_fraction: float = 0.05
    hot_weight: float = 0.55
    kind_weights: Optional[Dict[str, float]] = None
    topology_fanout: int = 8                 # leaf-switch fanout (the blast
                                             #   radius of switch_degrade)
    telemetry: bool = False
    telemetry_pad_metrics: Optional[int] = None   # None -> full 275-metric pad
    telemetry_store: bool = True             # False: stream-and-discard (the
                                             #   control plane consumes spans
                                             #   online; nothing is retained)
    # online detection->recovery control plane (event engine only).  Setting
    # this implies telemetry generation even when ``telemetry`` is False —
    # the streaming detector consumes the emitted spans.
    control: Optional[ControlConfig] = None
    engine: str = "event"                    # "event" | "tick"
    seed: int = 0


@dataclass
class CampaignResult:
    sessions: List[Session]
    chains: List[Chain]
    failures: List[FailureEvent]
    exclusions: ExclusionTracker
    store: Optional[TimeSeriesStore]
    downtimes: List[dict]                    # per recovery episode
    checkpoint_events: int
    lost_hours: List[float]
    duration_h: float
    checkpoint_save_s: float = 18.0          # resolved save cost (fabric-
                                             #   priced when storage is set)
    control: Optional[ControlStats] = None   # detection->recovery ledger
    degraded_hours: List[float] = field(default_factory=list)
                                             # per session: effective hours
                                             #   lost to degrade-band windows

    def running_h(self) -> float:
        """Running hours of the multi-node sessions, added left to right
        in session order (`repro.core.findings`' float-order rule)."""
        return findings.fold_left(
            s.elapsed_running_h(self.duration_h) for s in self.sessions
            if s.n_nodes > 1)

    def training_occupancy(self) -> float:
        return findings.occupancy(self.running_h(), self.duration_h)

    def goodput_h(self) -> float:
        """Productive training hours: RUNNING wall time minus redone (lost)
        work minus checkpoint-save overhead (scheduled + urgent) minus the
        effective hours eaten by degrade-band windows (a degraded gang
        still runs, just slower).  This is the quantity the proactive
        control plane trades on: urgent saves spend save time to shrink
        the lost-work window; drains spend a controlled restart to dodge
        a crash."""
        return findings.goodput_h(
            self.running_h(), float(np.sum(self.lost_hours)),
            self.checkpoint_events, self.checkpoint_save_s,
            self.control.urgent_save_h if self.control else 0.0,
            float(np.sum(self.degraded_hours)))

    def goodput(self) -> float:
        """Goodput as a fraction of the campaign wall clock."""
        return findings.goodput(self.goodput_h(), self.duration_h)

    def retry_chains(self) -> List[Chain]:
        """Chains with at least one retry (the paper's unit of analysis)."""
        return [c for c in self.chains if len(c.attempts) > 1]


class _CampaignState:
    """Mutable campaign state + transition rules shared by both engines."""

    def __init__(self, cfg: CampaignConfig, rng: np.random.Generator):
        self.cfg = cfg
        self.rng = rng
        # exponential draws live on dedicated streams (see RNG_STREAM_*):
        # the main stream stays pure-uniform and therefore tape-friendly
        self.rng_manual = np.random.default_rng(
            [cfg.seed, RNG_STREAM_MANUAL])
        self.rng_struct = np.random.default_rng(
            [cfg.seed, RNG_STREAM_STRUCT])
        self.sched = GangScheduler(cfg.n_nodes,
                                   spares=cfg.n_nodes - cfg.job_nodes)
        self.retry_engine = RetryEngine(cfg.retry)
        self.exclusions = ExclusionTracker(cfg.n_nodes)

        self.sessions: List[Session] = []
        self.chains: List[Chain] = []
        self.downtimes: List[dict] = []
        self.lost_hours: List[float] = []
        self.ckpt_events = 0
        self.version = 0

        self.isolated: Dict[int, str] = {}       # node -> reason
        self.repair_until: Dict[int, float] = {}

        self.chain = Chain(task_name=f"b200_v{self.version}")
        self.chains.append(self.chain)
        self.current: Optional[Session] = None
        self.prepare_until = 0.0
        self.prepare_fails = False               # structural: PREPARING fails
        self.structural_until = -1.0             # root cause fixed then
        self.pending_start: Optional[float] = 0.0  # next attempt start time
        self.start_is_manual = True              # operator-initiated attempt
        # two checkpoint clocks: ``last_ckpt`` is the scheduled cadence;
        # ``last_save`` is the effective latest save (urgent control-plane
        # saves advance it past the cadence).  Without a control plane the
        # two are always equal.
        self.last_ckpt = 0.0
        self.last_save = 0.0
        self.down_since: Optional[float] = None
        self.down_is_auto = True
        self.down_kind = "failure"               # "failure" | "drain"
        self.last_fail_hardware = False
        self.control: Optional[ControlPlane] = None
        # degrade-band ledger: windows from the sampled schedule, and the
        # per-session effective hours they cost (closed in event order)
        self.deg_windows: List[tuple] = []
        self.degraded: List[float] = []

    # -- attempt lifecycle --------------------------------------------------

    def start_attempt(self, t: float) -> bool:
        cfg, rng = self.cfg, self.rng
        s = Session(task_name=self.chain.task_name, n_nodes=cfg.job_nodes,
                    created_h=t)
        # alarm-informed placement: retries prefer nodes without a recent
        # alarm (the gang requirement still wins when the pool is tight)
        avoid = self.control.avoid_nodes(t) if self.control is not None \
            else None
        if not self.sched.try_allocate(s, t, avoid=avoid):
            # gang unmet: operators readmit a deliberately-isolated node
            # under pressure if it is healthy (paper: the license case took
            # hours) — only fail-slow isolations qualify; hardware-down
            # nodes stay out until repaired
            cand = [i for i in self.isolated
                    if self.sched.nodes[i].healthy]
            if cand and rng.random() < cfg.p_pressure_readmit:
                self.sched.readmit(cand[0], t)
                self.isolated.pop(cand[0], None)
                self.repair_until.pop(cand[0], None)
            self.chain.attempts.append(
                Attempt(start_h=t, end_h=t, failure_kind="alloc_fail"))
            return False
        s.transition(SessionState.PREPARING, t)
        self.sessions.append(s)
        self.chain.attempts.append(Attempt(start_h=t))
        self.current = s
        self.prepare_fails = t < self.structural_until
        # residual transient issues can also kill the first retry or two
        # (node not yet isolated, stale NCCL state) — paper's successful
        # chains still averaged >1 retry
        if not self.prepare_fails and len(self.chain.attempts) in (2, 3) \
                and rng.random() < cfg.p_transient_retry_fail:
            self.prepare_fails = True
        warm = cfg.loading_cold_h if self.last_fail_hardware \
            else cfg.loading_time_h
        dur = (warm + rng.uniform(-0.08, 0.3)) \
            if not self.prepare_fails else rng.uniform(0.05, 0.15)
        self.prepare_until = t + dur
        return True

    def account_degradation(self, t1: float):
        """Close the degradation ledger for the current session's RUNNING
        span ending at ``t1`` (called wherever the span closes: failure,
        drain, or campaign end)."""
        cur = self.current
        if cur is None or cur.started_h is None or not self.deg_windows:
            return
        d = degraded_overlap_h(self.deg_windows, cur.started_h, t1,
                               cur.nodes)
        if d:
            self.degraded.append(d)

    def exclusion_reasons(self, t0: float, t1: float) -> Dict[int, str]:
        """Per-node exclusion attribution for a session interval: the
        isolation ledger first (first-reason-wins in the tracker), then the
        control plane's correlated-band switch indictments — members of an
        indicted switch that were never individually isolated still
        concentrate exclusion intervals on the rack (reason ``"switch"``)."""
        reasons = dict(self.isolated)
        if self.control is not None:
            for node, why in self.control.switch_reasons(t0, t1).items():
                reasons.setdefault(node, why)
        return reasons

    def fail_session(self, t: float, kind: str, xid=None):
        self.account_degradation(t)
        self.last_fail_hardware = kind == "unreachable" or (
            xid is not None and XID_TABLE[xid].hardware)
        att = self.chain.attempts[-1]
        att.end_h = t
        att.failure_kind = kind
        att.xid = xid
        self.current.transition(SessionState.ERROR, t, error=f"{kind}:{xid}")
        self.sched.release(self.current, t)
        self.exclusions.record_session(self.current.created_h, t,
                                       self.current.nodes,
                                       self.exclusion_reasons(
                                           self.current.created_h, t))
        self.current = None
        if self.down_since is None:
            self.down_since = t

    def schedule_next(self, t: float, xid=None, structural: bool = False):
        """Decide auto-retry vs operator handoff after a failure."""
        cfg, rng = self.cfg, self.rng
        n_attempt = len(self.chain.attempts)
        delay_min = self.retry_engine.next_delay_min(n_attempt, xid=xid)
        # operators notice a repeatedly-failing chain via alerting and kill
        # it before max_retries (except off-hours: the paper's 30-attempt
        # chain ran overnight)
        noticed = n_attempt >= 3 and rng.random() < (
            (cfg.retry.delay_min / 60.0)
            / max(cfg.operator_notice_mean_h, 1e-6) * 0.5)
        if structural and cfg.retry.structural_stop:
            noticed = True                   # gang unmet: retrying is futile
        if cfg.retry.enabled and delay_min is not None \
                and n_attempt < cfg.retry.max_retries and not noticed:
            self.pending_start = t + delay_min / 60.0
            self.start_is_manual = False
        else:
            # chain abandoned -> operator intervention
            if n_attempt >= cfg.retry.max_retries:
                self.chain.stopped_reason = "max retries"
            self.version += 1
            self.chain = Chain(task_name=f"b200_v{self.version}")
            self.chains.append(self.chain)
            self.pending_start = t + self.manual_delay(t)
            self.start_is_manual = True
            self.down_is_auto = False
            # the operator fixes the root cause... usually
            if rng.random() < cfg.p_manual_misfix:
                self.structural_until = max(
                    self.structural_until,
                    self.pending_start + (cfg.structural_fix_mean_h / 2)
                    * self.rng_struct.standard_exponential())
            else:
                self.structural_until = min(self.structural_until,
                                            self.pending_start)

    def manual_delay(self, t_h: float) -> float:
        """Operator response latency: fast in working hours, slow at night
        and on weekends (paper Fig 17's 0-53 h manual tail)."""
        cfg = self.cfg
        hour_of_day = (t_h % 24.0)
        day = int(t_h // 24.0) % 7
        if day >= 5 or hour_of_day < 8 or hour_of_day > 20:
            return float(cfg.manual_response_h_night
                         * self.rng_manual.standard_exponential())
        return float(cfg.manual_response_h_day
                     * self.rng_manual.standard_exponential())

    # -- shared per-time-step handlers --------------------------------------

    def process_repairs(self, t: float):
        for node, until in list(self.repair_until.items()):
            if t >= until:
                self.sched.readmit(node, t)
                del self.repair_until[node]
                self.isolated.pop(node, None)

    def process_pending_start(self, t: float):
        if self.current is None and self.pending_start is not None \
                and t >= self.pending_start:
            if self.start_attempt(t):
                self.pending_start = None
            else:
                self.schedule_next(t, structural=True)

    def process_prepare_done(self, t: float):
        if self.current is not None \
                and self.current.state is SessionState.PREPARING \
                and t >= self.prepare_until:
            if self.prepare_fails:          # structural failure at NCCL init
                self.fail_session(t, "software")
                self.schedule_next(t)
            else:
                self.current.transition(SessionState.RUNNING, t)
                self.chain.attempts[-1].reached_training = True
                self.last_ckpt = t
                self.last_save = t
                if self.down_since is not None:
                    self.downtimes.append({"t": t,
                                           "hours": t - self.down_since,
                                           "auto": self.down_is_auto,
                                           "kind": self.down_kind})
                    self.down_since = None
                    self.down_is_auto = True
                    self.down_kind = "failure"

    def account_checkpoints(self, t: float):
        """Catch up checkpoint bookkeeping for a RUNNING span ending at
        ``t`` (analytic replacement for the per-tick interval check)."""
        cfg = self.cfg
        if self.current is None \
                or self.current.state is not SessionState.RUNNING:
            return
        k = int(np.floor((t - self.last_ckpt + 1e-12)
                         / cfg.checkpoint_interval_h))
        if k > 0:
            self.ckpt_events += k
            self.current.checkpoint_step += k
            self.last_ckpt += k * cfg.checkpoint_interval_h
            self.last_save = max(self.last_save, self.last_ckpt)

    def process_failure(self, t: float, ev: FailureEvent):
        cfg, rng = self.cfg, self.rng
        if ev.kind in INFRA_KINDS:
            # degrade-don't-kill: the event opens a window that acts via
            # telemetry overlays, the degradation ledger and (for
            # escalating pressure) a separate crash timer — no immediate
            # state change and, critically, no RNG draws here
            return
        if ev.kind == "fail_slow":
            self.isolated[ev.node] = "performance degradation"
            self.sched.exclude(ev.node, t, "fail-slow (deliberate isolation)")
            self.repair_until[ev.node] = t + cfg.slow_isolation_h
            return
        # a failure landing on a predictively-drained node cannot take the
        # gang down — that is the drain paying off
        if self.control is not None \
                and self.isolated.get(ev.node) == "predictive drain":
            self.control.stats.failures_on_drained_node += 1
        if ev.is_hardware:
            self.sched.mark_down(ev.node, t, f"xid={ev.xid}"
                                 if ev.xid else "unreachable")
            self.repair_until[ev.node] = t + cfg.repair_time_h
            # a node already isolated (fail-slow, predictive drain) keeps
            # the reason that took it out of the pool — that is the
            # exclusion mechanism F3 attributes the interval to
            self.isolated.setdefault(ev.node, "hardware failure")
        if self.current is not None and not self.current.is_terminal \
                and ev.node in self.current.nodes:
            if self.current.state is SessionState.RUNNING:
                lost = min(t - self.last_save, cfg.checkpoint_interval_h)
                self.lost_hours.append(lost)
                if self.control is not None:
                    baseline = min(t - self.last_ckpt,
                                   cfg.checkpoint_interval_h)
                    self.control.stats.lost_work_avoided_h += \
                        max(baseline - lost, 0.0)
            # software-level follow-on? (NCCL wedged after the event)
            if rng.random() < cfg.p_software_failure:
                self.structural_until = max(
                    self.structural_until,
                    t + cfg.structural_fix_mean_h
                    * self.rng_struct.standard_exponential())
            self.fail_session(t, ev.kind, xid=ev.xid)
            self.schedule_next(t, xid=ev.xid)

    def process_escalation(self, t: float, node: int):
        """An escalating resource-exhaustion window ends in a process-level
        crash: the node's runtime dies (no hardware isolation — the host
        recovers once the pressure source is gone) and takes the gang down
        if the node is in the current job."""
        cfg, rng = self.cfg, self.rng
        if self.control is not None \
                and self.isolated.get(node) == "predictive drain":
            self.control.stats.failures_on_drained_node += 1
        if self.current is not None and not self.current.is_terminal \
                and node in self.current.nodes:
            if self.current.state is SessionState.RUNNING:
                lost = min(t - self.last_save, cfg.checkpoint_interval_h)
                self.lost_hours.append(lost)
                if self.control is not None:
                    baseline = min(t - self.last_ckpt,
                                   cfg.checkpoint_interval_h)
                    self.control.stats.lost_work_avoided_h += \
                        max(baseline - lost, 0.0)
            if rng.random() < cfg.p_software_failure:
                self.structural_until = max(
                    self.structural_until,
                    t + cfg.structural_fix_mean_h
                    * self.rng_struct.standard_exponential())
            self.fail_session(t, "resource_exhaust")
            self.schedule_next(t)

    def drain_session(self, t: float, node: int, *, redeploy_h: float,
                      recheck_h: float):
        """Predictive drain (control plane): gracefully stop the session
        behind its final checkpoint, isolate ``node`` pending a health
        recheck, and redeploy the gang from the remaining pool.  Not a
        failure: the chain closes with a drain reason and the next chain
        starts automatically after the controlled handoff."""
        self.account_degradation(t)
        s = self.current
        att = self.chain.attempts[-1]
        att.end_h = t
        att.failure_kind = "drain"
        s.transition(SessionState.TERMINATING, t)
        s.transition(SessionState.TERMINATED, t)
        self.sched.release(s, t)
        self.exclusions.record_session(s.created_h, t, s.nodes,
                                       self.exclusion_reasons(s.created_h, t))
        self.current = None
        self.isolated[node] = "predictive drain"
        self.sched.exclude(node, t, "predictive drain (control plane)")
        self.repair_until[node] = t + recheck_h
        self.chain.stopped_reason = "predictive drain"
        self.version += 1
        self.chain = Chain(task_name=f"b200_v{self.version}")
        self.chains.append(self.chain)
        self.pending_start = t + redeploy_h
        self.start_is_manual = False
        self.last_fail_hardware = False          # controlled: warm restart
        self.down_since = t
        self.down_kind = "drain"

    def finalize(self, failures, store) -> CampaignResult:
        cfg = self.cfg
        if self.current is not None and not self.current.is_terminal:
            self.account_degradation(cfg.duration_h)
            self.exclusions.record_session(self.current.created_h,
                                           cfg.duration_h,
                                           self.current.nodes,
                                           self.exclusion_reasons(
                                               self.current.created_h,
                                               cfg.duration_h))
            self.current.transition(SessionState.TERMINATING, cfg.duration_h)
            self.current.transition(SessionState.TERMINATED, cfg.duration_h)
        return CampaignResult(
            sessions=self.sessions, chains=self.chains, failures=failures,
            exclusions=self.exclusions, store=store,
            downtimes=self.downtimes, checkpoint_events=self.ckpt_events,
            lost_hours=self.lost_hours, duration_h=cfg.duration_h,
            checkpoint_save_s=cfg.checkpoint_save_s,
            control=self.control.stats if self.control is not None else None,
            degraded_hours=self.degraded)


class _TelemetryBatcher:
    """Emits scrape snapshots for constant-state spans between events.

    Keeps an integer cursor over the global 30 s scrape grid; ``emit``
    generates every tick in [span start, span end) with one batched
    exporter call per <=``max_chunk`` chunk.  Failure signatures are
    pinned to the first grid tick at/after the event time (matching the
    serial loop, which applied them on the tick that processed the event).

    When a control plane is attached (``consumer``) every chunk is handed
    to it right after generation; a drain-grade alarm halts emission at
    that chunk's boundary so the drain can run as a first-class event
    (``max_chunk`` is then the control plane's reaction interval).
    ``store`` may be None for stream-and-discard campaigns — online
    consumers don't need day-scale telemetry retained in memory.
    """

    def __init__(self, cfg: CampaignConfig, exporters: ExporterSuite,
                 store: Optional[TimeSeriesStore],
                 consumer: Optional[ControlPlane] = None,
                 max_chunk: int = _MAX_SPAN_TICKS):
        self.cfg = cfg
        self.exporters = exporters
        self.store = store
        self.consumer = consumer
        self.max_chunk = max_chunk
        self.n_ticks_total = int(np.ceil(cfg.duration_h / TICK_H - 1e-9))
        self.next_k = 0                       # next un-emitted grid tick
        self.pending_sigs: List[Tuple[int, FailureEvent]] = []

    def add_failure_signature(self, ev: FailureEvent):
        if ev.kind in INFRA_KINDS:
            return      # window signatures are registered at setup
        k = int(np.ceil(ev.time_h / TICK_H - 1e-9))
        if k < self.n_ticks_total:
            self.pending_sigs.append((k, ev))

    def emit(self, t_end: float, state: _CampaignState) -> Optional[float]:
        """Emit all grid ticks with time < ``t_end`` (campaign state is
        constant over the span except checkpoint-save flags).

        Returns the early-stop time when the attached control plane
        demands an action (the main loop truncates the span there), else
        None."""
        cfg = self.cfg
        k_end = min(int(np.ceil(t_end / TICK_H - 1e-9)), self.n_ticks_total)
        if k_end <= self.next_k:
            return None
        n = cfg.n_nodes
        down_row = np.array([not nd.healthy for nd in state.sched.nodes],
                            dtype=float)
        training_row = np.zeros(n)
        loading_row = np.zeros(n)
        running = False
        cur = state.current
        if cur is not None:
            if cur.state is SessionState.RUNNING:
                training_row[cur.nodes] = 1.0
                running = True
            elif cur.state is SessionState.PREPARING:
                loading_row[cur.nodes] = 1.0

        while self.next_k < k_end:
            k0 = self.next_k
            k1 = min(k0 + self.max_chunk, k_end)
            ts = np.arange(k0, k1) * TICK_H
            T = len(ts)
            if running:
                # time since the most recent checkpoint at each tick
                phase = np.mod(ts - state.last_ckpt,
                               cfg.checkpoint_interval_h)
                ckpt_mask = (phase < cfg.checkpoint_save_s / 3600.0)
                ckpt = ckpt_mask[:, None] * training_row[None, :]
            else:
                ckpt = None
            batch = NodeStateBatch.constant(
                T, n, training=training_row, loading=loading_row,
                checkpointing=ckpt, down=down_row)
            rows = [(k - k0, ev) for k, ev in self.pending_sigs
                    if k0 <= k < k1]
            self.pending_sigs = [(k, ev) for k, ev in self.pending_sigs
                                 if k >= k1]
            snap = self.exporters.tick_batch(ts, batch, rows)
            if self.store is not None:
                self.store.append_batch(ts, snap)
            self.next_k = k1
            if self.consumer is not None \
                    and self.consumer.on_chunk(ts, snap, state):
                return float(k1) * TICK_H
        return None


class ClusterSim:
    def __init__(self, config: Optional[CampaignConfig] = None):
        # per-instance default (a shared default-argument instance would
        # alias every sim's config)
        config = config if config is not None else CampaignConfig()
        self.fabric: Optional[StorageFabric] = None
        if config.storage is not None:
            config = self._resolve_storage(config)
        self.cfg = config
        self.rng = np.random.default_rng(config.seed)

    def _resolve_storage(self, cfg: CampaignConfig) -> CampaignConfig:
        """Replace the checkpoint-timing constants with fabric queries at
        the gang fanin — the layer where the paper's scale-emergent F2
        bottleneck enters the campaign simulation."""
        import dataclasses
        self.fabric = StorageFabric(cfg.storage)
        wire = int(cfg.ckpt_bytes_per_node * cfg.ckpt_wire_ratio)
        save_s = self.fabric.expected_duration_s(
            "write", cfg.job_nodes, wire,
            slots_per_client=cfg.storage_slots)
        read_h = self.fabric.expected_duration_s(
            "read", cfg.job_nodes, cfg.restore_bytes_per_node,
            slots_per_client=2 * cfg.storage_slots) / 3600.0
        return dataclasses.replace(
            cfg,
            checkpoint_save_s=save_s,
            loading_time_h=cfg.loading_overhead_h + read_h,
            loading_cold_h=cfg.loading_overhead_cold_h + read_h)

    def _make_injector(self) -> FailureInjector:
        cfg = self.cfg
        return FailureInjector(n_nodes=cfg.n_nodes, mtbf_h=cfg.mtbf_h,
                               hot_fraction=cfg.hot_fraction,
                               hot_weight=cfg.hot_weight,
                               kind_weights=cfg.kind_weights,
                               topology_fanout=cfg.topology_fanout,
                               seed=cfg.seed)

    def _make_telemetry(self, failures):
        cfg = self.cfg
        # a control plane implies telemetry: the streaming detector is fed
        # by the emitted spans even when nothing is retained
        if not cfg.telemetry and cfg.control is None:
            return None, None
        n_pad = N_PAD_METRICS if cfg.telemetry_pad_metrics is None \
            else cfg.telemetry_pad_metrics
        # non-fabric campaigns still export storage signals, from a
        # paper-default fabric at THIS campaign's gang fanin
        fabric = self.fabric if self.fabric is not None else StorageFabric()
        exporters = ExporterSuite(
            cfg.n_nodes, seed=cfg.seed, n_pad=n_pad,
            storage_levels=fabric.telemetry_levels(cfg.job_nodes))
        # retention needs BOTH flags: a control-only campaign (telemetry
        # False) streams spans to the detector and discards them — holding
        # a 73-day full-registry store would be tens of GB nobody asked for
        store = TimeSeriesStore(cfg.n_nodes) \
            if cfg.telemetry and cfg.telemetry_store else None
        for ev in failures:
            if ev.precursor_lead_h > 0:
                exporters.begin_gradual_precursor(
                    ev.node, ev.time_h - ev.precursor_lead_h,
                    until_h=ev.time_h + 0.05)
            if ev.kind in DEGRADE_KINDS and ev.window_h > 0:
                exporters.begin_degradation(
                    ev.node, ev.time_h, ev.time_h + ev.window_h,
                    ev.slow_factor, ev.kind, ev.onset)
            elif ev.kind == "ctrl_blind" and ev.window_h > 0:
                exporters.begin_outage(ev.time_h, ev.time_h + ev.window_h)
            elif ev.kind in CORRELATED_KINDS and ev.window_h > 0:
                # correlated band: one fabric event co-degrades the whole
                # blast radius (switch members, or the flapping peer's gang)
                exporters.begin_link_degradation(
                    sorted(set(ev.members) | set(ev.peers)),
                    ev.time_h, ev.time_h + ev.window_h, ev.slow_factor)
        return exporters, store

    def run(self) -> CampaignResult:
        if self.cfg.engine == "tick":
            return self._run_tick()
        if self.cfg.engine == "event":
            return self._run_event()
        raise ValueError(f"unknown engine {self.cfg.engine!r}")

    # ------------------------------------------------------------------
    # event-driven engine (default)
    # ------------------------------------------------------------------

    def _run_event(self) -> CampaignResult:
        cfg = self.cfg
        st = _CampaignState(cfg, self.rng)
        failures = self._make_injector().sample(cfg.duration_h)
        fail_idx = 0
        # infra fault band timelines (all derived deterministically from
        # the schedule — shared helpers keep both engines bit-identical)
        st.deg_windows = degradation_windows(failures)
        escs = escalation_events(failures)
        esc_idx = 0
        blind_ends = [b1 for _, b1 in blind_windows(failures)]
        blind_idx = 0
        exporters, store = self._make_telemetry(failures)
        ctl = None
        if cfg.control is not None:
            # urgent saves are priced like regular ones: fabric-resolved at
            # the gang fanin when CampaignConfig.storage is set
            ctl = ControlPlane(cfg.control,
                               urgent_save_s=cfg.checkpoint_save_s,
                               n_nodes=cfg.n_nodes, seed=cfg.seed)
            ctl.infra_active = any(f.kind in INFRA_KINDS for f in failures)
            for b0, b1 in blind_windows(failures):
                ctl.begin_blind(b0, b1)
            ctl.register_failures(failures)
            st.control = ctl
        # only drains need a bounded alarm->action latency (they truncate
        # spans); urgent checkpoints apply retroactively at the alarm's own
        # timestamp, so drain-less control runs keep full-size spans
        max_chunk = min(_MAX_SPAN_TICKS, cfg.control.reaction_ticks) \
            if ctl is not None and cfg.control.drain else _MAX_SPAN_TICKS
        tel = _TelemetryBatcher(cfg, exporters, store, consumer=ctl,
                                max_chunk=max_chunk) if exporters else None

        t = 0.0
        while True:
            # ---- process everything due at t (same order as the serial
            # loop: repairs, control actions, pending start, session
            # progress, failures) ----
            st.process_repairs(t)
            if ctl is not None:
                ctl.process(t, st)
            st.process_pending_start(t)
            st.process_prepare_done(t)
            while fail_idx < len(failures) \
                    and failures[fail_idx].time_h <= t + 1e-12:
                ev = failures[fail_idx]
                fail_idx += 1
                if tel is not None:
                    tel.add_failure_signature(ev)
                st.process_failure(t, ev)
            while esc_idx < len(escs) and escs[esc_idx][0] <= t + 1e-12:
                _, node = escs[esc_idx]
                esc_idx += 1
                st.process_escalation(t, node)

            # ---- next event time ----
            cands = [cfg.duration_h]
            if st.repair_until:
                cands.append(min(st.repair_until.values()))
            if st.current is None and st.pending_start is not None:
                cands.append(st.pending_start)
            if st.current is not None \
                    and st.current.state is SessionState.PREPARING:
                cands.append(st.prepare_until)
            if fail_idx < len(failures):
                cands.append(failures[fail_idx].time_h)
            if esc_idx < len(escs):
                cands.append(escs[esc_idx][0])
            if ctl is not None:
                # wake at blind-window ends so queued decisions replay
                while blind_idx < len(blind_ends) \
                        and blind_ends[blind_idx] <= t + 1e-12:
                    blind_idx += 1
                if blind_idx < len(blind_ends):
                    cands.append(blind_ends[blind_idx])
            t_next = min(c for c in cands if c > t + 1e-12) \
                if any(c > t + 1e-12 for c in cands) else cfg.duration_h
            t_next = min(t_next, cfg.duration_h)

            # ---- emit the constant-state telemetry span, then catch up
            # checkpoint bookkeeping to the span end; the control plane
            # may truncate the span when a drain-grade alarm fires ----
            if tel is not None:
                t_stop = tel.emit(t_next, st)
                if t_stop is not None and t_stop < t_next:
                    t_next = t_stop
            st.account_checkpoints(t_next)
            if t_next >= cfg.duration_h:
                break
            t = t_next

        return st.finalize(failures, store)

    # ------------------------------------------------------------------
    # serial 30 s-tick engine (legacy reference)
    # ------------------------------------------------------------------

    def _run_tick(self) -> CampaignResult:
        cfg = self.cfg
        if cfg.control is not None:
            raise ValueError(
                "the control plane consumes span-batched telemetry and is "
                "only supported by the event engine (engine='event')")
        st = _CampaignState(cfg, self.rng)
        failures = self._make_injector().sample(cfg.duration_h)
        fail_iter = iter(failures)
        next_fail = next(fail_iter, None)
        st.deg_windows = degradation_windows(failures)
        esc_iter = iter(escalation_events(failures))
        next_esc = next(esc_iter, None)
        exporters, store = self._make_telemetry(failures)

        t = 0.0
        while t < cfg.duration_h:
            st.process_repairs(t)
            st.process_pending_start(t)
            st.process_prepare_done(t)
            if st.current is not None \
                    and st.current.state is SessionState.RUNNING \
                    and t - st.last_ckpt >= cfg.checkpoint_interval_h:
                st.ckpt_events += 1
                st.last_ckpt = t
                st.last_save = t
                st.current.checkpoint_step += 1

            fired: List[FailureEvent] = []
            while next_fail is not None and next_fail.time_h <= t:
                fired.append(next_fail)
                next_fail = next(fail_iter, None)
            for ev in fired:
                st.process_failure(t, ev)
            while next_esc is not None and next_esc[0] <= t:
                st.process_escalation(t, next_esc[1])
                next_esc = next(esc_iter, None)

            if exporters is not None and store is not None:
                cur = st.current
                states = []
                for i in range(cfg.n_nodes):
                    in_job = cur is not None and i in cur.nodes \
                        and cur.state is SessionState.RUNNING
                    loading = cur is not None and i in cur.nodes \
                        and cur.state is SessionState.PREPARING
                    states.append(NodeState(
                        training=in_job,
                        checkpointing=in_job and
                        (t - st.last_ckpt) < cfg.checkpoint_save_s / 3600.0,
                        loading=loading,
                        down=not st.sched.nodes[i].healthy,
                    ))
                snap = exporters.tick(t, states, fired)
                store.append(t, snap)

            t += TICK_H

        return st.finalize(failures, store)
