"""Seed-batched Monte Carlo campaign engine.

`BatchedCampaignEngine` simulates S seeds of one campaign configuration in
a single struct-of-arrays pass: per-seed clocks and session state live in
``(S,)`` numpy arrays, node pool / exclusion / repair state in ``(S,
n_nodes)`` arrays, and every wavefront iteration advances **all** seeds to
their own next event at once — the per-iteration bookkeeping (candidate
event times, checkpoint catch-up, repair scans) is one set of numpy calls
for the whole seed batch instead of S python loops.  Failure timelines are
pre-sampled per seed by the batched `FailureInjector.sample_batch`;
telemetry spans are pushed through `StreamingDetector.push_group` (the
leading-seed-axis form) and `ControlPlane` policy decisions are applied
per seed against lightweight array-backed views.

The parity contract
-------------------
``BatchedCampaignEngine(cfg).run(seeds)[i]`` reproduces
``ClusterSim(replace(cfg, seed=seeds[i])).run()`` **field-for-field**
(sessions, chains, failures, exclusion intervals, downtimes, lost-work
hours, checkpoint counts, and the control plane's counterfactual ledger;
``session_id`` is a process-global counter and is the one exempt field).
This holds because each seed consumes its own ``default_rng(seed)`` stream
with the exact draw sequence of the scalar event engine — the vectorized
wavefront only batches the *deterministic* bookkeeping, never the sampled
decisions — and because the stacked telemetry/detector math is row-wise
independent (see `StreamingDetector.push_group`).  Divergent retry chains,
predictive drains and span truncation stay exact: seeds advance in
lockstep over the shared event horizon, but each one's clocks move by its
own per-seed mask.

Why it exists: CI over hundreds of seeds.  The per-seed `SweepRunner`
path pays a full python event loop per campaign (one process-pool task
each); the batched engine runs 256 73-day seeds in roughly the wall-clock
of a handful of scalar campaigns (the ``--only mc_batch`` benchmark gates
>=10x over the pool path), which is what makes median/IQR/95%-CI columns
for the paper's F1-F4 findings routine instead of a batch job.
"""
from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro import tracing
from repro.core.cluster import (RNG_STREAM_MANUAL, RNG_STREAM_STRUCT,
                                TICK_H, _MAX_SPAN_TICKS, CampaignConfig,
                                CampaignResult, ClusterSim)
from repro.core.exclusion import ExclusionInterval, ExclusionTracker
from repro.core.findings import LaneTotals, findings, split_downtimes
from repro.core.failures import (CORRELATED_KINDS, DEGRADE_KINDS,
                                 KIND_NAMES, FailureBatch, FailureInjector,
                                 blind_windows, degradation_windows,
                                 degraded_overlap_h, escalation_events)
from repro.core.retry import Attempt, Chain, RetryEngine, RetryPolicy
from repro.core.session import Session, SessionState
from repro.core.xid import XID_TABLE
from repro.control.policy import ControlPlane
from repro.control.streaming import StreamingDetector
from repro.storage.fabric import StorageFabric
from repro.telemetry.exporters import (ExporterSuite, N_PAD_METRICS,
                                       NodeStateBatch)
from repro.telemetry.registry import TimeSeriesStore

__all__ = ["BatchedCampaignEngine", "run_findings_stacked"]

# hot-loop lookup: XID -> is-hardware (mirrors FailureEvent.is_hardware)
_XID_HW = {x: meta.hardware for x, meta in XID_TABLE.items()}
_NAN = float("nan")

# the process's thread pool for concurrent telemetry chunks, made on
# first use (`_telemetry_pool`) and kept across passes
_pool: Optional[ThreadPoolExecutor] = None
_pool_lock = threading.Lock()


def _telemetry_pool() -> ThreadPoolExecutor:
    """``os.cpu_count()`` threads, started as rounds need them: a round
    of k chunks runs on min(k, cpu_count) of them."""
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(max_workers=os.cpu_count() or 1,
                                       thread_name_prefix="repro-telemetry")
        return _pool


def _tick_chunks(jobs: Sequence[tuple]) -> list:
    """``exporter.tick_batch(ts, batch, rows)`` for each job of one
    round, in job order.  A lone chunk runs inline; several run on the
    pool at once (numpy's generators and ufunc loops release the GIL).
    Each job owns its exporter, and with it its rng stream and remap
    counters, so every value is bit-identical to the serial calls.  All
    jobs finish before a worker's exception is re-raised here."""
    if len(jobs) == 1:
        exp, ts, batch, rows = jobs[0]
        return [exp.tick_batch(ts, batch, rows)]
    pool = _telemetry_pool()
    futures = [pool.submit(exp.tick_batch, ts, batch, rows)
               for exp, ts, batch, rows in jobs]
    wait(futures)
    return [f.result() for f in futures]


# ---------------------------------------------------------------------------
# array-backed views: what ControlPlane sees for one seed of the batch
# ---------------------------------------------------------------------------

class _NodeView:
    """One node of one seed, duck-typing `repro.core.scheduler.Node`."""
    __slots__ = ("B", "s", "i")

    def __init__(self, B, s, i):
        self.B, self.s, self.i = B, s, i

    @property
    def healthy(self):
        return bool(self.B.healthy[self.s, self.i])

    @property
    def free(self):
        B, s, i = self.B, self.s, self.i
        return bool(B.healthy[s, i] and not B.excl[s, i]
                    and not (B.cur_on[s] and B.in_gang[s, i]))


class _SchedNodes:
    """Per-seed ``sched.nodes`` list view over the (S, n) pool arrays."""
    __slots__ = ("B", "s")

    def __init__(self, B, s):
        self.B, self.s = B, s

    def __getitem__(self, i):
        return _NodeView(self.B, self.s, i)

    def __iter__(self):
        for i in range(self.B.n):
            yield _NodeView(self.B, self.s, i)


class _SchedView:
    __slots__ = ("nodes",)

    def __init__(self, B, s):
        self.nodes = _SchedNodes(B, s)


class _CurView:
    """Current-session stand-in (state + node membership is all the
    control plane reads)."""
    __slots__ = ("state", "nodes")

    def __init__(self, state, nodes):
        self.state, self.nodes = state, nodes


class _SeedView:
    """The `_CampaignState` surface `ControlPlane` interacts with, backed
    by seed ``s``'s slice of the batch arrays."""
    __slots__ = ("eng", "B", "s", "sched")

    def __init__(self, eng, B, s):
        self.eng, self.B, self.s = eng, B, s
        self.sched = _SchedView(B, s)

    @property
    def current(self):
        B, s = self.B, self.s
        if not B.cur_on[s]:
            return None
        state = SessionState.RUNNING if B.cur_run[s] \
            else SessionState.PREPARING
        return _CurView(state, B.cur_nodes_idx[s])

    @property
    def last_save(self):
        return self.B.last_save[self.s]

    @last_save.setter
    def last_save(self, v):
        self.B.last_save[self.s] = v

    def drain_session(self, t, node, *, redeploy_h, recheck_h):
        self.eng._drain_session(self.B, self.s, t, node,
                                redeploy_h=redeploy_h, recheck_h=recheck_h)


# ---------------------------------------------------------------------------
# per-batch mutable state (struct-of-arrays + per-seed logs)
# ---------------------------------------------------------------------------

class _Batch:
    """All mutable state for one ``run``: (S,) / (S, n) arrays for the hot
    clocks and pool masks, plain per-seed python structures for the
    variable-length logs (chains, session records, downtimes) that the
    scalar engine also keeps as objects."""

    def __init__(self, cfg: CampaignConfig, seeds: Sequence[int],
                 fails: FailureBatch, materialize: bool):
        S, n = len(seeds), cfg.n_nodes
        self.cfg = cfg
        self.seeds = list(seeds)
        self.S, self.n = S, n
        self.fails = fails
        self.mat = materialize
        self.has_control = cfg.control is not None
        inf = np.inf

        # (S,) clocks that the vectorized wavefront steps consume
        self.t = np.zeros(S)
        self.alive = np.ones(S, dtype=bool)
        self.pend = np.zeros(S)                    # pending_start; NaN=None
        self.prep_until = np.zeros(S)
        self.last_ckpt = np.zeros(S)
        self.last_save = np.zeros(S)
        self.cur_on = np.zeros(S, dtype=bool)
        self.cur_run = np.zeros(S, dtype=bool)     # RUNNING vs PREPARING
        self.ckpt_events = np.zeros(S, dtype=np.int64)
        self.cur_steps = np.zeros(S, dtype=np.int64)
        # handler-only per-seed scalars: plain python lists (no vector
        # step reads them, and list access is several times cheaper than
        # numpy scalar indexing in the per-event handlers)
        self.prep_fails = [False] * S
        self.struct_until = [-1.0] * S
        self.down_since = [float("nan")] * S
        self.down_auto = [True] * S
        self.last_hw = [False] * S
        self.version = [0] * S
        self.fail_ptr = fails.offsets[:-1].astype(np.int64).copy()
        self.next_fail = np.full(S, inf)       # first failure time per seed
        has = fails.offsets[1:] > fails.offsets[:-1]
        if has.any():
            self.next_fail[has] = fails.times[fails.offsets[:-1][has]]

        # (S, n) pool state.  There is no separate "allocated" plane: the
        # single campaign job means allocated == (session live & in gang).
        self.healthy = np.ones((S, n), dtype=bool)
        self.excl = np.zeros((S, n), dtype=bool)
        self.in_gang = np.zeros((S, n), dtype=bool)
        self.repair = np.full((S, n), inf)
        self.rep_min = np.full(S, inf)    # row min, kept in sync by writers

        # per-seed python structures; the main stream consumes only
        # ``random()`` uniforms — exponentials live on dedicated
        # [seed, salt] streams exactly as in _CampaignState
        self.rngs = [np.random.default_rng(s) for s in seeds]
        self.rngs_manual = [
            np.random.default_rng([s, RNG_STREAM_MANUAL]) for s in seeds]
        self.rngs_struct = [
            np.random.default_rng([s, RNG_STREAM_STRUCT]) for s in seeds]
        self.isolated: List[Dict[int, str]] = [{} for _ in range(S)]
        self.cur_nodes_idx: List[Optional[List[int]]] = [None] * S
        self.npart_idx: List[Optional[List[int]]] = [None] * S
        self.downtimes: List[List[dict]] = [[] for _ in range(S)]
        self.lost: List[List[float]] = [[] for _ in range(S)]
        self.down_kind: List[str] = ["failure"] * S

        # findings accumulators — scalar mirrors of chain_stats /
        # ExclusionTracker.summary / Session.elapsed_running_h, updated in
        # event order so every float fold matches the scalar path
        self.n_att = [0] * S                   # attempts in the open chain
        self.first_reached = [False] * S
        self.retry_reached = [False] * S
        self.prev_end: List[Optional[float]] = [None] * S
        self.f4 = [[0, 0, 0] for _ in range(S)]  # retry chains/attempts/succ
        self.gaps: List[List[float]] = [[] for _ in range(S)]
        self.cur_started = [float("nan")] * S
        self.cur_created = [0.0] * S
        self.run_sum = [0.0] * S
        self.n_sessions = [0] * S
        # handler-side views of the stacked failure schedule
        self.ftimes = fails.times.tolist()
        self.fnodes = fails.nodes.tolist()
        self.fkind = fails.kind.tolist()
        self.fxid = fails.xid.tolist()
        self.fhw = fails.hardware.tolist()
        self.npart_all: List[List[int]] = [[] for _ in range(S)]
        self.n_intervals = [0] * S
        self.n_delib = [0] * S
        self.reason_counts: List[Dict[str, int]] = [{} for _ in range(S)]

        # object materialization (parity mode only)
        self.chains: List[List[Chain]] = \
            [[Chain(task_name="b200_v0")] if materialize else []
             for _ in range(S)]
        self.cur_log: List[Optional[list]] = [None] * S
        self.session_log: List[List[list]] = [[] for _ in range(S)]
        self.record_log: List[list] = [[] for _ in range(S)]

        # infra fault band (PR 6): degradation windows, escalation timers
        # and blind-window wake-ups, derived deterministically from the
        # stacked schedule by the same helpers the scalar engine uses.
        # All structures stay empty (and the (S,) next-* clocks inf) for
        # schedules without infra kinds, so legacy batches skip every new
        # wavefront step.
        self.has_infra = bool((fails.kind >= 3).any())
        self.deg_windows: List[list] = [[] for _ in range(S)]
        self.degraded: List[List[float]] = [[] for _ in range(S)]
        self.esc_list: List[list] = [[] for _ in range(S)]
        self.esc_ptr = [0] * S
        self.next_esc = np.full(S, inf)
        self.blind_list: List[list] = [[] for _ in range(S)]
        self.blind_ptr = [0] * S
        self.next_blind = np.full(S, inf)
        if self.has_infra:
            for i in range(S):
                evs = fails.events(i)
                self.deg_windows[i] = degradation_windows(evs)
                es = escalation_events(evs)
                self.esc_list[i] = es
                if es:
                    self.next_esc[i] = es[0][0]
                if self.has_control:
                    # blind ends only wake the loop when a control plane
                    # exists to replay queued decisions (scalar candidate
                    # list adds them under the same condition)
                    be = [b1 for _, b1 in blind_windows(evs)]
                    self.blind_list[i] = be
                    if be:
                        self.next_blind[i] = be[0]

        # telemetry / control (populated by the engine when enabled)
        self.planes: List[Optional[ControlPlane]] = [None] * S
        self.views: List[Optional[_SeedView]] = [None] * S
        self.exporters: List[Optional[ExporterSuite]] = [None] * S
        self.stores: List[Optional[TimeSeriesStore]] = [None] * S
        self.next_k = np.zeros(S, dtype=np.int64)
        self.pending_sigs: List[list] = [[] for _ in range(S)]
        self.tel_seeds: List[int] = []
        self.max_chunk = _MAX_SPAN_TICKS
        self.n_ticks_total = int(np.ceil(cfg.duration_h / TICK_H - 1e-9))


class BatchedCampaignEngine:
    """S seeds of one `CampaignConfig`, one stacked pass.

    ``run(seeds)`` materializes full per-seed `CampaignResult` objects
    (the parity surface); ``run_findings(seeds)`` skips object
    materialization and returns the per-seed findings dicts the sweep
    runner aggregates — same numbers, a fraction of the allocation work.
    Only the (default) event engine semantics are supported.
    """

    def __init__(self, config: CampaignConfig,
                 wavefront_backend: str = "auto"):
        if config.engine != "event":
            raise ValueError(
                "BatchedCampaignEngine batches the event engine; "
                f"got engine={config.engine!r}")
        if wavefront_backend not in ("auto", "numpy", "xla", "pallas"):
            raise ValueError(
                f"unknown wavefront backend {wavefront_backend!r}; "
                "expected 'auto', 'numpy', 'xla' or 'pallas'")
        self.wavefront_backend = wavefront_backend
        base = ClusterSim(config)         # resolves the storage fabric
        self.cfg = base.cfg
        self.fabric = base.fabric
        self.retry_engine = RetryEngine(self.cfg.retry)
        c = self.cfg
        self._notice_p = (c.retry.delay_min / 60.0) \
            / max(c.operator_notice_mean_h, 1e-6) * 0.5
        self._fixed_delay = c.retry.delay_min + c.retry.teardown_min \
            if c.retry.policy is RetryPolicy.FIXED else None

    # -- public API ---------------------------------------------------------

    def run(self, seeds: Sequence[int]) -> List[CampaignResult]:
        B = self._simulate(seeds, materialize=True)
        return [self._materialize(B, i) for i in range(B.S)]

    def run_findings(self, seeds: Sequence[int]) -> List[dict]:
        # findings-only campaigns are the compiled wavefront's parity
        # surface: route eligible batches through the device core (the
        # object-materializing `run` path stays numpy by construction)
        if self.wavefront_backend != "numpy":
            from repro.kernels.wavefront import (resolve_wavefront_backend,
                                                 run_findings_compiled)
            backend = resolve_wavefront_backend(
                self.wavefront_backend, self.cfg, len(seeds))
            if backend != "numpy":
                return run_findings_compiled(self.cfg, seeds,
                                             backend=backend)
        B = self._simulate(seeds, materialize=False)
        with tracing.span("engine.findings"):
            return [self._findings(B, i) for i in range(B.S)]

    # -- setup --------------------------------------------------------------

    def _setup_telemetry(self, B: _Batch):
        cfg = self.cfg
        if not cfg.telemetry and cfg.control is None:
            return
        n_pad = N_PAD_METRICS if cfg.telemetry_pad_metrics is None \
            else cfg.telemetry_pad_metrics
        fabric = self.fabric if self.fabric is not None else StorageFabric()
        levels = fabric.telemetry_levels(cfg.job_nodes)
        retain = cfg.telemetry and cfg.telemetry_store
        if cfg.control is not None and cfg.control.drain:
            B.max_chunk = min(_MAX_SPAN_TICKS, cfg.control.reaction_ticks)
        for i, seed in enumerate(B.seeds):
            exp = ExporterSuite(cfg.n_nodes, seed=seed, n_pad=n_pad,
                                storage_levels=levels)
            evs = B.fails.events(i)
            for ev in evs:
                if ev.precursor_lead_h > 0:
                    exp.begin_gradual_precursor(
                        ev.node, ev.time_h - ev.precursor_lead_h,
                        until_h=ev.time_h + 0.05)
                if ev.kind in DEGRADE_KINDS and ev.window_h > 0:
                    exp.begin_degradation(
                        ev.node, ev.time_h, ev.time_h + ev.window_h,
                        ev.slow_factor, ev.kind, ev.onset)
                elif ev.kind == "ctrl_blind" and ev.window_h > 0:
                    exp.begin_outage(ev.time_h, ev.time_h + ev.window_h)
                elif ev.kind in CORRELATED_KINDS and ev.window_h > 0:
                    # correlated band: co-degrade the whole blast radius
                    # (mirrors the scalar `_make_telemetry` registration)
                    exp.begin_link_degradation(
                        sorted(set(ev.members) | set(ev.peers)),
                        ev.time_h, ev.time_h + ev.window_h, ev.slow_factor)
            B.exporters[i] = exp
            if retain:
                B.stores[i] = TimeSeriesStore(cfg.n_nodes)
            if cfg.control is not None:
                plane = ControlPlane(
                    cfg.control, urgent_save_s=cfg.checkpoint_save_s,
                    n_nodes=cfg.n_nodes, seed=seed)
                plane.infra_active = B.has_infra and bool(
                    (B.fails.kind[B.fails.offsets[i]:
                                  B.fails.offsets[i + 1]] >= 3).any())
                for b0, b1 in blind_windows(evs):
                    plane.begin_blind(b0, b1)
                plane.register_failures(evs)
                B.planes[i] = plane
                B.views[i] = _SeedView(self, B, i)
            B.tel_seeds.append(i)

    # -- per-seed transition handlers (exact scalar-RNG discipline) ---------

    def _process_starts(self, B: _Batch, idx: np.ndarray,
                        t: List[float]):
        """Attempt starts for every due seed of this wavefront iteration.

        The deterministic pool scan is one stacked pass — free masks,
        gang-feasibility counts and first-``job_nodes`` selection via a
        row cumsum for all D seeds at once; only the sampled decisions
        (pressure readmits, transient-retry rolls, load-duration draws)
        and the per-seed logs run in python, each on its own rng stream.
        Seeds with an alarm-informed ``avoid`` preference (control plane)
        fall back to the scalar ordering — the soft sort is per-seed by
        nature and rare.
        """
        cfg = self.cfg
        job = cfg.job_nodes
        free = B.healthy[idx] & ~B.excl[idx]      # due seeds have no session
        counts = free.sum(axis=1)
        ok = counts >= job
        chosen_mask = free & (np.cumsum(free, axis=1) <= job)
        ok_rows = ok.nonzero()[0]
        # per-seed node lists for all gang-feasible seeds, in two calls
        nodes_flat = chosen_mask[ok_rows].nonzero()[1].reshape(-1, job)
        npart_flat = (~chosen_mask[ok_rows]).nonzero()[1].reshape(
            -1, B.n - job)

        nodes_all = nodes_flat.tolist()
        npart_all = npart_flat.tolist()
        p_readmit = cfg.p_pressure_readmit
        p_transient = cfg.p_transient_retry_fail
        load_cold, load_warm = cfg.loading_cold_h, cfg.loading_time_h
        mat = B.mat
        # locals for everything the per-seed body touches (attribute
        # loads in a 100k-invocation loop are real wall-clock)
        struct_until, last_hw = B.struct_until, B.last_hw
        rngs, planes, isolated = B.rngs, B.planes, B.isolated
        n_att_l, prev_end, gaps = B.n_att, B.prev_end, B.gaps
        cur_created, cur_started = B.cur_created, B.cur_started
        n_sessions = B.n_sessions
        cur_nodes_idx, npart_idx = B.cur_nodes_idx, B.npart_idx
        prep_fails = B.prep_fails
        sched_next = self._schedule_next
        # bit-exact fast forms of the scalar draws:
        #   uniform(a, b) == a + (b-a) * random()   (same C arithmetic)
        w_load = 0.3 - (-0.08)
        w_fail = 0.15 - 0.05
        started_seeds: List[int] = []
        started_until: List[float] = []
        ok_l = ok.tolist()
        no_ctl = not B.has_control
        if no_ctl and len(ok_rows):
            # reactive batch: no avoid preference anywhere — land every
            # gang row in one stacked write instead of 60-bool row copies
            B.in_gang[idx[ok]] = chosen_mask[ok]
        ok_i = 0
        for pos, s in enumerate(idx.tolist()):
            ts_ = t[s]
            rng = rngs[s]
            if no_ctl:
                avoid = None
            else:
                plane = planes[s]
                avoid = plane.avoid_nodes(ts_) \
                    if plane is not None else None
            if not ok_l[pos]:
                iso = isolated[s]
                hrow = B.healthy[s]
                cand = [i for i in iso if hrow[i]]
                if cand and rng.random() < p_readmit:
                    i0 = cand[0]
                    B.excl[s, i0] = False
                    hrow[i0] = True
                    iso.pop(i0, None)
                    B.repair[s, i0] = np.inf
                    B.rep_min[s] = B.repair[s].min()
                n_att_l[s] += 1
                pe = prev_end[s]
                if pe is not None:
                    gaps[s].append((ts_ - pe) * 60.0)
                prev_end[s] = ts_                 # alloc_fail ends at start
                if mat:
                    B.chains[s][-1].attempts.append(
                        Attempt(start_h=ts_, end_h=ts_,
                                failure_kind="alloc_fail"))
                sched_next(B, s, ts_, structural=True)
                continue
            if avoid:
                free_idx = free[pos].nonzero()[0]
                order = RetryEngine.placement_order(free_idx.tolist(),
                                                    avoid)
                nodes = order[:job]
                row = B.in_gang[s]
                row[:] = False
                row[nodes] = True
                npart = (~row).nonzero()[0].tolist()
                ok_i += 1
            else:
                nodes = nodes_all[ok_i]
                if not no_ctl:
                    B.in_gang[s] = chosen_mask[pos]
                npart = npart_all[ok_i]
                ok_i += 1
            cur_nodes_idx[s] = nodes
            npart_idx[s] = npart
            cur_created[s] = ts_
            cur_started[s] = _NAN
            n_sessions[s] += 1
            n_att = n_att_l[s] + 1
            n_att_l[s] = n_att
            pe = prev_end[s]
            if pe is not None:
                gaps[s].append((ts_ - pe) * 60.0)
            prev_end[s] = None                    # open until it ends
            if mat:
                chain = B.chains[s][-1]
                chain.attempts.append(Attempt(start_h=ts_))
                # session record: [created, nodes, started, ended,
                #                  end_is_error, error, steps, task_name]
                log = [ts_, nodes, None, None, False, None, 0,
                       chain.task_name]
                B.cur_log[s] = log
                B.session_log[s].append(log)
            fails = ts_ < struct_until[s]
            if not fails and n_att in (2, 3) \
                    and rng.random() < p_transient:
                fails = True
            prep_fails[s] = fails
            if fails:
                dur = 0.05 + w_fail * rng.random()
            else:
                warm = load_cold if last_hw[s] else load_warm
                dur = warm + (-0.08 + w_load * rng.random())
            started_seeds.append(s)
            started_until.append(ts_ + dur)

        if started_seeds:
            arr = np.array(started_seeds)
            B.cur_on[arr] = True
            B.cur_run[arr] = False
            B.cur_steps[arr] = 0
            B.pend[arr] = np.nan
            B.prep_until[arr] = started_until

    def _record_session(self, B: _Batch, s: int, t0: float, t1: float):
        """Exclusion bookkeeping for a finished session (the tracker's
        ``record_session`` in accumulator form + a replay log).  Mirrors
        `_CampaignState.exclusion_reasons`: the isolation ledger first,
        then the control plane's switch indictments (same setdefault
        order, so the replayed tracker matches the scalar one)."""
        iso = B.isolated[s]
        plane = B.planes[s]
        if plane is not None:
            sw = plane.switch_reasons(t0, t1)
            if sw:
                merged = dict(iso)
                for node, why in sw.items():
                    merged.setdefault(node, why)
                iso = merged
        npart = B.npart_idx[s]
        B.npart_all[s].extend(npart)
        B.n_intervals[s] += len(npart)
        if iso:
            in_gang = B.in_gang[s]
            delib = 0
            rc = B.reason_counts[s]
            for node in iso:
                if not in_gang[node]:
                    delib += 1
                    reason = iso[node]
                    rc[reason] = rc.get(reason, 0) + 1
            B.n_delib[s] += delib
        if B.mat:
            B.record_log[s].append((t0, t1, B.cur_nodes_idx[s],
                                    tuple(iso.items()) if iso else ()))

    def _account_degradation(self, B: _Batch, s: int, t1: float):
        """Close the degradation ledger for seed ``s``'s RUNNING span
        ending at ``t1`` (mirrors `_CampaignState.account_degradation`:
        called wherever the span closes — failure, drain, campaign end)."""
        if not B.deg_windows[s]:
            return
        started = B.cur_started[s]
        if started != started:          # NaN: never reached RUNNING
            return
        d = degraded_overlap_h(B.deg_windows[s], started, t1,
                               B.cur_nodes_idx[s])
        if d:
            B.degraded[s].append(d)

    def _fail_session(self, B: _Batch, s: int, t: float, kind: str, xid):
        self._account_degradation(B, s, t)
        B.last_hw[s] = kind == "unreachable" or (
            xid is not None and _XID_HW[xid])
        B.prev_end[s] = t
        started = B.cur_started[s]
        if started == started:          # session reached RUNNING
            B.run_sum[s] += max(0.0, t - started)
        if B.mat:
            att = B.chains[s][-1].attempts[-1]
            att.end_h = t
            att.failure_kind = kind
            att.xid = xid
            log = B.cur_log[s]
            log[3] = t                  # ended
            log[4] = True               # ERROR
            log[5] = f"{kind}:{xid}"
            log[6] = int(B.cur_steps[s])
            B.cur_log[s] = None
        self._record_session(B, s, B.cur_created[s], t)
        B.cur_on[s] = False
        ds = B.down_since[s]
        if ds != ds:                    # NaN: no open downtime window yet
            B.down_since[s] = t

    def _close_chain(self, B: _Batch, s: int):
        """Fold the open chain into the per-seed F4 aggregates (the
        `chain_stats` retry-chain filter and classification, inline)."""
        n_att = B.n_att[s]
        if n_att > 1:
            f4 = B.f4[s]
            f4[0] += 1
            f4[1] += n_att
            if B.retry_reached[s]:
                f4[2] += 1
        B.n_att[s] = 0
        B.first_reached[s] = False
        B.retry_reached[s] = False
        B.prev_end[s] = None

    def _schedule_next(self, B: _Batch, s: int, t: float, xid=None,
                       structural: bool = False):
        cfg = self.cfg
        rng = B.rngs[s]
        n_attempt = B.n_att[s]
        retry_on = cfg.retry.enabled
        max_r = cfg.retry.max_retries
        if self._fixed_delay is not None:       # FIXED policy ignores xid
            delay_min = self._fixed_delay \
                if retry_on and n_attempt <= max_r else None
        else:
            delay_min = self.retry_engine.next_delay_min(n_attempt, xid=xid)
        noticed = n_attempt >= 3 and rng.random() < self._notice_p
        if structural and cfg.retry.structural_stop:
            noticed = True
        if retry_on and delay_min is not None \
                and n_attempt < max_r and not noticed:
            B.pend[s] = t + delay_min / 60.0
        else:
            if B.mat:
                chain = B.chains[s][-1]
                if n_attempt >= cfg.retry.max_retries:
                    chain.stopped_reason = "max retries"
                B.version[s] += 1
                B.chains[s].append(
                    Chain(task_name=f"b200_v{B.version[s]}"))
            self._close_chain(B, s)
            B.pend[s] = t + self._manual_delay(B.rngs_manual[s], t)
            B.down_auto[s] = False
            if rng.random() < cfg.p_manual_misfix:
                B.struct_until[s] = max(
                    B.struct_until[s],
                    B.pend[s] + (cfg.structural_fix_mean_h / 2)
                    * B.rngs_struct[s].standard_exponential())
            else:
                B.struct_until[s] = min(B.struct_until[s], B.pend[s])

    def _manual_delay(self, rng_manual, t_h: float) -> float:
        cfg = self.cfg
        hour_of_day = (t_h % 24.0)
        day = int(t_h // 24.0) % 7
        if day >= 5 or hour_of_day < 8 or hour_of_day > 20:
            return float(cfg.manual_response_h_night
                         * rng_manual.standard_exponential())
        return float(cfg.manual_response_h_day
                     * rng_manual.standard_exponential())

    def _process_prepare_done(self, B: _Batch, s: int, t: float):
        if B.prep_fails[s]:
            self._fail_session(B, s, t, "software", None)
            self._schedule_next(B, s, t)
            return
        B.cur_run[s] = True
        B.cur_started[s] = t
        if B.n_att[s] == 1:
            B.first_reached[s] = True
        else:
            B.retry_reached[s] = True
        if B.mat:
            B.cur_log[s][2] = t                 # started (RUNNING)
            B.chains[s][-1].attempts[-1].reached_training = True
        B.last_ckpt[s] = t
        B.last_save[s] = t
        ds = B.down_since[s]
        if ds == ds:                            # not NaN: close the window
            B.downtimes[s].append({"t": t,
                                   "hours": t - ds,
                                   "auto": bool(B.down_auto[s]),
                                   "kind": B.down_kind[s]})
            B.down_since[s] = np.nan
            B.down_auto[s] = True
            B.down_kind[s] = "failure"

    def _process_failure(self, B: _Batch, s: int, t: float, j: int):
        """Failure row ``j`` of the stacked schedule lands on seed ``s``."""
        cfg = self.cfg
        node = B.fnodes[j]
        kcode = B.fkind[j]
        if kcode >= 3:
            # infra band (net_degrade / resource_exhaust / ctrl_blind):
            # degrade-don't-kill — the event acts via telemetry overlays,
            # the degradation ledger and (escalating pressure) a separate
            # crash timer; no immediate state change, no RNG draws
            return
        if kcode == 2:                              # fail_slow
            B.isolated[s][node] = "performance degradation"
            B.excl[s, node] = True
            B.repair[s, node] = t + cfg.slow_isolation_h
            return
        plane = B.planes[s]
        if plane is not None \
                and B.isolated[s].get(node) == "predictive drain":
            plane.stats.failures_on_drained_node += 1
        if B.fhw[j]:
            B.healthy[s, node] = False
            B.repair[s, node] = t + cfg.repair_time_h
            B.isolated[s].setdefault(node, "hardware failure")
        if B.cur_on[s] and B.in_gang[s, node]:
            rng = B.rngs[s]
            if B.cur_run[s]:
                lost = min(t - float(B.last_save[s]),
                           cfg.checkpoint_interval_h)
                B.lost[s].append(lost)
                if plane is not None:
                    baseline = min(t - float(B.last_ckpt[s]),
                                   cfg.checkpoint_interval_h)
                    plane.stats.lost_work_avoided_h += \
                        max(baseline - lost, 0.0)
            if rng.random() < cfg.p_software_failure:
                B.struct_until[s] = max(
                    B.struct_until[s],
                    t + cfg.structural_fix_mean_h
                    * B.rngs_struct[s].standard_exponential())
            xid = B.fxid[j]
            xid = xid if xid >= 0 else None
            self._fail_session(B, s, t, KIND_NAMES[kcode], xid)
            self._schedule_next(B, s, t, xid=xid)

    def _process_escalation(self, B: _Batch, s: int, t: float, node: int):
        """Escalating resource-exhaustion crash for seed ``s`` (mirrors
        `_CampaignState.process_escalation` draw for draw)."""
        cfg = self.cfg
        plane = B.planes[s]
        if plane is not None \
                and B.isolated[s].get(node) == "predictive drain":
            plane.stats.failures_on_drained_node += 1
        if B.cur_on[s] and B.in_gang[s, node]:
            rng = B.rngs[s]
            if B.cur_run[s]:
                lost = min(t - float(B.last_save[s]),
                           cfg.checkpoint_interval_h)
                B.lost[s].append(lost)
                if plane is not None:
                    baseline = min(t - float(B.last_ckpt[s]),
                                   cfg.checkpoint_interval_h)
                    plane.stats.lost_work_avoided_h += \
                        max(baseline - lost, 0.0)
            if rng.random() < cfg.p_software_failure:
                B.struct_until[s] = max(
                    B.struct_until[s],
                    t + cfg.structural_fix_mean_h
                    * B.rngs_struct[s].standard_exponential())
            self._fail_session(B, s, t, "resource_exhaust", None)
            self._schedule_next(B, s, t)

    def _drain_session(self, B: _Batch, s: int, t: float, node: int, *,
                       redeploy_h: float, recheck_h: float):
        self._account_degradation(B, s, t)
        B.prev_end[s] = t
        started = B.cur_started[s]
        if started == started:
            B.run_sum[s] += max(0.0, t - started)
        if B.mat:
            chain = B.chains[s][-1]
            att = chain.attempts[-1]
            att.end_h = t
            att.failure_kind = "drain"
            log = B.cur_log[s]
            log[3] = t
            log[4] = False                      # TERMINATED (graceful)
            log[6] = int(B.cur_steps[s])
            B.cur_log[s] = None
            chain.stopped_reason = "predictive drain"
            B.version[s] += 1
            B.chains[s].append(Chain(task_name=f"b200_v{B.version[s]}"))
        self._record_session(B, s, B.cur_created[s], t)
        B.cur_on[s] = False
        self._close_chain(B, s)
        B.isolated[s][node] = "predictive drain"
        B.excl[s, node] = True
        B.repair[s, node] = t + recheck_h
        B.rep_min[s] = min(B.rep_min[s], t + recheck_h)
        B.pend[s] = t + redeploy_h
        B.last_hw[s] = False
        B.down_since[s] = t
        B.down_kind[s] = "drain"

    # -- telemetry emission (per-seed chunks, group-scanned detector) -------

    def _emit(self, B: _Batch, t_next: np.ndarray):
        """Emit every telemetry seed's constant-state span up to its own
        ``t_next``, mirroring `_TelemetryBatcher.emit` chunk for chunk.
        In each round the emitting seeds' chunks are generated at once
        (`_tick_chunks`: each exporter owns its rng stream), then
        scanned through the streaming detector in same-shape groups —
        one stacked pass per group.  A drain-grade alarm truncates that
        seed's span at the chunk boundary (returned in ``t_stop``)."""
        cfg = self.cfg
        k_end = np.minimum(
            np.ceil(t_next / TICK_H - 1e-9).astype(np.int64),
            B.n_ticks_total)
        emitting = [s for s in B.tel_seeds
                    if B.alive[s] and k_end[s] > B.next_k[s]]
        t_stop: Dict[int, float] = {}
        rows_cache: Dict[int, tuple] = {}
        for s in emitting:
            down_row = (~B.healthy[s]).astype(float)
            training = np.zeros(B.n)
            loading = np.zeros(B.n)
            running = False
            if B.cur_on[s]:
                if B.cur_run[s]:
                    training[B.cur_nodes_idx[s]] = 1.0
                    running = True
                else:
                    loading[B.cur_nodes_idx[s]] = 1.0
            rows_cache[s] = (training, loading, down_row, running)

        while emitting:
            chunk: Dict[int, tuple] = {}
            with tracing.span("engine.telemetry"):
                jobs = []
                for s in emitting:
                    k0 = int(B.next_k[s])
                    k1 = min(k0 + B.max_chunk, int(k_end[s]))
                    ts = np.arange(k0, k1) * TICK_H
                    training, loading, down_row, running = rows_cache[s]
                    if running:
                        phase = np.mod(ts - B.last_ckpt[s],
                                       cfg.checkpoint_interval_h)
                        ckpt_mask = (phase
                                     < cfg.checkpoint_save_s / 3600.0)
                        ckpt = ckpt_mask[:, None] * training[None, :]
                    else:
                        ckpt = None
                    batch = NodeStateBatch.constant(
                        len(ts), B.n, training=training, loading=loading,
                        checkpointing=ckpt, down=down_row)
                    sigs = B.pending_sigs[s]
                    rows = [(k - k0, ev) for k, ev in sigs
                            if k0 <= k < k1]
                    B.pending_sigs[s] = [(k, ev) for k, ev in sigs
                                         if k >= k1]
                    jobs.append((B.exporters[s], ts, batch, rows))
                snaps = _tick_chunks(jobs)
                for s, (_, ts, _, _), snap in zip(emitting, jobs, snaps):
                    if B.stores[s] is not None:
                        B.stores[s].append_batch(ts, snap)
                    B.next_k[s] += len(ts)
                    chunk[s] = (ts, snap)
                lengths = [len(job[1]) for job in jobs]
                tracing.count("engine.telemetry_ticks", sum(lengths))
                tracing.count("engine.telemetry_path_ticks", max(lengths))

            # group-scan control seeds by chunk length; apply per seed
            ctl = [s for s in emitting if B.planes[s] is not None]
            halted = set()
            by_T: Dict[int, List[int]] = {}
            for s in ctl:
                by_T.setdefault(len(chunk[s][0]), []).append(s)
            for group in by_T.values():
                alarm_lists = StreamingDetector.push_group(
                    [B.planes[s].detector for s in group],
                    [chunk[s][0] for s in group],
                    [chunk[s][1] for s in group])
                with tracing.span("control.apply"):
                    for s, alarms in zip(group, alarm_lists):
                        plane = B.planes[s]
                        if plane.log is not None:
                            # log channel: same per-chunk fusion point as
                            # the scalar `ControlPlane.on_chunk` — chunk
                            # windows are mirrored, so the emitter's draws
                            # line up
                            alarms = plane.fuse_alarms(
                                alarms, plane.scan_logs(chunk[s][0],
                                                        B.views[s]))
                        if plane.apply_alarms(alarms, B.views[s]):
                            t_stop[s] = float(B.next_k[s]) * TICK_H
                            halted.add(s)
            emitting = [s for s in emitting
                        if s not in halted and B.next_k[s] < k_end[s]]
        return t_stop

    # -- the wavefront loop -------------------------------------------------

    def _simulate(self, seeds: Sequence[int],
                  materialize: bool) -> _Batch:
        cfg = self.cfg
        with tracing.span("engine.draws"):
            injector = FailureInjector(
                n_nodes=cfg.n_nodes, mtbf_h=cfg.mtbf_h,
                hot_fraction=cfg.hot_fraction, hot_weight=cfg.hot_weight,
                kind_weights=cfg.kind_weights,
                topology_fanout=cfg.topology_fanout, seed=cfg.seed)
            fails = injector.sample_batch(cfg.duration_h, seeds)
            B = _Batch(cfg, seeds, fails, materialize)
            self._setup_telemetry(B)
        telemetry = bool(B.tel_seeds)
        duration = cfg.duration_h
        interval = cfg.checkpoint_interval_h
        ftimes, foffs = B.ftimes, fails.offsets
        cand = np.empty((7, B.S))
        cand[0] = duration
        cand[5] = np.inf        # escalation crashes (infra band)
        cand[6] = np.inf        # blind-window wake-ups (control only)
        rep_min = B.rep_min

        # NaN pending-times flow through the candidate comparisons by
        # design; silence the FPE flag once for the whole run
        err_state = np.seterr(invalid="ignore")
        try:
            with tracing.span("engine.events"):
                self._wavefront(B, cand, rep_min, ftimes, foffs, duration,
                                interval, telemetry)
        finally:
            np.seterr(**err_state)
        return B

    def _wavefront(self, B: _Batch, cand, rep_min, ftimes, foffs,
                   duration, interval, telemetry):
        fails = B.fails
        while B.alive.any():
            alive = B.alive
            t = B.t

            # 1. repairs due (t >= repair time)
            t_list = t.tolist()      # python floats for the event handlers

            due_rep = (alive & (rep_min <= t)).nonzero()[0]
            for s in due_rep.tolist():
                row = B.repair[s]
                iso = B.isolated[s]
                for i in (row <= t_list[s]).nonzero()[0]:
                    B.healthy[s, i] = True
                    B.excl[s, i] = False
                    row[i] = np.inf
                    iso.pop(int(i), None)
            if len(due_rep):
                rep_min[due_rep] = B.repair[due_rep].min(axis=1)

            # 2. control plane: execute pending drains at chunk boundaries
            # and replay decisions queued during blind windows (the scalar
            # loop calls ``ctl.process`` unconditionally; both paths are
            # no-ops without a pending drain or a due blind queue)
            if telemetry:
                for s in B.tel_seeds:
                    plane = B.planes[s]
                    if plane is not None and alive[s] \
                            and (plane.pending_drain is not None
                                 or plane.blind_ready(t_list[s])):
                        plane.process(t_list[s], B.views[s])

            # 3. pending attempt starts (stacked pool scan + per-seed rng)
            due_start = (alive & ~B.cur_on & (B.pend <= t)).nonzero()[0]
            if len(due_start):
                self._process_starts(B, due_start, t_list)

            # 4. PREPARING completions
            due_prep = alive & B.cur_on & ~B.cur_run & (t >= B.prep_until)
            for s in due_prep.nonzero()[0].tolist():
                self._process_prepare_done(B, s, t_list[s])

            # 5. failures due at t (possibly several per seed)
            due_fail = (alive & (B.next_fail <= t + 1e-12)).nonzero()[0]
            for s in due_fail.tolist():
                ptr, end = int(B.fail_ptr[s]), int(foffs[s + 1])
                ts_ = t_list[s]
                while ptr < end and ftimes[ptr] <= ts_ + 1e-12:
                    if telemetry and B.exporters[s] is not None:
                        k = int(np.ceil(ftimes[ptr] / TICK_H - 1e-9))
                        if k < B.n_ticks_total:
                            B.pending_sigs[s].append(
                                (k, B.fails.events(s)[ptr - int(foffs[s])]))
                    self._process_failure(B, s, ts_, ptr)
                    ptr += 1
                B.fail_ptr[s] = ptr
                B.next_fail[s] = ftimes[ptr] if ptr < end else np.inf
            if len(due_fail):        # failures schedule repairs/isolations
                rep_min[due_fail] = B.repair[due_fail].min(axis=1)

            # 5b. escalation crashes from resource-exhaustion windows
            # (processed after the failures due at t, like the scalar loop)
            due_esc = (alive & (B.next_esc <= t + 1e-12)).nonzero()[0]
            for s in due_esc.tolist():
                es, p = B.esc_list[s], B.esc_ptr[s]
                ts_ = t_list[s]
                while p < len(es) and es[p][0] <= ts_ + 1e-12:
                    self._process_escalation(B, s, ts_, es[p][1])
                    p += 1
                B.esc_ptr[s] = p
                B.next_esc[s] = es[p][0] if p < len(es) else np.inf

            # 6. next event horizon, per seed.  NaN pending (= no queued
            # attempt) propagates into the min and is rinsed by the
            # isfinite fallback, exactly like the scalar candidate filter.
            preparing = B.cur_on & ~B.cur_run
            cand[1] = rep_min
            cand[2] = np.where(B.cur_on, np.inf, B.pend)
            cand[3] = np.where(preparing, B.prep_until, np.inf)
            cand[4] = B.next_fail
            cand[5] = B.next_esc
            if B.has_infra and B.has_control:
                # wake at blind-window ends so queued decisions replay
                # (span boundaries must break there exactly like the
                # scalar candidate list — emission chunking feeds the
                # exporter rng, so the horizons must match bit for bit)
                due_bl = (alive & (B.next_blind <= t + 1e-12)).nonzero()[0]
                for s in due_bl.tolist():
                    bl, p = B.blind_list[s], B.blind_ptr[s]
                    ts_ = t_list[s]
                    while p < len(bl) and bl[p] <= ts_ + 1e-12:
                        p += 1
                    B.blind_ptr[s] = p
                    B.next_blind[s] = bl[p] if p < len(bl) else np.inf
                cand[6] = B.next_blind
            masked = np.where(cand <= t[None, :] + 1e-12, np.inf, cand)
            t_next = np.nanmin(masked, axis=0)
            t_next = np.where(np.isfinite(t_next), t_next, duration)
            np.minimum(t_next, duration, out=t_next)

            # 7. telemetry span emission (may truncate at a drain alarm)
            if telemetry:
                for s, ts_stop in self._emit(B, t_next).items():
                    if ts_stop < t_next[s]:
                        t_next[s] = ts_stop

            # 8. checkpoint catch-up over the span, vectorized
            run_mask = alive & B.cur_on & B.cur_run
            if run_mask.any():
                k = np.floor((t_next - B.last_ckpt + 1e-12)
                             / interval).astype(np.int64)
                k = np.where(run_mask, np.maximum(k, 0), 0)
                B.ckpt_events += k
                B.cur_steps += k
                B.last_ckpt += k * interval
                np.maximum(B.last_save, B.last_ckpt, out=B.last_save)

            # 9. advance / finish
            finishing = alive & (t_next >= duration)
            fin_idx = finishing.nonzero()[0]
            for s in fin_idx.tolist():
                self._finalize_seed(B, s)
            if len(fin_idx):
                B.alive = alive & ~finishing
            B.t = np.where(B.alive, t_next, B.t)

    def _finalize_seed(self, B: _Batch, s: int):
        duration = self.cfg.duration_h
        if B.cur_on[s]:
            self._account_degradation(B, s, duration)
            self._record_session(B, s, B.cur_created[s], duration)
            started = B.cur_started[s]
            if started == started:
                B.run_sum[s] += max(0.0, duration - started)
            if B.mat:
                log = B.cur_log[s]
                log[3] = duration
                log[4] = False                  # TERMINATED
                log[6] = int(B.cur_steps[s])
                B.cur_log[s] = None
            B.cur_on[s] = False
        self._close_chain(B, s)                 # the last (open) chain

    # -- result assembly ----------------------------------------------------

    def _materialize(self, B: _Batch, i: int) -> CampaignResult:
        cfg = self.cfg
        sessions = []
        for created, nodes, started, ended, is_err, error, steps, _tn \
                in B.session_log[i]:
            s = Session(task_name=_tn, n_nodes=cfg.job_nodes,
                        created_h=created)
            s.nodes = list(nodes)
            s.history = [(created, SessionState.SCHEDULED),
                         (created, SessionState.PREPARING)]
            if started is not None:
                s.started_h = started
                s.history.append((started, SessionState.RUNNING))
            if is_err:
                s.state = SessionState.ERROR
                s.history.append((ended, SessionState.ERROR))
                s.error = error
            else:
                s.state = SessionState.TERMINATED
                s.history.append((ended, SessionState.TERMINATING))
                s.history.append((ended, SessionState.TERMINATED))
            s.ended_h = ended
            s.checkpoint_step = steps
            sessions.append(s)

        tracker = ExclusionTracker(cfg.n_nodes)
        for t0, t1, part, iso_items in B.record_log[i]:
            iso = dict(iso_items)
            part_set = set(part)
            for node in range(cfg.n_nodes):
                if node in part_set:
                    continue
                tracker.intervals.append(ExclusionInterval(
                    node=node, t0_h=t0, t1_h=t1,
                    deliberate=node in iso,
                    reason=iso.get(node, "not selected")))

        plane = B.planes[i]
        return CampaignResult(
            sessions=sessions, chains=B.chains[i],
            failures=B.fails.events(i), exclusions=tracker,
            store=B.stores[i], downtimes=B.downtimes[i],
            checkpoint_events=int(B.ckpt_events[i]),
            lost_hours=B.lost[i], duration_h=cfg.duration_h,
            checkpoint_save_s=cfg.checkpoint_save_s,
            control=plane.stats if plane is not None else None,
            degraded_hours=B.degraded[i])

    def _findings(self, B: _Batch, i: int) -> dict:
        """`repro.ops.sweep.compute_findings` without the object graph:
        the run-time accumulators (the F4 fold of `chain_stats`, the
        tracker's counts, the session running-hour fold) fill the record
        `repro.core.findings` folds, so the values match the scalar path
        bit for bit."""
        cfg = self.cfg
        o0, o1 = int(B.fails.offsets[i]), int(B.fails.offsets[i + 1])
        kinds = B.fails.kind[o0:o1]
        plane = B.planes[i]
        autos, mans = split_downtimes(B.downtimes[i])
        return findings(LaneTotals(
            duration_h=cfg.duration_h,
            run_h=B.run_sum[i] if cfg.job_nodes > 1 else 0.0,
            lost_h=B.lost[i], ckpt_events=int(B.ckpt_events[i]),
            save_s=cfg.checkpoint_save_s,
            urgent_h=plane.stats.urgent_save_h if plane is not None
            else 0.0,
            degraded_h=B.degraded[i],
            n_failures=B.fails.count(i), n_sessions=B.n_sessions[i],
            excl_counts=np.bincount(B.npart_all[i], minlength=cfg.n_nodes)
            if B.npart_all[i] else np.zeros(cfg.n_nodes),
            n_deliberate=B.n_delib[i], n_intervals=B.n_intervals[i],
            f4=tuple(B.f4[i]), gaps_min=B.gaps[i],
            auto_down_h=autos, manual_down_h=mans,
            infra_n=int((kinds >= 3).sum()),
            corr_n=int((kinds >= 6).sum()),
            switch_ids=B.fails.switch[o0:o1][kinds == 6],
            control=plane.stats.summarize(B.fails.events(i), cfg.duration_h)
            if plane is not None else None,
            drain_excl=B.reason_counts[i].get("predictive drain", 0)))

# ---------------------------------------------------------------------------
# heterogeneous stacked dispatch (the what-if service's engine entry)
# ---------------------------------------------------------------------------

def run_findings_stacked(configs: Sequence[CampaignConfig],
                         seeds: Sequence[int], *,
                         wavefront_backend: str = "auto"
                         ) -> List[Dict[int, List[dict]]]:
    """Findings for every (config, seed) lane of a heterogeneous batch.

    The engine's lane axis is homogeneous per pass — every lane shares
    one ``CampaignConfig`` (numpy wavefront) or one node count
    (compiled grid, where gang masks share the node axis).  Callers
    holding a *mixed* bag of configs (the request coalescer) therefore
    get the documented grouping discipline instead of a free-form lane
    stack:

    * compiled-eligible configs (control-free, telemetry off, no
      correlated band) are grouped **by node count** and each group runs
      as ONE `run_findings_grid` device pass when the combined lane
      count clears the compiled floor;
    * every other config runs its own `BatchedCampaignEngine` pass
      (S seeds, one stacked-numpy wavefront).

    Per-seed findings are bitwise identical to running each config alone
    — lanes never interact (the parity contract both engines carry), so
    stacking is free coalescing, not approximation.  Returns
    ``out[i][seed]`` wrapped as per-config ``{seed: findings}`` dicts
    aligned with ``configs``; the number of underlying engine passes is
    ``len(configs)`` at most (fewer when grid groups form).
    """
    if wavefront_backend not in ("auto", "numpy", "xla", "pallas"):
        raise ValueError(
            f"unknown wavefront backend {wavefront_backend!r}")
    seeds = list(seeds)
    covered: Dict[int, List[dict]] = {}
    if wavefront_backend != "numpy":
        from repro.kernels.common import WAVEFRONT_MIN_SEEDS
        from repro.kernels.wavefront import compiled_eligible
        from repro.kernels.wavefront.ops import run_findings_grid
        groups: Dict[int, List[int]] = {}
        with tracing.span("stacked.resolve"):
            for i, cfg in enumerate(configs):
                if compiled_eligible(ClusterSim(cfg).cfg):
                    groups.setdefault(cfg.n_nodes, []).append(i)
        dev = "xla" if wavefront_backend == "auto" else wavefront_backend
        for idxs in groups.values():
            if wavefront_backend == "auto" \
                    and len(idxs) * len(seeds) < WAVEFRONT_MIN_SEEDS:
                continue                 # too few lanes to beat numpy
            per_cfg = run_findings_grid([configs[i] for i in idxs],
                                        seeds, backend=dev)
            for j, i in enumerate(idxs):
                covered[i] = per_cfg[j]
    out: List[Dict[int, List[dict]]] = []
    for i, cfg in enumerate(configs):
        per_seed = covered.get(i)
        if per_seed is None:
            per_seed = BatchedCampaignEngine(
                cfg, wavefront_backend="numpy").run_findings(seeds)
        out.append(dict(zip(seeds, per_seed)))
    return out
