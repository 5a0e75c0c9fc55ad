"""Host-side draw tapes and event tables for the compiled wavefront.

The compiled core cannot call ``default_rng`` mid-loop, so every sampled
decision a campaign can take is materialized up front, extending
``sample_batch``'s draw-order discipline to the remaining streams:

* the **main uniform tape** ``u`` — ``default_rng(seed).random(U)`` is
  positionally identical to U sequential ``rng.random()`` calls, and
  after the rng stream refactor (``RNG_STREAM_MANUAL`` /
  ``RNG_STREAM_STRUCT`` in ``repro.core.cluster``) the main stream
  consumes *only* ``random()`` uniforms, so one pointer walks it;
* the **manual-delay tapes** — one ``standard_exponential`` sequence on
  the dedicated ``[seed, RNG_STREAM_MANUAL]`` stream, pre-scaled by both
  the day and the night response means (the consumer picks one, the
  pointer advances once — exactly the scalar call pattern);
* the **structural-fix tapes** — the ``[seed, RNG_STREAM_STRUCT]``
  sequence pre-scaled by ``mean/2`` (manual-misfix horizon) and ``mean``
  (software follow-on), one pointer, scaling chosen per consumption site.

Why the tapes carry *transformed* values rather than raw draws: XLA CPU
contracts ``a + b*c`` into an FMA inside a jitted computation, which
breaks bitwise parity with the numpy engines by 1 ulp on ~12% of
elements (and ``lax.optimization_barrier`` does not prevent it).  Every
multiply-add that feeds a parity-critical float therefore happens here,
in numpy elementwise ufuncs (separate C loops, never fused): the device
only gathers, compares, and performs lone adds.  The same reasoning
produces the **retry delay tables** (``dna`` per attempt count, per-event
``fdelay`` for the XID branch, both pre-divided by 60) so the device
computes ``pend = t + delay`` as a single fadd.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.cluster import (RNG_STREAM_MANUAL, RNG_STREAM_STRUCT,
                                CampaignConfig)
from repro.core.failures import (CORRELATED_KINDS, DEGRADE_KINDS,
                                 KIND_NAMES, FailureBatch,
                                 degradation_windows, escalation_events)
from repro.core.retry import RetryEngine, RetryPolicy

__all__ = ["WavefrontCaps", "LaneTables", "build_lane_tables",
           "concat_lane_tables", "max_failures", "pad_lanes_pow2"]

# load-duration uniform widths (bit-exact fast forms of the scalar
# draws, shared with the numpy engines: uniform(a, b) == a + (b-a)*u)
_W_LOAD = 0.3 - (-0.08)
_W_FAIL = 0.15 - 0.05

# Kind codes whose events open degradation windows or escalate: a lane
# with none of them has neither, and its events are not materialized.
_WINDOW_KINDS = np.array(sorted(KIND_NAMES.index(k)
                                for k in DEGRADE_KINDS | CORRELATED_KINDS))


# Cap consumption per failure of a block's busiest lane (see
# ``WavefrontCaps.sized``).
_PER_FAILURE = {"n_uniform": 12, "n_manual": 2, "n_struct": 2,
                "n_sessions": 6, "n_iters": 12}


@dataclass(frozen=True)
class WavefrontCaps:
    """Static device-array capacities (all jit-cache keys).

    Each cap carries slack beyond the expected consumption; the device
    flags any lane that comes within one iteration's worth of a cap and
    the driver re-runs with that cap doubled (see ``ops.py``).  The
    defaults are the floors of :meth:`sized`.
    """
    n_uniform: int = 2048        # main-stream uniforms per lane
    n_manual: int = 512          # manual-delay draws per lane
    n_struct: int = 512          # structural-fix draws per lane
    n_sessions: int = 512        # session gang-mask slots per lane
    n_iters: int = 4096          # wavefront iterations

    @classmethod
    def sized(cls, max_failures: int) -> "WavefrontCaps":
        """Caps for a block whose busiest lane draws ``max_failures``
        failures: each cap is ``max(default, next_pow2(rate *
        max_failures))`` with the per-failure rates of ``_PER_FAILURE``
        (powers of two keep the jit keys few).

        The rates come from what clean passes consumed (every cap raised,
        on the CPU), per lane, largest over the block, divided by the
        block's largest failure count:

        * 2,176-node pool, 2,048-node gang, 54 d (``llama3-16k``; 4
          variants x seeds 0..63, largest failure count 486): 4,200
          iterations (8.6 a failure), 3,791 uniforms (7.8), 1,792
          sessions (3.7), 203 manual and 236 structural draws (0.4, 0.5);
          with automatic retry off, 493 and 351 (1.0, 0.7).  The rates
          leave at least 1.4x of room above these.
        * 63-node pool, 60-node gang, 73 d (``paper-63n``; 10 variants x
          seeds 0..99, largest failure count 81): 1,349 iterations, 1,783
          uniforms, 371 sessions, 455 manual and 196 structural draws.  A
          small pool retries through long alloc-fail chains while its
          three spares are in repair, so it consumes more per failure (up
          to 31 uniforms, and 14 manual draws without automatic retry).
          The floors hold such blocks: the rates pass them only beyond
          85 (sessions) to 341 (iterations) failures a lane.

        The retry policy and ``max_retries`` do not enter: each rate is
        the largest over every retry policy measured (fixed, exponential
        backoff, XID branch, structural stop, none), and the bound they
        give, ``max_retries + 1`` sessions a failure, is eight times
        looser than what lanes use.  The doubling rerun in
        ``ops._run_with_caps`` stays the guard.
        """
        from repro.kernels.common import next_pow2
        floor = cls()
        return replace(floor, **{
            k: max(getattr(floor, k), next_pow2(rate * int(max_failures)))
            for k, rate in _PER_FAILURE.items()})

    def doubled(self, which: Sequence[str]) -> "WavefrontCaps":
        return replace(self, **{k: 2 * getattr(self, k) for k in which})


@dataclass
class LaneTables:
    """Device inputs + host-side replay context for a block of lanes.

    ``device`` maps names to stacked ``(L, ...)`` numpy arrays (tapes,
    event tables, per-lane parameters); everything else is host-only
    context the replay/findings pass needs (degradation windows, the
    original per-lane failure slices, checkpoint constants).
    """
    device: Dict[str, np.ndarray]
    n_nodes: int
    caps: WavefrontCaps
    # host-side per-lane context
    seeds: List[int]
    interval: np.ndarray         # (L,) checkpoint_interval_h
    duration: np.ndarray         # (L,) duration_h
    save_s: np.ndarray           # (L,) checkpoint_save_s
    job_gt1: np.ndarray          # (L,) bool: job_nodes > 1 (occupancy gate)
    deg_windows: List[list]      # per-lane degradation windows
    n_failures: np.ndarray       # (L,) failure-event counts
    infra_n: np.ndarray          # (L,) infra-band event counts

    @property
    def n_lanes(self) -> int:
        return len(self.seeds)


def max_failures(fails: FailureBatch) -> int:
    """The largest failure count of one lane of ``fails``."""
    return int(np.diff(fails.offsets).max(initial=0))


def _delay_table(cfg: CampaignConfig, engine: RetryEngine,
                 n_rows: int) -> np.ndarray:
    """``dna[k]`` = automatic-retry delay (hours) after attempt count
    ``k`` with no XID resolution, NaN where the scalar path yields None.
    Mirrors ``BatchedCampaignEngine._schedule_next``'s FIXED shortcut and
    ``RetryEngine.next_delay_min`` for the other policies."""
    r = cfg.retry
    fixed = r.delay_min + r.teardown_min \
        if r.policy is RetryPolicy.FIXED else None
    out = np.full(n_rows, np.nan)
    for k in range(n_rows):
        if fixed is not None:
            d = fixed if r.enabled and k <= r.max_retries else None
        else:
            d = engine.next_delay_min(k, xid=None)
        if d is not None:
            out[k] = d / 60.0
    return out


def build_lane_tables(cfg: CampaignConfig, fails: FailureBatch,
                      seeds: Sequence[int],
                      caps: Optional[WavefrontCaps] = None) -> LaneTables:
    """Materialize one config's S seed lanes (config must be resolved —
    i.e. ``ClusterSim(cfg).cfg`` — so storage-derived checkpoint params
    are final).  ``caps`` defaults to :meth:`WavefrontCaps.sized` for
    these lanes."""
    caps = caps if caps is not None \
        else WavefrontCaps.sized(max_failures(fails))
    S, n = len(seeds), cfg.n_nodes
    U, M, X = caps.n_uniform, caps.n_manual, caps.n_struct
    engine = RetryEngine(cfg.retry)

    u = np.empty((S, U))
    man_day = np.empty((S, M))
    man_night = np.empty((S, M))
    x_half = np.empty((S, X))
    x_full = np.empty((S, X))
    half_mean = cfg.structural_fix_mean_h / 2
    for i, seed in enumerate(seeds):
        u[i] = np.random.default_rng(seed).random(U)
        std_m = np.random.default_rng(
            [seed, RNG_STREAM_MANUAL]).standard_exponential(M)
        man_day[i] = cfg.manual_response_h_day * std_m
        man_night[i] = cfg.manual_response_h_night * std_m
        std_x = np.random.default_rng(
            [seed, RNG_STREAM_STRUCT]).standard_exponential(X)
        x_half[i] = half_mean * std_x
        x_full[i] = cfg.structural_fix_mean_h * std_x
    # pre-transformed load durations (numpy ufuncs are separate C loops —
    # bitwise equal to the scalar chain, and no fmul feeds an fadd on
    # device).  The inner term is shared exactly like the scalar form.
    inner = -0.08 + _W_LOAD * u
    dur_fail = 0.05 + _W_FAIL * u
    dur_warm = cfg.loading_time_h + inner
    dur_cold = cfg.loading_cold_h + inner

    # failure tables, padded (S, F); +inf times never come due.  The +1
    # guarantees a trailing +inf sentinel on EVERY lane: the device gather
    # clips the pointer, so without it the widest lane would re-read its
    # last real event after draining the queue and never leave "pending"
    offs = fails.offsets
    F = max(int((offs[1:] - offs[:-1]).max()), 0) + 1
    ft = np.full((S, F), np.inf)
    fnode = np.zeros((S, F), dtype=np.int32)
    fkcode = np.full((S, F), 3, dtype=np.int32)   # pad rows are inert
    fhw = np.zeros((S, F), dtype=bool)
    fdelay = np.full((S, F), np.nan)
    fhas_xid = np.zeros((S, F), dtype=bool)
    is_xid_policy = cfg.retry.policy is RetryPolicy.XID_BRANCH
    E = 1
    esc_rows: List[list] = []
    deg_windows: List[list] = []
    for i in range(S):
        o0, o1 = int(offs[i]), int(offs[i + 1])
        k = o1 - o0
        ft[i, :k] = fails.times[o0:o1]
        fnode[i, :k] = fails.nodes[o0:o1]
        fkcode[i, :k] = fails.kind[o0:o1]
        fhw[i, :k] = fails.hardware[o0:o1]
        if is_xid_policy:
            for j in range(k):
                xid = int(fails.xid[o0 + j])
                if fails.kind[o0 + j] <= 1 and xid >= 0:
                    fhas_xid[i, j] = True
                    # the attempt-count guard lives on device (n < max_r
                    # subsumes it), so the table only resolves the action
                    d = engine.next_delay_min(1, xid=xid)
                    if d is not None:
                        fdelay[i, j] = d / 60.0
        if np.isin(fails.kind[o0:o1], _WINDOW_KINDS).any():
            evs = fails.events(i)
            deg_windows.append(degradation_windows(evs))
            es = escalation_events(evs)
        else:
            deg_windows.append([])
            es = []
        esc_rows.append(es)
        E = max(E, len(es))
    et = np.full((S, E + 1), np.inf)      # same +inf sentinel discipline
    enode = np.zeros((S, E + 1), dtype=np.int32)
    for i, es in enumerate(esc_rows):
        for j, (t_crash, node) in enumerate(es):
            et[i, j] = t_crash
            enode[i, j] = node

    dna = np.tile(_delay_table(cfg, engine, cfg.retry.max_retries + 2),
                  (S, 1))
    notice_p = (cfg.retry.delay_min / 60.0) \
        / max(cfg.operator_notice_mean_h, 1e-6) * 0.5

    def const(v, dtype=np.float64):
        return np.full(S, v, dtype=dtype)

    device = {
        "u": u, "dur_fail": dur_fail, "dur_warm": dur_warm,
        "dur_cold": dur_cold, "man_day": man_day, "man_night": man_night,
        "x_half": x_half, "x_full": x_full,
        "ft": ft, "fnode": fnode, "fkcode": fkcode, "fhw": fhw,
        "fdelay": fdelay, "fhas_xid": fhas_xid, "et": et, "enode": enode,
        "dna": dna,
        "duration": const(cfg.duration_h),
        "job": const(cfg.job_nodes, np.int32),
        "p_readmit": const(cfg.p_pressure_readmit),
        "p_transient": const(cfg.p_transient_retry_fail),
        "p_soft": const(cfg.p_software_failure),
        "p_misfix": const(cfg.p_manual_misfix),
        "notice_p": const(notice_p),
        "repair_h": const(cfg.repair_time_h),
        "slow_iso_h": const(cfg.slow_isolation_h),
        "retry_on": const(cfg.retry.enabled, bool),
        "max_r": const(cfg.retry.max_retries, np.int32),
        "policy_xid": const(is_xid_policy, bool),
        "struct_stop": const(cfg.retry.structural_stop, bool),
        "lane_on": np.ones(S, dtype=bool),
    }
    kinds = fails.kind
    infra_n = np.array([int((kinds[int(offs[i]):int(offs[i + 1])] >= 3)
                            .sum()) for i in range(S)])
    return LaneTables(
        device=device, n_nodes=n, caps=caps, seeds=list(seeds),
        interval=const(cfg.checkpoint_interval_h),
        duration=const(cfg.duration_h),
        save_s=const(cfg.checkpoint_save_s),
        job_gt1=const(cfg.job_nodes > 1, bool),
        deg_windows=deg_windows,
        n_failures=(offs[1:] - offs[:-1]).astype(np.int64),
        infra_n=infra_n)


def _pad_cols(a: np.ndarray, width: int, fill) -> np.ndarray:
    if a.shape[1] == width:
        return a
    out = np.full((a.shape[0], width), fill, dtype=a.dtype)
    out[:, :a.shape[1]] = a
    return out


def concat_lane_tables(blocks: Sequence[LaneTables]) -> LaneTables:
    """Stack per-config lane blocks into one dense grid batch.  Ragged
    event-table widths (failure count, escalations, retry-table rows)
    pad to the grid maximum with inert rows; every other array simply
    concatenates along the lane axis."""
    if len(blocks) == 1:
        return blocks[0]
    n = blocks[0].n_nodes
    caps = blocks[0].caps
    for b in blocks[1:]:
        if b.n_nodes != n:
            raise ValueError("dense grid requires a uniform n_nodes; got "
                             f"{b.n_nodes} vs {n}")
        if b.caps != caps:
            raise ValueError("lane blocks built with different caps")
    pad_fill = {"ft": np.inf, "fkcode": 3, "fdelay": np.nan,
                "et": np.inf, "dna": np.nan}
    ragged = ("ft", "fnode", "fkcode", "fhw", "fdelay", "fhas_xid",
              "et", "enode", "dna")
    device: Dict[str, np.ndarray] = {}
    for key in blocks[0].device:
        parts = [b.device[key] for b in blocks]
        if key in ragged:
            width = max(p.shape[1] for p in parts)
            parts = [_pad_cols(p, width, pad_fill.get(key, 0))
                     for p in parts]
        device[key] = np.concatenate(parts, axis=0)
    return LaneTables(
        device=device, n_nodes=n, caps=caps,
        seeds=sum((b.seeds for b in blocks), []),
        interval=np.concatenate([b.interval for b in blocks]),
        duration=np.concatenate([b.duration for b in blocks]),
        save_s=np.concatenate([b.save_s for b in blocks]),
        job_gt1=np.concatenate([b.job_gt1 for b in blocks]),
        deg_windows=sum((b.deg_windows for b in blocks), []),
        n_failures=np.concatenate([b.n_failures for b in blocks]),
        infra_n=np.concatenate([b.infra_n for b in blocks]))


def pad_lanes_pow2(tables: LaneTables, min_lanes: int = 64) -> LaneTables:
    """Pad the lane axis to a power of two (the shared seed-bucketing
    discipline, ``kernels.common.next_pow2``).  Padded lanes arrive with
    ``lane_on=False`` — the device loop never wakes them and the findings
    pass slices them away."""
    from repro.kernels.common import next_pow2
    L = tables.n_lanes
    Lp = max(next_pow2(L), min_lanes)
    if Lp == L:
        return tables
    pad = Lp - L
    fill = {"ft": np.inf, "et": np.inf, "fdelay": np.nan, "dna": np.nan,
            "fkcode": 3, "duration": 1.0, "job": 1, "max_r": 0}
    device = {}
    for key, a in tables.device.items():
        out = np.full((Lp,) + a.shape[1:], fill.get(key, 0),
                      dtype=a.dtype)
        out[:L] = a
        device[key] = out
    device["lane_on"][L:] = False
    ones = np.ones(pad)
    return LaneTables(
        device=device, n_nodes=tables.n_nodes, caps=tables.caps,
        seeds=tables.seeds + [-1] * pad,
        interval=np.concatenate([tables.interval, ones]),
        duration=np.concatenate([tables.duration, ones]),
        save_s=np.concatenate([tables.save_s, ones]),
        job_gt1=np.concatenate(
            [tables.job_gt1, np.zeros(pad, dtype=bool)]),
        deg_windows=tables.deg_windows + [[] for _ in range(pad)],
        n_failures=np.concatenate(
            [tables.n_failures, np.zeros(pad, dtype=np.int64)]),
        infra_n=np.concatenate(
            [tables.infra_n, np.zeros(pad, dtype=np.int64)]))
