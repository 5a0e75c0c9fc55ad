"""Dispatch + host glue for the compiled whole-campaign wavefront.

``run_findings_compiled(cfg, seeds)`` (and the dense-grid form
``run_findings_grid``) produce per-seed findings dicts **bitwise
identical** to ``BatchedCampaignEngine.run_findings`` / the scalar
``ClusterSim``, in three phases:

1. **materialize** (``tapes.py``) — every rng draw a campaign can
   consume becomes a pre-transformed tape; failure/escalation schedules
   and retry-delay tables become padded per-lane arrays;
2. **device pass** (``ref.py``) — one jitted ``lax.while_loop`` advances
   all lanes event by event, emitting a per-iteration record stream,
   integer accumulators and per-session gang bitmasks;
3. **host replay** (here) — the float accounting folds (checkpoint
   catch-up, lost work, run-hours, downtime windows, retry-gap lists,
   degradation overlaps) rerun in numpy along the iteration axis, where
   C-double arithmetic matches the scalar engine bit for bit; findings
   assemble with the exact ``_findings`` formulas.

Dispatch rules: the compiled core covers the control-free scope —
``cfg.telemetry`` off and ``cfg.control is None`` (reactive presets, all
retry policies, and the full infra fault band without a control plane).
Telemetry/control campaigns route to the numpy wavefront: the detector
feedback loop is already compiled elsewhere (``kernels/robust_stats``)
and the drain path is control-plane-coupled, so an honest backend split
beats a speculative one (same precedent as the detector's numpy floor).
``backend="auto"`` also floors at ``WAVEFRONT_MIN_SEEDS`` lanes, below
which the device round trip costs more than the numpy pass.

Cap discipline: device arrays are fixed-size (tape lengths, session
slots, iteration budget).  The core flags any lane that approaches a
cap; the driver doubles the flagged capacities and reruns — results are
only ever read from a clean pass.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from repro import tracing
from repro.core.cluster import CampaignConfig, ClusterSim
from repro.core.failures import (FailureInjector, degraded_overlap_h,
                                 has_correlated_band)
from repro.kernels.common import (WAVEFRONT_MIN_SEEDS, next_pow2, on_tpu,
                                  validate_backend)
from repro.kernels.wavefront.ref import (F_ADVANCE, F_ALLOCFAIL,
                                         F_CHAIN_CLOSE, F_FINALIZE,
                                         F_LOST, F_PREP_OK, F_RUNNING,
                                         F_SESS_FAIL, F_START, F_VALID,
                                         wavefront_core)
from repro.kernels.wavefront.tapes import (LaneTables, WavefrontCaps,
                                           build_lane_tables,
                                           concat_lane_tables,
                                           pad_lanes_pow2)

__all__ = ["compiled_eligible", "resolve_wavefront_backend",
           "run_findings_compiled", "run_findings_grid",
           "fabric_query_batch"]

_MAX_CAP_RETRIES = 6


def compiled_eligible(cfg: CampaignConfig) -> bool:
    """True when the campaign is in the compiled wavefront's scope.

    The correlated fault band (switch_degrade / dns_flap) is host-only:
    its variable-size blast-radius sets don't fit the fixed-lane tape
    layout, so configs carrying those kinds route to the numpy engines."""
    return (cfg.engine == "event" and not cfg.telemetry
            and cfg.control is None
            and not has_correlated_band(cfg.kind_weights))


def resolve_wavefront_backend(backend: str, cfg: CampaignConfig,
                              n_seeds: int) -> str:
    """Map a requested wavefront backend to the one that will run.

    ``auto`` picks the compiled path only when the config is eligible
    AND the batch clears the ``WAVEFRONT_MIN_SEEDS`` floor; explicit
    ``xla``/``pallas`` on an ineligible config is an error (silent
    fallback would misreport what ran)."""
    if backend == "auto":
        if compiled_eligible(cfg) and n_seeds >= WAVEFRONT_MIN_SEEDS:
            return "xla"
        return "numpy"
    validate_backend(backend, what="wavefront backend")
    if backend != "numpy" and not compiled_eligible(cfg):
        raise ValueError(
            f"wavefront backend {backend!r} requires a control-free "
            "campaign (telemetry off, control None, no correlated fault "
            "band); use backend='auto' or 'numpy' for telemetry/control/"
            "correlated configs")
    return backend


# -- device pass + cap-doubling driver ---------------------------------------

def device_tables(tables: LaneTables) -> Dict[str, np.ndarray]:
    """The lane tables as the device pass takes them: doubles cross to
    the device (and ``rec_t`` back) as their int64 bit patterns (see
    ``ref.py``): a TPU changes the low bits of a double it holds as
    f64."""
    return {k: v.view(np.int64) if v.dtype == np.float64 else v
            for k, v in tables.device.items()}


def _run_core(tables: LaneTables, backend: str, interpret: bool):
    import jax
    import jax.numpy as jnp
    with jax.enable_x64(True):
        with tracing.span("grid.upload"):
            P = {k: jnp.asarray(v)
                 for k, v in device_tables(tables).items()}
        with tracing.span("grid.run"):          # dispatch through fetch
            out = wavefront_core(
                P, n_nodes=tables.n_nodes,
                n_sessions=tables.caps.n_sessions,
                n_iters=tables.caps.n_iters,
                backend=backend, interpret=interpret)
            host = {k: np.asarray(v) for k, v in out.items()}
    host["rec_t"] = host["rec_t"].view(np.float64)
    return host


def _run_with_caps(build, backend: str, interpret: bool):
    """build(caps) -> LaneTables; rerun with doubled caps until no lane
    overflows (results are never read from an overflowed pass)."""
    caps = None
    for attempt in range(_MAX_CAP_RETRIES):
        if attempt:
            tracing.count("grid.cap_reruns")
        tables = build(caps)
        caps = tables.caps
        host = _run_core(tables, backend, interpret)
        if not host["overflow"][tables.device["lane_on"]].any():
            return tables, host
        caps = caps.doubled(("n_uniform", "n_manual", "n_struct",
                             "n_sessions", "n_iters"))
    raise RuntimeError(
        f"wavefront caps still overflow after {_MAX_CAP_RETRIES} "
        f"doublings (last: {caps})")


# -- host replay of the float accounting folds -------------------------------

class _Replay:
    """Per-lane accounting state driven by the device record stream."""

    def __init__(self, L: int):
        self.cur_t = np.zeros(L)
        self.last_ckpt = np.zeros(L)
        self.last_save = np.zeros(L)
        self.ckpt_events = np.zeros(L, dtype=np.int64)
        self.started = np.full(L, np.nan)
        self.open_sess = np.zeros(L, dtype=bool)
        self.prev_end = np.full(L, np.nan)
        self.down_since = np.full(L, np.nan)
        self.down_auto = np.ones(L, dtype=bool)
        self.n_att = np.zeros(L, dtype=np.int64)
        self.retry_reached = np.zeros(L, dtype=bool)
        self.run_sum = np.zeros(L)
        self.f4 = np.zeros((L, 3), dtype=np.int64)
        self.gaps: List[List[float]] = [[] for _ in range(L)]
        self.lost: List[List[float]] = [[] for _ in range(L)]
        self.downtimes: List[List[tuple]] = [[] for _ in range(L)]
        self.sess: List[List[tuple]] = [[] for _ in range(L)]


def _replay(tables: LaneTables, host: Dict[str, np.ndarray]) -> _Replay:
    """Rerun the float folds along the iteration axis.  Application
    order within an iteration mirrors the numpy wavefront's step order
    (starts -> prep-done -> session fail/lost -> chain close -> finalize
    -> checkpoint catch-up), so every sequential float accumulation sees
    the same operand sequence as the scalar engine."""
    L = host["rec_t"].shape[1]
    R = _Replay(L)
    interval = tables.interval
    duration = tables.duration
    it_count = int(host["it"])
    rec_t, rec_fl = host["rec_t"], host["rec_flags"]
    isnan = np.isnan
    for it in range(it_count):
        fl = rec_fl[it]
        if not fl.any():
            continue
        tn = rec_t[it]
        t = R.cur_t

        m_start = (fl & F_START) != 0
        m_af = (fl & F_ALLOCFAIL) != 0
        m_att = m_start | m_af
        if m_att.any():
            gm = m_att & ~isnan(R.prev_end)
            if gm.any():
                gv = (t - R.prev_end) * 60.0
                for s in np.nonzero(gm)[0]:
                    R.gaps[s].append(float(gv[s]))
            R.n_att[m_att] += 1
            R.prev_end[m_af] = t[m_af]
            R.prev_end[m_start] = np.nan
            R.started[m_start] = np.nan
            R.open_sess[m_start] = True

        m_pok = (fl & F_PREP_OK) != 0
        if m_pok.any():
            R.started[m_pok] = t[m_pok]
            R.retry_reached[m_pok & (R.n_att != 1)] = True
            R.last_ckpt[m_pok] = t[m_pok]
            R.last_save[m_pok] = t[m_pok]
            dc = m_pok & ~isnan(R.down_since)
            for s in np.nonzero(dc)[0]:
                R.downtimes[s].append(
                    (float(t[s] - R.down_since[s]), bool(R.down_auto[s])))
            R.down_since[dc] = np.nan
            R.down_auto[dc] = True

        m_fail = (fl & F_SESS_FAIL) != 0
        m_lost = (fl & F_LOST) != 0
        if m_fail.any():
            if m_lost.any():            # lost precedes the teardown fold
                lv = np.minimum(t - R.last_save, interval)
                for s in np.nonzero(m_lost)[0]:
                    R.lost[s].append(float(lv[s]))
            rs = m_fail & ~isnan(R.started)
            R.run_sum[rs] += np.maximum(0.0, t[rs] - R.started[rs])
            for s in np.nonzero(m_fail)[0]:
                R.sess[s].append((float(R.started[s]), float(t[s])))
            R.started[m_fail] = np.nan
            R.open_sess[m_fail] = False
            R.prev_end[m_fail] = t[m_fail]
            dn = m_fail & isnan(R.down_since)
            R.down_since[dn] = t[dn]

        m_cc = (fl & F_CHAIN_CLOSE) != 0
        if m_cc.any():
            g = m_cc & (R.n_att > 1)
            R.f4[g, 0] += 1
            R.f4[g, 1] += R.n_att[g]
            R.f4[g & R.retry_reached, 2] += 1
            R.n_att[m_cc] = 0
            R.retry_reached[m_cc] = False
            R.prev_end[m_cc] = np.nan
            R.down_auto[m_cc] = False

        m_fin = (fl & F_FINALIZE) != 0
        if m_fin.any():
            fo = m_fin & R.open_sess
            rs = fo & ~isnan(R.started)
            R.run_sum[rs] += np.maximum(0.0, duration[rs] - R.started[rs])
            for s in np.nonzero(fo)[0]:
                R.sess[s].append((float(R.started[s]), float(duration[s])))
            R.open_sess[fo] = False
            R.started[fo] = np.nan
            g = m_fin & (R.n_att > 1)
            R.f4[g, 0] += 1
            R.f4[g, 1] += R.n_att[g]
            R.f4[g & R.retry_reached, 2] += 1
            R.n_att[m_fin] = 0
            R.retry_reached[m_fin] = False

        m_run = ((fl & F_ADVANCE) != 0) & ((fl & F_RUNNING) != 0)
        if m_run.any():
            k = np.floor((tn - R.last_ckpt + 1e-12)
                         / interval).astype(np.int64)
            k = np.where(m_run, np.maximum(k, 0), 0)
            R.ckpt_events += k
            R.last_ckpt += k * interval
            np.maximum(R.last_save, R.last_ckpt, out=R.last_save)

        m_adv = (fl & F_ADVANCE) != 0
        R.cur_t = np.where(m_adv, tn, R.cur_t)
    return R


def _degraded(tables: LaneTables, host, R: _Replay,
              lane: int) -> List[float]:
    windows = tables.deg_windows[lane]
    if not windows:
        return []
    gang = host["se_gang"][lane]
    out: List[float] = []
    for k, (t0, t1) in enumerate(R.sess[lane]):
        if t0 != t0:                    # never reached RUNNING
            continue
        nodes = np.nonzero(gang[k])[0].tolist()
        d = degraded_overlap_h(windows, t0, t1, nodes)
        if d:
            out.append(d)
    return out


def _lane_findings(tables: LaneTables, host, R: _Replay,
                   lane: int) -> dict:
    duration = float(tables.duration[lane])
    n_chains, n_attempts, succ = (int(v) for v in R.f4[lane])
    gaps = R.gaps[lane]
    counts = host["npart_counts"][lane].astype(float)
    total = counts.sum()
    top3 = float(np.sort(counts)[::-1][:3].sum() / total) \
        if total else 0.0
    delib_frac = float(int(host["n_delib"][lane])
                       / max(int(host["n_intervals"][lane]), 1))
    autos = [h for h, auto in R.downtimes[lane] if auto]
    mans = [h for h, auto in R.downtimes[lane] if not auto]
    run = float(R.run_sum[lane]) if tables.job_gt1[lane] else 0.0
    lost = R.lost[lane]
    ckpt_h = int(R.ckpt_events[lane]) \
        * float(tables.save_s[lane]) / 3600.0
    degraded = _degraded(tables, host, R, lane)
    deg_h = float(np.sum(degraded))
    goodput_h = run - float(np.sum(lost)) - ckpt_h - 0.0 - deg_h
    return {
        "occupancy": min(run / duration, 1.0),
        "goodput": max(goodput_h, 0.0) / duration,
        "n_failures": float(tables.n_failures[lane]),
        "n_sessions": float(host["n_sessions"][lane]),
        "ckpt_events": float(R.ckpt_events[lane]),
        "mean_lost_h": float(np.mean(lost)) if lost else 0.0,
        "f3_top3_share": top3,
        "f3_deliberate_fraction": delib_frac,
        "f4_n_chains": float(n_chains),
        "f4_n_attempts": float(n_attempts),
        "f4_success_rate": succ / n_chains if n_chains else 0.0,
        "f4_gap_median_min": float(np.median(gaps)) if gaps else None,
        "f4_auto_downtime_h": float(np.median(autos)) if autos else None,
        "f4_manual_downtime_h": float(np.median(mans)) if mans else None,
        "infra_n_events": float(tables.infra_n[lane]),
        "infra_degraded_h": deg_h,
        # eligibility excludes the correlated band, so these lanes carry
        # no switch_degrade / dns_flap events by construction
        "corr_n_events": 0.0,
        "corr_top_switch_share": 0.0,
    }


# -- public entry points -----------------------------------------------------

def run_findings_grid(configs: Sequence[CampaignConfig],
                      seeds: Sequence[int], *, backend: str = "xla",
                      interpret: Optional[bool] = None,
                      caps: Optional[WavefrontCaps] = None
                      ) -> List[List[dict]]:
    """Findings for every (config, seed) lane of a dense scenario grid
    in ONE stacked device pass.  Returns ``out[g][s]`` aligned with the
    inputs; every dict is bitwise identical to the numpy engines'."""
    if not configs:
        return []
    if interpret is None:
        interpret = not on_tpu()
    resolved = []
    with tracing.span("grid.draws"):
        for cfg in configs:
            base = ClusterSim(cfg)
            rcfg = base.cfg
            if not compiled_eligible(rcfg):
                raise ValueError(
                    "run_findings_grid covers control-free campaigns only "
                    "(telemetry off, control None, no correlated fault "
                    "band)")
            injector = FailureInjector(
                n_nodes=rcfg.n_nodes, mtbf_h=rcfg.mtbf_h,
                hot_fraction=rcfg.hot_fraction, hot_weight=rcfg.hot_weight,
                kind_weights=rcfg.kind_weights,
                topology_fanout=rcfg.topology_fanout, seed=rcfg.seed)
            fails = injector.sample_batch(rcfg.duration_h, seeds)
            resolved.append((rcfg, fails))

    def build(caps_in):
        with tracing.span("grid.tapes"):
            blocks = [build_lane_tables(rcfg, fails, seeds, caps=caps_in)
                      for rcfg, fails in resolved]
            return pad_lanes_pow2(concat_lane_tables(blocks))

    first = build(caps)
    tables, host = _run_with_caps(
        lambda c: first if c is None else build(c), backend, interpret)
    with tracing.span("grid.replay"):
        R = _replay(tables, host)
    S = len(seeds)
    out: List[List[dict]] = []
    with tracing.span("grid.findings"):
        for g in range(len(configs)):
            out.append([_lane_findings(tables, host, R, g * S + s)
                        for s in range(S)])
    return out


def run_findings_compiled(config: CampaignConfig, seeds: Sequence[int],
                          *, backend: str = "xla",
                          interpret: Optional[bool] = None,
                          caps: Optional[WavefrontCaps] = None
                          ) -> List[dict]:
    """Single-config form of :func:`run_findings_grid`."""
    return run_findings_grid([config], seeds, backend=backend,
                             interpret=interpret, caps=caps)[0]


def fabric_query_batch(fabric, op, fanins, bytes_per_client, *,
                       slots_per_client=None, rpc_bytes=None,
                       backend: str = "numpy",
                       interpret: Optional[bool] = None) -> np.ndarray:
    """Batched ``StorageFabric.expected_duration_s`` over stacked query
    rows (``fanins``/``bytes_per_client`` broadcast together).

    ``backend='numpy'`` evaluates through the fabric itself (the bitwise
    resolution oracle); ``'xla'`` evaluates the same analytic formula on
    device in f64 (1-ulp class; the mul-add chains may contract to FMA)
    and ``'pallas'`` in f32 lane tiles (~1e-7 relative) — both for wide
    sweep surfaces, never for campaign setup."""
    from repro.storage.fabric import _std_rpc_bytes, _std_slots
    validate_backend(backend, what="fabric query backend")
    fanins = np.atleast_1d(np.asarray(fanins))
    byts = np.broadcast_to(np.atleast_1d(np.asarray(bytes_per_client)),
                           fanins.shape)
    slots = _std_slots(op) if slots_per_client is None else slots_per_client
    size = _std_rpc_bytes(op) if rpc_bytes is None else rpc_bytes
    if backend == "numpy":
        return np.array([fabric.expected_duration_s(
            op, int(f), int(b), slots_per_client=slots, rpc_bytes=size)
            for f, b in zip(fanins, byts)])
    cfg = fabric.config
    server_bw, ctx, t_base, t_queue = cfg.op_params(op)
    inflight = np.maximum(fanins.astype(np.int64), 1) * slots
    n_rpcs = np.maximum(np.ceil(byts / size), 1.0)
    n_waves = np.maximum(n_rpcs / slots, 1.0)
    jmean = float(np.exp(cfg.service_jitter ** 2 / 2.0))
    args = (np.full_like(n_waves, t_base), np.full_like(n_waves, size),
            inflight.astype(float), np.full_like(n_waves, server_bw),
            np.full_like(n_waves, t_queue), np.full_like(n_waves, ctx),
            np.full_like(n_waves, slots),
            np.full_like(n_waves, cfg.client_link_bw),
            np.full_like(n_waves, cfg.degradation), n_waves,
            np.full_like(n_waves, jmean))
    import jax.numpy as jnp
    if backend == "pallas":
        from repro.kernels.wavefront.kernel import fabric_query_pallas
        if interpret is None:
            interpret = not on_tpu()
        out = fabric_query_pallas(*(jnp.asarray(a) for a in args),
                                  interpret=interpret)
        return np.asarray(out, dtype=float)
    import jax

    from repro.kernels.wavefront.kernel import _fabric_ref_jit
    with jax.enable_x64(True):
        out = _fabric_ref_jit(*(jnp.asarray(a) for a in args))
        return np.asarray(out, dtype=float)
