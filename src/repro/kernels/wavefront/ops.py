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
   integer accumulators and, only where some lane has degradation
   windows, per-session gang bitmasks (packed);
3. **host replay** (here) — the float accounting folds (checkpoint
   catch-up, lost work, run-hours, downtime windows, retry-gap lists,
   degradation overlaps) rerun in numpy along the iteration axis, where
   C-double arithmetic matches the scalar engine bit for bit; each
   lane's totals go through the one findings fold
   (``repro.core.findings``).

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
slots, iteration budget), sized from the block's largest failure count
(``WavefrontCaps.sized``).  The core flags any lane that approaches a
cap; the driver doubles the capacities and reruns — results are only
ever read from a clean pass.

Tracing counters (``repro.tracing``): ``grid.cap_reruns`` (device passes
rerun), ``grid.gang_mask_bytes`` (session gang masks fetched from the
device, every pass) and ``grid.iterations`` (the clean pass's iteration
count).  ``last_grid_pass`` keeps the last grid call's numbers whether
or not tracing is on.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from repro import tracing
from repro.core.cluster import CampaignConfig, ClusterSim
from repro.core.findings import LaneTotals, findings
from repro.core.failures import (FailureInjector, degraded_overlap_h,
                                 has_correlated_band)
from repro.kernels.common import (WAVEFRONT_MIN_SEEDS, on_tpu,
                                  validate_backend)
from repro.kernels.wavefront.ref import (F_ADVANCE, F_ALLOCFAIL,
                                         F_CHAIN_CLOSE, F_FINALIZE,
                                         F_LOST, F_PREP_OK, F_RUNNING,
                                         F_SESS_FAIL, F_START,
                                         unpack_gang, wavefront_core)
from repro.kernels.wavefront.tapes import (LaneTables, WavefrontCaps,
                                           build_lane_tables,
                                           concat_lane_tables,
                                           max_failures, pad_lanes_pow2)

__all__ = ["compiled_eligible", "resolve_wavefront_backend",
           "run_findings_compiled", "run_findings_grid"]

_MAX_CAP_RETRIES = 6

# The last run_findings_grid call's device-pass numbers, as the tracing
# counters of one call give them: ``cap_reruns``, ``gang_mask_bytes``
# and ``iterations``.  Kept whether or not tracing is on (one dict a
# call), for a caller that cannot open a tracing window around the call.
last_grid_pass: Dict[str, int] = {}


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
    """One device pass.  Session gang masks are carried only where some
    lane has degradation windows, their one reader (``_degraded``)."""
    import jax
    import jax.numpy as jnp
    masks = any(tables.deg_windows)
    with jax.enable_x64(True):
        with tracing.span("grid.upload"):
            P = {k: jnp.asarray(v)
                 for k, v in device_tables(tables).items()}
        with tracing.span("grid.run"):          # dispatch through fetch
            out = wavefront_core(
                P, n_nodes=tables.n_nodes,
                n_sessions=tables.caps.n_sessions if masks else 0,
                n_iters=tables.caps.n_iters,
                backend=backend, interpret=interpret)
            host = {k: np.asarray(v) for k, v in out.items()}
    host["rec_t"] = host["rec_t"].view(np.float64)
    return host


def _run_with_caps(build, caps: WavefrontCaps, backend: str,
                   interpret: bool):
    """build(caps) -> LaneTables; rerun with doubled caps until no lane
    overflows (results are never read from an overflowed pass)."""
    fetched = 0
    for attempt in range(_MAX_CAP_RETRIES):
        if attempt:
            tracing.count("grid.cap_reruns")
        tables = build(caps)
        host = _run_core(tables, backend, interpret)
        mask_bytes = host["se_gang"].nbytes if "se_gang" in host else 0
        tracing.count("grid.gang_mask_bytes", mask_bytes)
        fetched += mask_bytes
        if not host["overflow"][tables.device["lane_on"]].any():
            tracing.count("grid.iterations", int(host["it"]))
            last_grid_pass.clear()
            last_grid_pass.update(cap_reruns=attempt,
                                  gang_mask_bytes=fetched,
                                  iterations=int(host["it"]))
            return tables, host
        caps = caps.doubled(("n_uniform", "n_manual", "n_struct",
                             "n_sessions", "n_iters"))
    raise RuntimeError(
        f"wavefront caps still overflow after {_MAX_CAP_RETRIES} "
        f"doublings (last: {caps})")


# -- host replay of the float accounting folds -------------------------------

class _Lists:
    """Per-lane lists of values (one or more columns) appended in replay
    order: kept as (lanes, values) chunks, one per replay step, and
    grouped by lane at the first read (a stable sort keeps each lane's
    order)."""

    def __init__(self, L: int, n_cols: int):
        self.L = L
        self._lanes: List[np.ndarray] = []
        self._chunks: List[List[np.ndarray]] = [[] for _ in range(n_cols)]
        self._cols: Optional[List[np.ndarray]] = None

    def add(self, lanes: np.ndarray, *cols: np.ndarray) -> None:
        self._lanes.append(lanes)
        for chunks, col in zip(self._chunks, cols):
            chunks.append(col)

    def lane(self, s: int, col: int = 0) -> np.ndarray:
        if self._cols is None:
            lanes = np.concatenate(self._lanes) if self._lanes \
                else np.zeros(0, dtype=np.int64)
            order = np.argsort(lanes, kind="stable")
            self._cols = [np.concatenate(chunks)[order] if chunks
                          else np.zeros(0) for chunks in self._chunks]
            self._bounds = np.searchsorted(lanes[order],
                                           np.arange(self.L + 1))
        return self._cols[col][self._bounds[s]:self._bounds[s + 1]]


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
        self.gaps = _Lists(L, 1)         # retry gap, minutes
        self.lost = _Lists(L, 1)         # lost work, hours
        self.downtimes = _Lists(L, 2)    # hours, automatic
        self.sess = _Lists(L, 2)         # session start, end


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
    rec_t, rec_fl = host["rec_t"], host["rec_flags"][:it_count]
    isnan = np.isnan

    def flag(f):
        """(iterations, L) mask of flag ``f``, and its rows' any."""
        m = (rec_fl & f) != 0
        return m, m.any(axis=1)

    M_START, _ = flag(F_START)
    M_AF, _ = flag(F_ALLOCFAIL)
    M_ATT = M_START | M_AF
    ANY_ATT = M_ATT.any(axis=1)
    M_POK, ANY_POK = flag(F_PREP_OK)
    M_FAIL, ANY_FAIL = flag(F_SESS_FAIL)
    M_LOST, ANY_LOST = flag(F_LOST)
    M_CC, ANY_CC = flag(F_CHAIN_CLOSE)
    M_FIN, ANY_FIN = flag(F_FINALIZE)
    M_ADV, _ = flag(F_ADVANCE)
    M_RUN = M_ADV & ((rec_fl & F_RUNNING) != 0)
    ANY_RUN = M_RUN.any(axis=1)
    ACTIVE = rec_fl.any(axis=1)
    for it in range(it_count):
        if not ACTIVE[it]:
            continue
        tn = rec_t[it]
        t = R.cur_t

        m_start, m_af, m_att = M_START[it], M_AF[it], M_ATT[it]
        if ANY_ATT[it]:
            gm = m_att & ~isnan(R.prev_end)
            if gm.any():
                idx = np.nonzero(gm)[0]
                R.gaps.add(idx, (t[idx] - R.prev_end[idx]) * 60.0)
            R.n_att[m_att] += 1
            R.prev_end[m_af] = t[m_af]
            R.prev_end[m_start] = np.nan
            R.started[m_start] = np.nan
            R.open_sess[m_start] = True

        m_pok = M_POK[it]
        if ANY_POK[it]:
            R.started[m_pok] = t[m_pok]
            R.retry_reached[m_pok & (R.n_att != 1)] = True
            R.last_ckpt[m_pok] = t[m_pok]
            R.last_save[m_pok] = t[m_pok]
            dc = m_pok & ~isnan(R.down_since)
            idx = np.nonzero(dc)[0]
            R.downtimes.add(idx, t[idx] - R.down_since[idx],
                            R.down_auto[idx])
            R.down_since[dc] = np.nan
            R.down_auto[dc] = True

        m_fail, m_lost = M_FAIL[it], M_LOST[it]
        if ANY_FAIL[it]:
            if ANY_LOST[it]:            # lost precedes the teardown fold
                idx = np.nonzero(m_lost)[0]
                R.lost.add(idx, np.minimum(t[idx] - R.last_save[idx],
                                           interval[idx]))
            rs = m_fail & ~isnan(R.started)
            R.run_sum[rs] += np.maximum(0.0, t[rs] - R.started[rs])
            idx = np.nonzero(m_fail)[0]
            R.sess.add(idx, R.started[idx], t[idx])
            R.started[m_fail] = np.nan
            R.open_sess[m_fail] = False
            R.prev_end[m_fail] = t[m_fail]
            dn = m_fail & isnan(R.down_since)
            R.down_since[dn] = t[dn]

        m_cc = M_CC[it]
        if ANY_CC[it]:
            g = m_cc & (R.n_att > 1)
            R.f4[g, 0] += 1
            R.f4[g, 1] += R.n_att[g]
            R.f4[g & R.retry_reached, 2] += 1
            R.n_att[m_cc] = 0
            R.retry_reached[m_cc] = False
            R.prev_end[m_cc] = np.nan
            R.down_auto[m_cc] = False

        m_fin = M_FIN[it]
        if ANY_FIN[it]:
            fo = m_fin & R.open_sess
            rs = fo & ~isnan(R.started)
            R.run_sum[rs] += np.maximum(0.0, duration[rs] - R.started[rs])
            idx = np.nonzero(fo)[0]
            R.sess.add(idx, R.started[idx], duration[idx])
            R.open_sess[fo] = False
            R.started[fo] = np.nan
            g = m_fin & (R.n_att > 1)
            R.f4[g, 0] += 1
            R.f4[g, 1] += R.n_att[g]
            R.f4[g & R.retry_reached, 2] += 1
            R.n_att[m_fin] = 0
            R.retry_reached[m_fin] = False

        m_run = M_RUN[it]
        if ANY_RUN[it]:
            k = np.floor((tn - R.last_ckpt + 1e-12)
                         / interval).astype(np.int64)
            k = np.where(m_run, np.maximum(k, 0), 0)
            R.ckpt_events += k
            R.last_ckpt += k * interval
            np.maximum(R.last_save, R.last_ckpt, out=R.last_save)

        R.cur_t = np.where(M_ADV[it], tn, R.cur_t)
    return R


def _degraded(tables: LaneTables, host, R: _Replay,
              lane: int) -> List[float]:
    """Per session of ``lane`` that reached RUNNING, the hours its gang
    lost to degradation windows; only these rows of the packed session
    gang masks are unpacked."""
    windows = tables.deg_windows[lane]
    if not windows:
        return []
    gang = host["se_gang"][lane]
    out: List[float] = []
    for k, (t0, t1) in enumerate(zip(R.sess.lane(lane, 0).tolist(),
                                     R.sess.lane(lane, 1).tolist())):
        if t0 != t0:                    # never reached RUNNING
            continue
        d = degraded_overlap_h(windows, t0, t1,
                               unpack_gang(gang[k], tables.n_nodes))
        if d:
            out.append(d)
    return out


def _lane_findings(tables: LaneTables, host, R: _Replay,
                   lane: int) -> dict:
    down_h = R.downtimes.lane(lane, 0)
    auto = R.downtimes.lane(lane, 1).astype(bool)
    return findings(LaneTotals(
        duration_h=float(tables.duration[lane]),
        run_h=float(R.run_sum[lane]) if tables.job_gt1[lane] else 0.0,
        lost_h=R.lost.lane(lane), ckpt_events=int(R.ckpt_events[lane]),
        save_s=float(tables.save_s[lane]), urgent_h=0.0,
        degraded_h=_degraded(tables, host, R, lane),
        n_failures=int(tables.n_failures[lane]),
        n_sessions=int(host["n_sessions"][lane]),
        excl_counts=host["npart_counts"][lane],
        n_deliberate=int(host["n_delib"][lane]),
        n_intervals=int(host["n_intervals"][lane]),
        f4=tuple(R.f4[lane].tolist()), gaps_min=R.gaps.lane(lane),
        auto_down_h=down_h[auto], manual_down_h=down_h[~auto],
        infra_n=int(tables.infra_n[lane]),
        # eligibility excludes the correlated band, so these lanes carry
        # no switch_degrade / dns_flap events by construction
        corr_n=0, switch_ids=(), control=None, drain_excl=0))


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
    drawn = []                  # (injector, duration, schedules) drawn
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
            # variants that differ only in policy share one failure
            # process, and so one draw of its schedules
            fails = next((f for inj, d, f in drawn
                          if inj == injector and d == rcfg.duration_h),
                         None)
            if fails is None:
                fails = injector.sample_batch(rcfg.duration_h, seeds)
                drawn.append((injector, rcfg.duration_h, fails))
            resolved.append((rcfg, fails))

    def build(caps_in):
        with tracing.span("grid.tapes"):
            blocks = [build_lane_tables(rcfg, fails, seeds, caps=caps_in)
                      for rcfg, fails in resolved]
            return pad_lanes_pow2(concat_lane_tables(blocks))

    if caps is None:
        caps = WavefrontCaps.sized(
            max(max_failures(fails) for _, fails in resolved))
    tables, host = _run_with_caps(build, caps, backend, interpret)
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
