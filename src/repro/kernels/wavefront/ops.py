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
   degradation overlaps) rerun in numpy, event by event in each lane's
   order, where C-double arithmetic matches the scalar engine bit for
   bit; each lane's totals go through the one findings fold
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
device, every pass), ``grid.iterations`` (the clean pass's iteration
count), ``grid.replay_events`` (event cells the replay folds outside a
loop) and ``grid.replay_ckpt_rows`` (steps of its checkpoint catch-up
loop).  ``last_grid_pass`` keeps the last grid call's numbers whether
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

# event bits the replay folds outside its catch-up loop, and the record
# bits of a checkpoint catch-up (the clock advanced, the session RUNNING)
_ATT = F_START | F_ALLOCFAIL
_EVENTS = (_ATT | F_PREP_OK | F_SESS_FAIL | F_LOST | F_CHAIN_CLOSE
           | F_FINALIZE)
_CATCH_UP = F_ADVANCE | F_RUNNING


class _Lists:
    """Per-lane lists of values (one or more columns) in append order:
    kept as (lanes, values) chunks and grouped by lane at the first read
    (a stable sort keeps each lane's order), or built already grouped
    (``grouped``)."""

    def __init__(self, L: int, n_cols: int):
        self.L = L
        self._lanes: List[np.ndarray] = []
        self._chunks: List[List[np.ndarray]] = [[] for _ in range(n_cols)]
        self._cols: Optional[List[np.ndarray]] = None

    @classmethod
    def grouped(cls, bounds: np.ndarray, pos: np.ndarray,
                *cols: np.ndarray) -> "_Lists":
        """Lists whose values ``cols`` sit at the sorted lane-major
        positions ``pos``, lane ``s`` owning ``bounds[s]:bounds[s + 1]``."""
        out = cls(len(bounds) - 1, len(cols))
        out._cols = list(cols)
        out._bounds = np.searchsorted(pos, bounds)
        return out

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
    """What the findings fold reads of the replay, per lane: checkpoint
    events, running hours, F4's retry-chain counts and four lists."""

    def __init__(self, L: int):
        self.ckpt_events = np.zeros(L, dtype=np.int64)
        self.run_sum = np.zeros(L)
        self.f4 = np.zeros((L, 3), dtype=np.int64)
        self.gaps = _Lists(L, 1)         # retry gap, minutes
        self.lost = _Lists(L, 1)         # lost work, hours
        self.downtimes = _Lists(L, 2)    # hours, automatic
        self.sess = _Lists(L, 2)         # session start, end


def _lane_major(x: np.ndarray, tile: int = 128) -> np.ndarray:
    """``x`` (rows, lanes) as (lanes, rows) in C order, copied tile by
    tile: a whole-array transpose reads one side strided, out of cache."""
    n, L = x.shape
    out = np.empty((L, n), dtype=x.dtype)
    for r in range(0, n, tile):
        for s in range(0, L, tile):
            out[s:s + tile, r:r + tile] = x[r:r + tile, s:s + tile].T
    return out


def _last(pos: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Per query, the latest of the sorted event positions ``pos`` at or
    before it; -1 where none."""
    i = np.searchsorted(pos, q, "right") - 1
    return np.where(i >= 0, pos[i], -1) if len(pos) else np.full_like(q, -1)


def _next(pos: np.ndarray, q: np.ndarray, end: int) -> np.ndarray:
    """Per query, the first of the sorted event positions ``pos`` at or
    after it; ``end`` where none."""
    i = np.searchsorted(pos, q)
    if not len(pos):
        return np.full_like(q, end)
    return np.where(i < len(pos), pos[np.minimum(i, len(pos) - 1)], end)


def _shift(vals: np.ndarray, pos: np.ndarray, bounds: np.ndarray,
           init) -> np.ndarray:
    """Per element at the sorted lane-major positions ``pos``, the value
    of the element before it where that one is in the same lane (lane
    ``s`` owns positions ``bounds[s]:bounds[s + 1]``), else ``init``."""
    out = np.empty_like(vals)
    out[1:] = vals[:-1]
    lead = np.searchsorted(pos, bounds[:-1])
    out[lead[lead < len(pos)]] = init
    return out


def _counts(vals: np.ndarray) -> np.ndarray:
    """``c[i]``: the sum of the integer ``vals[:i]``."""
    c = np.zeros(len(vals) + 1, dtype=np.int64)
    np.cumsum(vals, out=c[1:])
    return c


def _per_lane(vals: np.ndarray, bounds: np.ndarray) -> np.ndarray:
    """Integer ``vals`` of lane-major elements summed per lane (lane
    ``s`` owns ``bounds[s]:bounds[s + 1]``)."""
    c = _counts(vals)
    return c[bounds[1:]] - c[bounds[:-1]]


def _catch_up(interval: np.ndarray, flags: np.ndarray, clock: np.ndarray,
              prep_at: np.ndarray, lost_at: np.ndarray):
    """The checkpoint catch-up, the one fold that steps (``ref.py``'s
    docstring): per lane, ``last_ckpt`` and ``last_save`` reset to the
    clock at each PREP_OK, ``last_save`` read at each LOST, and at each
    row where the lane is RUNNING at an advanced clock the scalar
    engine's catch-up.  A lane's steps depend on each other and on no
    other lane's (a lane that is not RUNNING adds 0 checkpoints), so
    step ``j`` of the loop takes every lane's ``j``-th such row.

    ``flags`` and ``clock`` are ``rec_flags`` and ``rec_t`` lane-major,
    (lanes, rows); ``prep_at`` and ``lost_at`` the lane-major positions
    of the PREP_OK and LOST events.  Returns the checkpoint events per
    lane and ``last_save`` as each LOST event sees it."""
    L, n = flags.shape
    fv, cv = flags.ravel(), clock.ravel()
    due = (fv & _CATCH_UP) == _CATCH_UP
    due[prep_at] = True
    due[lost_at] = True
    cells = np.flatnonzero(due)
    bounds = np.searchsorted(cells, np.arange(L + 1) * n)
    lane = np.repeat(np.arange(L), np.diff(bounds))
    step = np.arange(len(cells)) - bounds[lane]
    n_steps = int(step.max(initial=-1)) + 1
    tracing.count("grid.replay_ckpt_rows", n_steps)

    def by_step(vals):
        out = np.zeros((n_steps, L), dtype=vals.dtype)
        out[step, lane] = vals
        return out

    f = fv[cells]
    run = by_step((f & _CATCH_UP) == _CATCH_UP)
    prep_ok = by_step((f & F_PREP_OK) != 0)
    lost = by_step((f & F_LOST) != 0)
    entering = cv[cells - 1]            # the clock entering the row
    entering[cells == lane * n] = 0.0
    t_prep = by_step(entering)
    t_run = by_step(cv[cells])
    last_ckpt = np.zeros(L)
    last_save = np.zeros(L)
    ckpt_events = np.zeros(L, dtype=np.int64)
    saves = np.empty((n_steps, L))
    d = np.empty(L)
    k = np.empty(L, dtype=np.int64)
    for j, p, s, u in zip(range(n_steps), prep_ok.any(axis=1).tolist(),
                          lost.any(axis=1).tolist(),
                          run.any(axis=1).tolist()):
        if p:
            np.putmask(last_ckpt, prep_ok[j], t_prep[j])
            np.putmask(last_save, prep_ok[j], t_prep[j])
        if s:
            saves[j] = last_save
        if u:
            # k = floor((tn - last_ckpt + 1e-12) / interval), clamped at
            # 0 and kept on RUNNING lanes; last_ckpt += k * interval
            np.subtract(t_run[j], last_ckpt, out=d)
            d += 1e-12
            d /= interval
            np.floor(d, out=d)
            np.copyto(k, d, casting="unsafe")
            np.maximum(k, 0, out=k)
            k *= run[j]
            ckpt_events += k
            np.multiply(k, interval, out=d)
            last_ckpt += d
            np.maximum(last_save, last_ckpt, out=last_save)
    i = np.searchsorted(cells, lost_at)
    return ckpt_events, saves[step[i], lane[i]]


def _replay(tables: LaneTables, host: Dict[str, np.ndarray]) -> _Replay:
    """Rerun the float folds of the device record stream, event by event.

    Each lane's events apply in the numpy wavefront's order: by row, and
    within a row by phase (attempt start or alloc-fail -> PREP_OK ->
    session fail and its lost work -> chain close -> finalize ->
    checkpoint catch-up).  Every state a phase reads is the value the
    latest earlier event that sets it left, so it is found among the
    lane's events by position (a shift, a search, or a difference of
    cumulative counts), and the lists and running hours gather in event
    order: every float accumulation sees the scalar engine's operands in
    its order.  Only the checkpoint catch-up steps (``_catch_up``).

    An event at row ``it`` sees the clock ``rec_t[it - 1]`` (0 at row
    0): the device writes ``t_next`` for every lane every iteration, and
    on a pending one that is the lane's clock itself.  Padding lanes
    carry no events."""
    L = host["rec_t"].shape[1]
    R = _Replay(L)
    n = int(host["it"])
    # event cells in lane-major order, each lane's events in row order:
    # an event's position in this order is what the folds compare; lane
    # s owns positions bounds[s]:bounds[s + 1]
    flags = _lane_major(host["rec_flags"][:n])
    cell = np.flatnonzero((flags & _EVENTS) != 0)
    N = len(cell)
    tracing.count("grid.replay_events", N)
    f = flags.ravel()[cell]
    bounds = np.searchsorted(cell, np.arange(L + 1) * n)
    clock = _lane_major(host["rec_t"][:n])
    t = clock.ravel()[cell - 1]
    lead = bounds[:-1][np.diff(bounds) > 0]
    t[lead[cell[lead] % n == 0]] = 0.0

    def at(bits):
        return np.flatnonzero((f & bits) != 0)

    def lane_of(pos):
        return np.searchsorted(bounds, pos, side="right") - 1

    # retry gaps: an attempt reads the end of the lane's last attempt (an
    # alloc-fail or a session fail); a START or a chain close clears it
    s = at(_ATT | F_SESS_FAIL | F_CHAIN_CLOSE)
    fs = f[s]
    ends = ((fs & (F_SESS_FAIL | F_CHAIN_CLOSE)) == F_SESS_FAIL) \
        | ((fs & (_ATT | F_SESS_FAIL | F_CHAIN_CLOSE)) == F_ALLOCFAIL)
    prev_end = _shift(np.where(ends, t[s], np.nan), s, bounds, np.nan)
    g = np.flatnonzero(((fs & _ATT) != 0) & ~np.isnan(prev_end))
    gap_at = s[g]
    R.gaps = _Lists.grouped(bounds, gap_at,
                            (t[gap_at] - prev_end[g]) * 60.0)

    # attempts since the chain's last close or finalize (the chain's
    # base), read at PREP_OK (retry reached after more than one), chain
    # close and finalize (F4 counts each chain of more than one)
    r = at(F_PREP_OK | F_CHAIN_CLOSE | F_FINALIZE)
    fr, lr = f[r], lane_of(r)
    rb = np.searchsorted(r, bounds)
    is_pok = (fr & F_PREP_OK) != 0
    is_cc = (fr & F_CHAIN_CLOSE) != 0
    reset = (fr & (F_CHAIN_CLOSE | F_FINALIZE)) != 0
    j = np.arange(len(r))
    after_reset = np.maximum.accumulate(np.where(reset, j + 1, 0))
    base = np.maximum(np.concatenate(([0], after_reset[:-1])), rb[lr])
    start_of = np.where(base > rb[lr], r[base - 1] + 1, bounds[lr])
    catt = _counts((f & _ATT) != 0)
    n_att = catt[r + 1] - catt[start_of]
    crr = _counts(is_pok & (n_att != 1))
    reached = crr[j + 1] > crr[base]
    closed = reset & (n_att > 1)
    R.f4 = np.stack([_per_lane(closed, rb),
                     _per_lane(np.where(closed, n_att, 0), rb),
                     _per_lane(closed & reached, rb)], axis=1)

    # sessions: opened at START, closed by a session fail or, still open,
    # at finalize; a session's start is the PREP_OK's clock if the lane's
    # next close comes before its next START and PREP_OK
    starts, fails = at(F_START), at(F_SESS_FAIL)
    fin = r[(fr & F_FINALIZE) != 0]
    last_start = _last(starts, fin)
    open_ = (last_start >= bounds[lane_of(fin)]) \
        & (last_start > _last(fails, fin)) \
        & (last_start > _shift(fin, fin, bounds, -1))
    fin = fin[open_]
    closes = np.insert(fails, np.searchsorted(fails, fin), fin)
    ends_at = t[closes]
    ends_at[np.searchsorted(closes, fin)] = tables.duration[lane_of(fin)]
    pok = r[is_pok]
    c = np.searchsorted(closes, pok)
    nc = np.append(closes, N)[c]
    ran = (nc < bounds[lane_of(pok) + 1]) \
        & (nc < _next(starts, pok + 1, N)) & (nc < np.append(pok[1:], N))
    started = np.full(len(closes), np.nan)
    started[c[ran]] = t[pok[ran]]
    R.sess = _Lists.grouped(bounds, closes, started, ends_at)
    if ran.any():
        # running hours, summed left to right per lane from 0.0
        k = c[ran]
        terms = np.maximum(0.0, ends_at[k] - started[k])
        tl = lane_of(closes[k])
        rank = np.arange(len(k)) - np.searchsorted(tl, tl) + 1
        M = np.zeros((L, rank.max() + 1))
        M[tl, rank] = terms
        R.run_sum = np.add.accumulate(M, axis=1)[:, -1]

    # downtime: from the first session fail after the lane's last PREP_OK
    # to the next PREP_OK; automatic unless a chain closed in between
    since = _next(fails, np.maximum(_shift(pok, pok, bounds, -1),
                                    bounds[lane_of(pok)]), N)
    dc = since < pok
    dc_r = np.zeros(len(r), dtype=bool)
    dc_r[np.flatnonzero(is_pok)[dc]] = True
    a = np.flatnonzero(is_cc | dc_r)
    auto = _shift(~is_cc[a], r[a], bounds, True)[dc_r[a]]
    p = pok[dc]
    R.downtimes = _Lists.grouped(bounds, p, t[p] - t[since[dc]], auto)

    # lost work (a LOST comes with its session fail): since the last
    # save, as the catch-up leaves it
    lost = at(F_LOST)
    R.ckpt_events, saved = _catch_up(tables.interval, flags, clock,
                                     cell[r[is_pok]], cell[lost])
    R.lost = _Lists.grouped(bounds, lost, np.minimum(
        t[lost] - saved, tables.interval[lane_of(lost)]))
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
