"""Streaming precursor detection — the control plane's sensor.

``StreamingDetector`` is the incremental reformulation of
``PrecursorDetector.scan`` (paper F1 / §4.1): it consumes span-batched
telemetry *as the event engine emits it* and returns the alarms raised by
each span.  The per-tick math is unchanged — robust peer z-scores
(median/MAD across the active cohort), a multi-signal vote, and a
persistence streak — but the formulation is online:

* one vectorized numpy pass per pushed span (no full-store rescan), so the
  amortized cost of online detection equals one offline scan of the same
  window — the ``control_plane`` benchmark measures >=10x over rescanning
  the growing store at each span;
* O(n_nodes) carry state between spans: the previous tick's activity row
  (the peer cohort is "was running the SPMD workload at the previous
  scrape") and the per-node consecutive-hit streak.  Nothing else crosses
  span boundaries, which is what makes the reformulation exact;
* alarm attribution (``top_metrics``) runs as a second pass restricted to
  the alarming ticks, so the per-(tick, node) bookkeeping that dominated
  the offline scan is only paid where an alarm actually fired.

``PrecursorDetector.scan`` delegates to this class (one push of the whole
store), so the offline and online paths share one implementation and one
set of tests; the parity test asserts chunked pushes reproduce ``scan``'s
alarm list exactly.

Backends: the numpy pass above is the *parity oracle*; ``backend="xla"``
(jitted XLA) and ``backend="pallas"`` (TPU kernel) route pass 1 through
the fused `repro.kernels.robust_stats` implementation — masked peer
median/MAD, robust z, the multi-signal vote and the streak scan in one
compiled call over the stacked block.  The compiled backends must
produce the identical alarm set (same (tick, node) pairs, same streak
counts and vote totals) on all tested seeds — asserted by the backend
tier-1 tests and the ``detector_backend`` benchmark gate — so every
parity contract built on the numpy path survives a backend switch.
Attribution (pass 2) always runs host-side: it touches only the alarming
ticks.
"""
from __future__ import annotations

import warnings
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro import tracing
from repro.core.precursor import Alarm, DetectorConfig


def _nanmedian_rows(a: np.ndarray) -> np.ndarray:
    """Median over the last axis, ignoring NaNs; keepdims.

    NaNs (inactive peers) are mapped to +inf so they land past every valid
    entry; the median of the ``m`` valid values is then the midpoint pair
    of order statistics.  The cohort size ``m`` takes only a handful of
    distinct values per span (gang width, minus the occasional down node),
    so ``np.partition`` at that small ``kth`` set replaces a full sort.
    Unlike ``np.nanmedian`` (which drops into a per-row python path when
    NaNs are present) this stays fully vectorized, and it is the ONE
    median both the offline scan and the online detector evaluate — their
    parity is structural.  Partition and the sort fallback select the same
    order statistics, so results are identical either way.  All-NaN rows
    return NaN, as ``np.nanmedian`` would.
    """
    finite = ~np.isnan(a)
    m = np.maximum(finite.sum(axis=-1, keepdims=True), 1)
    k_lo, k_hi = (m - 1) // 2, m // 2
    filled = np.where(finite, a, np.inf)
    ks = np.unique(np.concatenate([k_lo.ravel(), k_hi.ravel()]))
    if len(ks) > 8:                      # pathological cohort variety
        s = np.sort(filled, axis=-1)
    else:
        s = np.partition(filled, list(ks), axis=-1)
    med = (np.take_along_axis(s, k_lo, axis=-1)
           + np.take_along_axis(s, k_hi, axis=-1)) / 2
    return np.where(finite.any(axis=-1, keepdims=True), med, np.nan)


def robust_peer_z_block(series: np.ndarray,
                        active: np.ndarray) -> np.ndarray:
    """|z| of every node vs its active peer cohort, per tick row.

    ``series``: (..., T, n_nodes) — a single metric or a stacked block of
    metrics sharing one dtype; ``active``: (T, n_nodes), broadcast over
    leading axes.  Median/MAD are computed over the active nodes of each
    row (the faulty node is <=1/N of the sample, so both are stable).
    Row-wise selection is independent of the stacking, so blocked and
    per-metric evaluation are bit-identical for a given dtype.
    """
    masked = np.where(active, series, np.nan)
    with np.errstate(all="ignore"), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        med = _nanmedian_rows(masked)
        mad = _nanmedian_rows(np.abs(masked - med))
    med = np.nan_to_num(med)
    mad = np.nan_to_num(mad)
    scale = 1.4826 * mad
    floor = np.maximum(1e-12, 1e-6 * np.maximum(np.abs(med), 1.0))
    scale = np.where(scale < 1e-12, floor, scale)
    return np.abs((series - med) / scale)


# stacked-block budget for pass 1: bounds the transient (B, T, n) buffer
_BLOCK_ELEMS = 1 << 24


def _by_dtype(values: Dict[str, np.ndarray],
              names: Sequence[str]) -> Dict[np.dtype, List[str]]:
    """Group metric names by array dtype (stacking mixed dtypes would
    upcast and change the per-metric math bit-for-bit)."""
    groups: Dict[np.dtype, List[str]] = {}
    for name in names:
        groups.setdefault(np.asarray(values[name]).dtype, []).append(name)
    return groups


def _worth_compiling(S: int, B: int, T: int, n: int) -> bool:
    """Small spans are cheaper on the numpy pass than on a device round
    trip; route them back regardless of the configured backend (the
    outputs are identical either way — this is pure size dispatch)."""
    from repro.kernels.robust_stats.ops import COMPILED_MIN_ELEMS
    return S * B * T * n >= COMPILED_MIN_ELEMS


class StreamingDetector:
    """Online multi-signal detector over span-batched telemetry.

    Feed scrape spans in order via :meth:`push`; each call returns the
    alarms whose persistence streak completed inside that span.  Pushing a
    whole store in one call is exactly the offline scan.

    ``backend`` selects the pass-1 implementation: ``"numpy"`` (the
    reference and parity oracle), ``"xla"`` (jitted XLA, fused), or
    ``"pallas"`` (TPU kernel; interpreted off-TPU, so only useful there).
    All three produce the same alarms on tested telemetry.
    """

    def __init__(self, config: Optional[DetectorConfig] = None,
                 backend: str = "numpy"):
        # NOTE: config default is constructed per instance — a shared
        # default-argument instance would alias every detector's config
        self.config = config if config is not None else DetectorConfig()
        if backend != "numpy":
            from repro.kernels.robust_stats.ops import validate_backend
            validate_backend(backend)
        self.backend = backend
        self._streak: Optional[np.ndarray] = None     # (n,) consecutive hits
        self._prev_act: Optional[np.ndarray] = None   # (1, n) last activity row
        self._tick_offset = 0                         # global tick index
        self.n_alarms = 0

    # -- state helpers ------------------------------------------------------

    def _activity(self, values: Dict[str, np.ndarray],
                  shape) -> np.ndarray:
        """Active cohort per tick: node ran the workload at the *previous*
        scrape (so the failure tick itself stays eligible).  The previous
        span's last row carries across the boundary."""
        cfg = self.config
        if cfg.activity_metric in values:
            act_now = np.asarray(values[cfg.activity_metric]) \
                > cfg.activity_threshold
            prev = self._prev_act if self._prev_act is not None \
                else act_now[:1]
            active = np.vstack([prev, act_now[:-1]])
            self._prev_act = act_now[-1:].copy()
        else:
            active = np.ones(shape, dtype=bool)
            self._prev_act = active[-1:].copy()
        return active

    # -- the one-pass-per-span core -----------------------------------------

    def _hit_pass_numpy(self, values, names, active, T, n) -> np.ndarray:
        """Pass 1, numpy oracle: multi-signal vote counts (T, n) int32.

        Metrics are stacked into (B, T, n) blocks — grouped by dtype so
        the stacked math stays bit-identical to per-metric evaluation —
        which collapses the ~300 per-metric numpy calls of a fine-grained
        online chunk into a handful.
        """
        cfg = self.config
        hit = np.zeros((T, n), dtype=np.int32)
        block_n = max(_BLOCK_ELEMS // max(T * n, 1), 1)
        for group in _by_dtype(values, names).values():
            for i in range(0, len(group), block_n):
                block = np.stack([np.asarray(values[name])
                                  for name in group[i:i + block_n]])
                z = robust_peer_z_block(block, active)
                hit += ((z > cfg.z_threshold) & active).sum(
                    axis=0, dtype=np.int32)
        return hit

    @staticmethod
    def _detect_compiled(values_list, names, active, carry, cfg, backend):
        """Pass 1 + streak scan via the fused robust_stats backend.

        ``active``: (S, T, n); ``carry``: (S, n) pre-span streaks.
        Returns (hit, streak), both (S, T, n) int32.  Metric chunks are
        stacked float32 directly (half the host footprint of a float64
        stack) under the same block budget as the numpy path — votes are
        additive across chunks, so a 300-metric offline scan never holds
        more than one chunk's block on the host — and the streak scan
        runs once on the accumulated counts.
        """
        from repro.kernels.robust_stats.ops import (BLOCK_ELEMS,
                                                    bucket_layout, hit_block,
                                                    streak_scan)
        S, T, n = active.shape
        Sp, layout = bucket_layout(S, T)
        Tp = sum(layout)
        act = np.zeros((Sp, Tp, n), dtype=bool)
        act[:S, :T] = active
        hit = np.zeros((S, T, n), dtype=np.int32)
        block_n = max(BLOCK_ELEMS // max(Sp * Tp * n, 1), 1)
        for i in range(0, len(names), block_n):
            chunk = names[i:i + block_n]
            # build straight into the bucketed buffer (see bucket_layout)
            # so the kernel layer pays no second pad copy
            block = np.zeros((Sp, len(chunk), Tp, n), dtype=np.float32)
            for s, values in enumerate(values_list):
                for b, name in enumerate(chunk):
                    block[s, b, :T] = values[name]
            hit += hit_block(block, act, z_threshold=cfg.z_threshold,
                             backend=backend, prepadded=(S, T))
        return hit, streak_scan(hit, carry, cfg.min_signals)

    def _span_streak(self, hit: np.ndarray, T: int, n: int) -> np.ndarray:
        """Persistence streak with cross-span carry, vectorized:
        streak[t] = (streak[t-1] + 1) * over[t]  ==  distance to the last
        reset row, plus the carried-in streak while no reset has occurred.
        """
        over = hit >= self.config.min_signals
        carry = self._streak if self._streak is not None \
            else np.zeros(n, dtype=np.int64)
        idx = np.arange(1, T + 1, dtype=np.int64)[:, None]
        last_reset = np.maximum.accumulate(np.where(over, 0, idx), axis=0)
        streak = np.where(over, idx - last_reset, 0)
        streak += np.where(over & (last_reset == 0), carry[None, :], 0)
        return streak

    def push(self, ts: np.ndarray,
             values: Dict[str, np.ndarray]) -> List[Alarm]:
        """Consume one telemetry span; return the alarms it raised.

        ``ts``: (T,) scrape times in hours; ``values``: metric -> (T, n)
        arrays (a ``TimeSeriesStore`` snapshot slice or an
        ``ExporterSuite.tick_batch`` output).
        """
        cfg = self.config
        ts = np.asarray(ts, dtype=float)
        names = [n for n in values if n not in cfg.exclude_metrics]
        if len(ts) == 0 or not names:
            return []
        T, n = np.asarray(values[names[0]]).shape
        with tracing.span("detector.pass1"):
            active = self._activity(values, (T, n))
            tracing.count("detector.seed_ticks", T)
            if self.backend == "numpy" or not _worth_compiling(
                    1, len(names), T, n):
                hit = self._hit_pass_numpy(values, names, active, T, n)
                streak = self._span_streak(hit, T, n)
            else:
                # fused compiled pass; the pre-span carry feeds the scan
                tracing.count("detector.compiled_seed_ticks", T)
                carry = np.zeros((1, n), dtype=np.int32) \
                    if self._streak is None \
                    else self._streak[None].astype(np.int32)
                with tracing.span("detector.device"):
                    hit, streak = self._detect_compiled(
                        [values], names, active[None], carry, cfg,
                        self.backend)
                hit, streak = hit[0], streak[0]
            self._streak = streak[-1].copy()

        rows, nodes = np.nonzero(streak == cfg.persistence)
        if len(rows) == 0:
            self._tick_offset += T
            return []

        with tracing.span("detector.attribute"):
            alarms = self._attribute(ts, values, names, active, hit, rows,
                                     nodes)
        self._tick_offset += T
        self.n_alarms += len(alarms)
        return alarms

    def _attribute(self, ts, values, names, active, hit,
                   rows, nodes) -> List[Alarm]:
        """Pass 2: per-alarm metric attribution, restricted to the alarming
        ticks — recompute z on just those rows (row-sliced median/MAD is
        bit-identical).

        All alarming ticks are scored at once: metrics stack into
        (B, U, n) blocks (dtype-grouped, like pass 1) so one
        `robust_peer_z_block` call covers a whole group instead of one
        call per metric.  Candidate lists are still assembled in ``names``
        order, so the stable sort ties break exactly as the per-metric
        loop broke them.
        """
        cfg = self.config
        urows = np.unique(rows)
        pos = {int(r): i for i, r in enumerate(urows)}
        sub_active = active[urows]
        U, n = sub_active.shape

        # stacked z for every metric on just the alarming ticks, gathered
        # down to one (B, n_alarms) column matrix in metric-name order
        zcols = np.empty((len(names), len(rows)))
        arows = np.array([pos[int(r)] for r in rows])
        order = {name: b for b, name in enumerate(names)}
        block_n = max(_BLOCK_ELEMS // max(U * n, 1), 1)
        for group in _by_dtype(values, names).values():
            for i in range(0, len(group), block_n):
                chunk = group[i:i + block_n]
                block = np.stack([np.asarray(values[name])[urows]
                                  for name in chunk])
                z = robust_peer_z_block(block, sub_active)
                rows_idx = [order[name] for name in chunk]
                zcols[rows_idx] = z[:, arows, nodes]

        exceed = zcols > cfg.z_threshold
        exceed &= sub_active[arows, nodes][None, :]
        alarms = []
        for j, (r, node) in enumerate(zip(rows, nodes)):
            cand = np.nonzero(exceed[:, j])[0]
            # stable argsort on -z ties in metric-name order, exactly as
            # the per-metric append + stable sort resolved them
            best = cand[np.argsort(-zcols[cand, j], kind="stable")[:5]]
            metrics = [(names[b], float(zcols[b, j])) for b in best]
            alarms.append(Alarm(tick=self._tick_offset + int(r),
                                time_h=float(ts[r]), node=int(node),
                                n_signals=int(hit[r, node]),
                                top_metrics=metrics))
        return alarms

    # -- leading-seed-axis form (the batched campaign engine's path) ---------

    @classmethod
    def push_group(cls, detectors: "Sequence[StreamingDetector]",
                   ts_list: Sequence[np.ndarray],
                   values_list: Sequence[Dict[str, np.ndarray]],
                   ) -> List[List[Alarm]]:
        """Push S same-shape spans through S detectors in one stacked pass.

        ``values_list[i]`` is detector ``i``'s span (metric -> (T, n)); all
        spans must share (T, n) and the metric vocabulary — their tick
        *times* may differ (the z math never reads ``ts``; per-seed times
        only label the alarms).  Metrics are stacked to (S, B, T, n) blocks
        for pass 1, so a group of seeds costs one set of numpy calls
        instead of S.  Every per-element operation is independent of the
        stacking (`robust_peer_z_block` broadcasts over leading axes and
        selects medians row-wise), so each detector's alarms, carry state
        (activity row, streak) and tick offset advance bit-identically to
        S scalar ``push`` calls — the batched campaign engine's parity
        contract leans on exactly this.
        """
        S = len(detectors)
        if S == 1:
            return [detectors[0].push(ts_list[0], values_list[0])]
        cfg = detectors[0].config
        if any(d.config is not cfg and d.config != cfg for d in detectors):
            raise ValueError("push_group requires a shared DetectorConfig")
        backend = detectors[0].backend
        if any(d.backend != backend for d in detectors):
            raise ValueError("push_group requires a shared backend")
        names = [n for n in values_list[0] if n not in cfg.exclude_metrics]
        if len(ts_list[0]) == 0 or not names:
            return [d.push(t, v) for d, t, v in
                    zip(detectors, ts_list, values_list)]
        T, n = np.asarray(values_list[0][names[0]]).shape
        with tracing.span("detector.pass1"):
            active, hit, streak = cls._pass1_group(
                detectors, values_list, names, cfg, backend, S, T, n)

        out: List[List[Alarm]] = []
        with tracing.span("detector.attribute"):
            for i, d in enumerate(detectors):
                d._streak = streak[i, -1].copy()
                rows, nodes = np.nonzero(streak[i] == cfg.persistence)
                alarms = [] if len(rows) == 0 else d._attribute(
                    ts_list[i], values_list[i], names, active[i], hit[i],
                    rows, nodes)
                d._tick_offset += T
                d.n_alarms += len(alarms)
                out.append(alarms)
        return out

    @classmethod
    def _pass1_group(cls, detectors, values_list, names, cfg, backend,
                     S: int, T: int, n: int):
        """Activity, pass 1 and the streak scan of ``push_group``:
        (active, hit, streak), each (S, T, n)."""
        # activity with per-detector carry, stacked to (S, T, n)
        if cfg.activity_metric in values_list[0]:
            act_now = np.stack(
                [np.asarray(v[cfg.activity_metric]) > cfg.activity_threshold
                 for v in values_list])
            prev = np.stack(
                [d._prev_act if d._prev_act is not None else act_now[i, :1]
                 for i, d in enumerate(detectors)])
            active = np.concatenate([prev, act_now[:, :-1]], axis=1)
            for i, d in enumerate(detectors):
                d._prev_act = act_now[i, -1:].copy()
        else:
            active = np.ones((S, T, n), dtype=bool)
            for d in detectors:
                d._prev_act = active[0, -1:].copy()

        tracing.count("detector.seed_ticks", S * T)
        if backend == "numpy" or not _worth_compiling(S, len(names), T, n):
            # pass 1 on (S, B, T, n) blocks; same per-seed dtype grouping
            # and block budget as the scalar path (the grouping never
            # changes the per-metric math, only how many numpy calls)
            hit = np.zeros((S, T, n), dtype=np.int32)
            block_n = max(_BLOCK_ELEMS // max(T * n, 1), 1)
            act_b = active[:, None]               # (S, 1, T, n)
            for group in _by_dtype(values_list[0], names).values():
                for i in range(0, len(group), block_n):
                    block = np.stack(
                        [[np.asarray(v[name])
                          for name in group[i:i + block_n]]
                         for v in values_list])   # (S, B, T, n)
                    z = robust_peer_z_block(block, act_b)
                    hit += ((z > cfg.z_threshold) & act_b).sum(
                        axis=1, dtype=np.int32)

            # streak with per-detector carry, vectorized over the seed axis
            over = hit >= cfg.min_signals
            carry = np.stack(
                [d._streak if d._streak is not None
                 else np.zeros(n, dtype=np.int64) for d in detectors])
            idx = np.arange(1, T + 1, dtype=np.int64)[None, :, None]
            last_reset = np.maximum.accumulate(np.where(over, 0, idx),
                                               axis=1)
            streak = np.where(over, idx - last_reset, 0)
            streak += np.where(over & (last_reset == 0),
                               carry[:, None, :], 0)
        else:
            tracing.count("detector.compiled_seed_ticks", S * T)
            carry = np.stack(
                [d._streak.astype(np.int32) if d._streak is not None
                 else np.zeros(n, dtype=np.int32) for d in detectors])
            with tracing.span("detector.device"):
                hit, streak = cls._detect_compiled(
                    values_list, names, active, carry, cfg, backend)
        return active, hit, streak
