"""The compiled grid's host replay (``kernels/wavefront/ops._replay``),
which folds the device record stream event by event, against the
per-iteration loop it replaced, kept here as the oracle.

Every field of the replay's result is compared with the oracle's:
arrays bit for bit, each lane's lists in order and bit for bit.  The
cases are device passes of real configurations and hand-built record
streams that put every phase of an iteration in one row.
"""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from repro.kernels.wavefront import ops
from repro.kernels.wavefront.ref import (F_ADVANCE, F_ALLOCFAIL,
                                         F_CHAIN_CLOSE, F_FINALIZE, F_LOST,
                                         F_PREP_OK, F_RUNNING, F_SESS_FAIL,
                                         F_START, F_VALID)
from repro.ops.scenario import Scenario

CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"
LIST_COLS = {"gaps": 1, "lost": 1, "downtimes": 2, "sess": 2}


# -- the oracle: the replay as one numpy step per device iteration -----------

class _OracleLists:
    """Per-lane lists appended as (lanes, values) chunks, grouped by lane
    with a stable sort at the first read."""

    def __init__(self, L, n_cols):
        self.L = L
        self._lanes = []
        self._chunks = [[] for _ in range(n_cols)]
        self._cols = None

    def add(self, lanes, *cols):
        self._lanes.append(lanes)
        for chunks, col in zip(self._chunks, cols):
            chunks.append(col)

    def lane(self, s, col=0):
        if self._cols is None:
            lanes = np.concatenate(self._lanes) if self._lanes \
                else np.zeros(0, dtype=np.int64)
            order = np.argsort(lanes, kind="stable")
            self._cols = [np.concatenate(chunks)[order] if chunks
                          else np.zeros(0) for chunks in self._chunks]
            self._bounds = np.searchsorted(lanes[order],
                                           np.arange(self.L + 1))
        return self._cols[col][self._bounds[s]:self._bounds[s + 1]]


class _OracleState:
    def __init__(self, L):
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
        self.gaps = _OracleLists(L, 1)
        self.lost = _OracleLists(L, 1)
        self.downtimes = _OracleLists(L, 2)
        self.sess = _OracleLists(L, 2)


def replay_by_iteration(tables, host):
    """The float folds along the iteration axis, one step per device
    iteration, in the numpy wavefront's step order (starts -> prep-done
    -> session fail/lost -> chain close -> finalize -> checkpoint
    catch-up)."""
    L = host["rec_t"].shape[1]
    R = _OracleState(L)
    interval = tables.interval
    duration = tables.duration
    it_count = int(host["it"])
    rec_t, rec_fl = host["rec_t"], host["rec_flags"][:it_count]
    isnan = np.isnan

    def flag(f):
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
            if ANY_LOST[it]:
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


# -- the cases ---------------------------------------------------------------

@dataclasses.dataclass
class Stream:
    """The replay's inputs without a device pass: the tables' two columns
    it reads, and a record stream."""
    interval: np.ndarray
    duration: np.ndarray


def variants(name: str, **cut) -> dict:
    """The variants of ``bench/configs/<name>.json`` as campaign configs,
    with the file's cluster and campaign applied, then ``cut``."""
    conf = json.loads((CONFIGS / f"{name}.json").read_text())
    out = {}
    for v, spec in conf["variants"].items():
        spec = dict(spec, **{k: conf[k] for k in
                             ("n_nodes", "job_nodes", "duration_days")})
        spec.update(cut)
        out[v] = Scenario.from_dict(spec).to_campaign_config(0)
    return out


def device_pass(cfgs, seeds):
    """The (tables, host) the replay of one ``run_findings_grid`` call
    reads."""
    got = []
    replay = ops._replay

    def capture(tables, host):
        got.append((tables, host))
        return replay(tables, host)
    ops._replay = capture
    try:
        ops.run_findings_grid(cfgs, seeds, backend="xla")
    finally:
        ops._replay = replay
    return got[-1]


def llama3_16k():
    """16 lanes of Llama 3's 2,176-node pool, whole 54-day campaigns:
    dense in attempt starts and session fails."""
    return device_pass(list(variants("llama3-16k").values()), range(4))


def paper_63n_infra():
    """The paper's 63-node cluster under the infra fault band
    (degradation windows, escalation crashes, fail-slow isolation) and
    three retry policies, 15 lanes padded to 16."""
    band = {"net_degrade": 8.0, "resource_exhaust": 6.0}
    cfgs = variants("paper-63n", duration_days=20.0, mtbf_h=20.0)
    cfgs = [dataclasses.replace(cfgs[v], kind_weights=band)
            for v in ("paper-faithful", "no-auto-retry", "exp-backoff")]
    tables, host = device_pass(cfgs, range(5))
    assert not tables.device["lane_on"][15]
    assert any(tables.deg_windows), "no degradation window landed"
    return tables, host


def _stream(rows, interval, duration, L):
    """A record stream from ``rows``: per row, {lane: (flags, t_next)};
    lanes left out of a row hold their clock with no flags."""
    n = len(rows)
    rec_t = np.zeros((n + 2, L))        # device buffers outlast the pass
    rec_fl = np.zeros((n + 2, L), dtype=np.int32)
    clock = np.zeros(L)
    for it, row in enumerate(rows):
        for s, (f, t) in row.items():
            rec_fl[it, s] = f | F_VALID
            clock[s] = t if f & F_ADVANCE else clock[s]
        rec_t[it] = clock
    tables = Stream(interval=np.asarray(interval, dtype=float),
                    duration=np.asarray(duration, dtype=float))
    return tables, {"rec_t": rec_t, "rec_flags": rec_fl, "it": np.int32(n)}


def hand_built():
    """Lane 0 has every phase in one row; lane 1 has no events; lane 2
    runs a retry chain through a chain close; one row has only RUN."""
    A, RUN = F_ADVANCE, F_ADVANCE | F_RUNNING
    rows = [
        {0: (F_START | A, 0.4), 1: (A, 0.5), 2: (F_ALLOCFAIL | A, 0.3)},
        {0: (F_PREP_OK | RUN, 1.1), 1: (A, 1.0), 2: (F_START | A, 0.9)},
        {0: (RUN, 3.7), 2: (F_PREP_OK | RUN, 2.5)},
        {0: (RUN, 6.05)},                                   # only RUN
        {0: (F_SESS_FAIL | F_LOST, 6.05),                   # pending
         2: (F_SESS_FAIL | F_LOST | A, 7.0)},
        {0: (F_START | F_PREP_OK | F_SESS_FAIL | F_LOST | F_CHAIN_CLOSE
             | F_FINALIZE | RUN, 9.0),
         2: (F_START | A, 7.5)},
        {2: (F_SESS_FAIL | A, 8.0)},
        {2: (F_ALLOCFAIL | F_CHAIN_CLOSE | A, 11.0)},
        {2: (F_START | A, 12.0)},
        {2: (F_PREP_OK | RUN, 13.25)},
        {2: (RUN | F_FINALIZE, 20.0)},
    ]
    return _stream(rows, [2.23, 1.0, 1.5, 1.0], [9.0, 1.0, 20.0, 1.0], 4)


def random_stream(seed):
    """Random flag combinations a device row can carry (LOST with its
    SESS_FAIL), pending rows that hold the clock, and a padding lane."""
    rng = np.random.default_rng(seed)
    L, n = 9, 300
    events = [F_START, F_ALLOCFAIL, F_PREP_OK, F_SESS_FAIL,
              F_SESS_FAIL | F_LOST, F_CHAIN_CLOSE, F_FINALIZE]
    clock = np.zeros(L)
    rows = []
    for _ in range(n):
        row = {}
        for s in range(L - 1):
            f = 0
            for e in events:
                if rng.random() < 0.12:
                    f |= e
            pending = rng.random() < 0.2
            if not pending:
                f |= F_ADVANCE
                clock[s] += rng.exponential(0.7)
            if rng.random() < 0.7:
                f |= F_RUNNING
            if f:
                row[s] = (f, clock[s])
        rows.append(row)
    interval = rng.uniform(0.3, 2.5, L)
    return _stream(rows, interval, clock + 1.0, L)


CASES = {
    "llama3-16k": llama3_16k,
    "paper-63n-infra": paper_63n_infra,
    "hand-built": hand_built,
    "random-0": lambda: random_stream(0),
    "random-1": lambda: random_stream(1),
}
_PASSES = {}


def case(name):
    if name not in _PASSES:
        _PASSES[name] = CASES[name]()
    return _PASSES[name]


def _bits(a):
    return np.asarray(a, dtype=np.float64).view(np.int64)


@pytest.mark.parametrize("name", list(CASES))
def test_replay_by_events_equals_the_iteration_loop(name):
    tables, host = case(name)
    got = ops._replay(tables, host)
    want = replay_by_iteration(tables, host)
    L = host["rec_t"].shape[1]
    fields = set(vars(ops._Replay(L)))
    assert fields == {"ckpt_events", "run_sum", "f4"} | set(LIST_COLS)
    for k in ("ckpt_events", "run_sum", "f4"):
        a, b = getattr(got, k), getattr(want, k)
        assert a.dtype == b.dtype and a.shape == b.shape, k
        assert np.array_equal(_bits(a), _bits(b)), k
    for k, n_cols in LIST_COLS.items():
        for s in range(L):
            for c in range(n_cols):
                a = getattr(got, k).lane(s, c)
                b = getattr(want, k).lane(s, c)
                assert len(a) == len(b), (k, s, c)
                if len(b):
                    assert a.dtype == b.dtype, (k, c)
                assert np.array_equal(_bits(a), _bits(b)), (k, s, c)
    # the case exercises every fold
    for k in LIST_COLS:
        assert any(len(getattr(want, k).lane(s)) for s in range(L)), k
    assert want.ckpt_events.any() and want.f4[:, 0].any()


@pytest.mark.parametrize("name", ["llama3-16k", "paper-63n-infra"])
def test_rec_t_is_the_clock_entering_each_row(name):
    """For every live lane, ``rec_t[it - 1]`` is the clock entering row
    ``it`` (0 at row 0), as the iteration loop kept it: ``rec_t`` of the
    last row whose clock advanced.  ``ref.py``'s ``_iteration`` writes
    ``rec_t[it] = t_next`` for every lane every iteration, and a pending
    iteration's ``t_next`` is the lane's clock itself."""
    tables, host = case(name)
    n = int(host["it"])
    rec_t, fl = host["rec_t"], host["rec_flags"]
    clock = np.zeros(rec_t.shape[1])
    live_cells = 0
    for it in range(n):
        live = (fl[it] & F_VALID) != 0
        entering = rec_t[it - 1] if it else np.zeros_like(clock)
        assert np.array_equal(_bits(entering[live]), _bits(clock[live])), it
        live_cells += live.sum()
        clock = np.where((fl[it] & F_ADVANCE) != 0, rec_t[it], clock)
    assert live_cells > n
