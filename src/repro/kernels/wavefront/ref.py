"""Jitted XLA core of the whole-campaign wavefront.

One ``lax.while_loop`` advances every lane (seed x scenario config) of a
campaign batch to its own next event per iteration, over fixed-size
struct-of-arrays state: per-lane clocks, pool/repair masks, gang
assignments, and the tape pointers into the pre-materialized draw tapes
(``tapes.py``).  The loop mirrors the numpy wavefront's step order
(repairs, attempt starts, PREPARING completions, failures, escalation
crashes, horizon) with one deliberate difference: the numpy engine
drains *all* same-time failures per seed in an inner python loop, the
device processes **at most one kill event per lane per iteration** and
holds the lane's clock (a "pending" iteration) until the queue at that
instant drains — same event order, one extra iteration per queued event.

Bitwise discipline (the parity contract with ``ClusterSim``): every
float the loop touches travels as the int64 bit pattern of its double.
On a TPU v5e a host double held as a device f64 comes back with its
low mantissa bits changed (up to 8 ulp; only doubles that are the sum
of two f32 survive), and f64 division is coarser still, so no double is
stored or computed as f64 here.  On non-negative doubles (every clock, delay and
probability here) the bit patterns order exactly as the values do, so
compares, min/max, gathers and selects are integer ops, and the one
arithmetic op the loop needs, a lone add (``pend = t + delay``), is an
exact round-half-even integer routine (`f64_add`).  All multiply-adds
live in the host tapes/tables.  Float accounting folds (checkpoint
catch-up, lost work, run-hours, downtime) do not happen here at all: the
device emits a per-iteration record stream — ``(rec_t, rec_flags)`` with
the event bits below — plus integer accumulators, and the host *replay*
(``ops.py``) reruns the folds in numpy, where double arithmetic matches
the scalar engine exactly.  Where some lane of the block has
degradation windows, the device also records each session's gang, bit
packed (``pack_gang``), for the replay's degraded-hours ledger; a block
with none carries no per-session state at all.

The checkpoint catch-up in particular cannot be split across device
iterations (``c + k1*i`` then ``+ k2*i`` differs bitwise from
``c + (k1+k2)*i``), which is why pending iterations clear ``F_ADVANCE``:
the replay folds once per *visited* time, exactly like the numpy pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["wavefront_core", "f64_add", "f64_night", "pack_gang",
           "unpack_gang", "F_VALID",
           "F_ADVANCE", "F_RUNNING", "F_START", "F_ALLOCFAIL", "F_PREP_OK",
           "F_SESS_FAIL", "F_LOST", "F_CHAIN_CLOSE", "F_FINALIZE"]

# rec_flags bits (replayed host-side in this order within an iteration)
F_VALID = 1          # lane alive this iteration
F_ADVANCE = 2        # clock advanced to rec_t (catch-up folds once)
F_RUNNING = 4        # session RUNNING at span end (catch-up applies)
F_START = 8          # attempt started (session opened)
F_ALLOCFAIL = 16     # attempt could not allocate a gang
F_PREP_OK = 32       # PREPARING completed -> RUNNING
F_SESS_FAIL = 64     # open session failed at this time
F_LOST = 128         # lost-work event (RUNNING session was killed)
F_CHAIN_CLOSE = 256  # retry chain closed (manual-intervention branch)
F_FINALIZE = 512     # campaign end reached

_ORD_MAX = jnp.iinfo(jnp.int32).max


# -- doubles as int64 bit patterns ------------------------------------------

def _bits(x: float) -> int:
    return int(np.float64(x).view(np.int64))


_EPS = _bits(1e-12)
_INF = _bits(np.inf)
_NAN = _bits(np.nan)
_ONE = _bits(1.0)
_NEG_ONE = _bits(-1.0)    # struct_until's "never": below every clock
_MANT = (1 << 52) - 1
_IMPL = 1 << 52
_EXP_ALL = 0x7FF << 52


def _finite(x):
    return (x & _EXP_ALL) != _EXP_ALL


def f64_add(a, b):
    """IEEE-754 ``a + b`` rounded half to even, on int64 bit patterns of
    non-negative doubles (+inf absorbs; NaN operands are never read)."""
    hi, lo = jnp.maximum(a, b), jnp.minimum(a, b)
    e_hi, e_lo = hi >> 52, lo >> 52
    # mantissas with the implicit bit and 3 guard/round/sticky bits;
    # subnormals share exponent 1 without the implicit bit
    m_hi = jnp.where(e_hi > 0, (hi & _MANT) | _IMPL, hi & _MANT) << 3
    m_lo = jnp.where(e_lo > 0, (lo & _MANT) | _IMPL, lo & _MANT) << 3
    e_hi = jnp.maximum(e_hi, 1)
    d = jnp.minimum(e_hi - jnp.maximum(e_lo, 1), 63)
    aligned = lax.shift_right_logical(m_lo, d)
    sticky = (aligned << d) != m_lo
    s = m_hi + (aligned | sticky.astype(aligned.dtype))
    carry = s >> 56
    s = jnp.where(carry > 0, (s >> 1) | (s & 1), s)
    e = e_hi + carry
    grs = s & 7
    s = s >> 3
    s = s + ((grs > 4) | ((grs == 4) & ((s & 1) == 1)))
    ovf = s >> 53
    s = jnp.where(ovf > 0, s >> 1, s)
    e = jnp.where(s < _IMPL, 0, e + ovf)
    out = jnp.where(e >= 0x7FF, _INF, (e << 52) | (s & _MANT))
    return jnp.where((hi >= _EXP_ALL) | (lo == 0), hi, out)


def f64_night(t):
    """The operator's off-hours test of ``_manual_delay`` — ``day >= 5 or
    hour < 8 or hour > 20`` with ``hour = t % 24`` and ``day = (t // 24)
    % 7`` — exact on the bit pattern of a non-negative clock ``t``."""
    shift = jnp.clip(1075 - (t >> 52), 0, 63)
    m = (t & _MANT) | _IMPL
    whole = t >= _ONE
    ip = jnp.where(whole, lax.shift_right_logical(m, shift), 0)
    frac = jnp.where(whole, (ip << shift) != m, t != 0)
    ip = jnp.minimum(ip, _ORD_MAX).astype(jnp.int32)
    hour, day = ip % 24, (ip // 24) % 7
    return (day >= 5) | (hour < 8) | (hour > 20) | ((hour == 20) & frac)


def pack_gang(m):
    """``(L, n)`` bool -> ``(L, ceil(n / 32))`` uint32: node ``j`` is bit
    ``j % 32`` of word ``j // 32``."""
    L, n = m.shape
    W = -(-n // 32)
    bits = jnp.pad(m, ((0, 0), (0, 32 * W - n))).reshape(L, W, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(bits.astype(jnp.uint32) << shifts, axis=2,
                   dtype=jnp.uint32)


def unpack_gang(words: np.ndarray, n: int) -> np.ndarray:
    """One packed gang row (``(ceil(n / 32),)`` uint32, from
    :func:`pack_gang`) as an ``(n,)`` bool row, on the host."""
    b = np.ascontiguousarray(words, dtype="<u4").view(np.uint8)
    return np.unpackbits(b, bitorder="little")[:n].astype(bool)


# -- tape reads: once an iteration, only what it can use -------------------
#
# On the chip a gather costs about the same for each element it fetches,
# whatever the table, and the loop body is nearly all gathers; so each
# iteration fetches, at the pointers' values on entry, just the elements
# its steps can use, each once, and every read of a pointer picks from
# them.  The four ``_sched_next`` calls of an iteration have masks that
# exclude each other (alloc-fail, PREPARING failure, gang hit, escalation
# crash: each of the later ones needs a session the earlier ones close or
# never open), so in one iteration a lane moves
#
# * ``u_ptr`` by at most 5: an opened session's transient roll and load
#   duration, then a gang hit's or an escalation crash's software roll
#   and ``_sched_next``'s notice and misfix rolls.  An alloc-fail takes
#   the readmit roll and ``_sched_next``'s two and opens nothing.  Rolls
#   read offsets 0 to 4, the load duration 0 or 1;
# * ``m_ptr`` by at most 1, read before it moves;
# * ``x_ptr`` by at most 2 (a software follow-on, then the misfix
#   horizon): ``x_full`` is read at offset 0, ``x_half`` at 0 or 1.
#
# ``READ`` holds these counts: every read of a pointer in an iteration
# sits at an offset below its count.  The rolls need only ``u < p`` for
# the lane's five probabilities (``_PROBS``), so the uniforms cross as
# bits: entry ``p`` of the ``ubits`` table packs the comparisons of
# positions ``p`` to ``p + READ["u_ptr"] - 1``, and one int32 a lane
# serves every roll.  ``MARGIN`` holds the cap sentries' margins, at
# least ``READ``: a lane is halted once its pointer passes its tape's
# length less the margin, so an alive lane reads inside its tapes (reads
# past a table's end clip, and only halted lanes make them).
#
# ``fail_ptr`` and ``esc_ptr`` move by at most 1.  The loop carries the
# row each points at (``f_*``, ``e_*``), fetches the failure row after it
# each iteration, and the escalation row only in an iteration where some
# lane's escalation came due.
READ = {"u_ptr": 5, "m_ptr": 1, "x_ptr": 2}
MARGIN = {"u_ptr": 8, "m_ptr": 4, "x_ptr": 4}
# the capped pointers and the tape whose length caps each
CAPPED = {"u_ptr": "u", "m_ptr": "man_day", "x_ptr": "x_half"}
_PROBS = ("p_readmit", "p_transient", "p_soft", "notice_p", "p_misfix")


def _rows(*tabs):
    """(L, N) tables -> one (k * N, L) table, lane-minor: position p of
    the j-th table at row k * p + j."""
    return jnp.stack([t.T for t in tabs], axis=1).reshape(
        -1, tabs[0].shape[0])


def _tape_tables(P):
    """The tables the loop reads, built on the device once a pass, each
    lane-minor (a gather that returns rows of lanes is the cheaper form
    on the chip)."""
    nb, w = len(_PROBS), READ["u_ptr"]
    below = sum((P["u"] < P[p][:, None]).astype(jnp.int32) << k
                for k, p in enumerate(_PROBS))
    U = below.shape[1]
    below = jnp.pad(below, ((0, 0), (0, w - 1)))
    small = P["fnode"] | (P["fkcode"] << 16) \
        | (P["fhw"].astype(jnp.int32) << 20) \
        | (P["fhas_xid"].astype(jnp.int32) << 21)
    return {
        "ubits": _rows(sum(below[:, j:j + U] << (nb * j)
                           for j in range(w))),
        "dur": _rows(jnp.concatenate([P["dur_fail"], P["dur_warm"],
                                      P["dur_cold"]], axis=1)),
        "man": _rows(P["man_day"], P["man_night"]),
        "x": _rows(P["x_half"], P["x_full"]),
        "ftd": _rows(P["ft"], P["fdelay"]),
        "fsmall": _rows(small),
        "et": _rows(P["et"]), "enode": _rows(P["enode"]),
    }


def _take(tab, row, n=1):
    """Rows ``row[l]`` to ``row[l] + n - 1`` of lane ``l``'s column of a
    lane-minor table, as ``(n, L)``; rows clipped to the table."""
    idx = row[None, :] + jnp.arange(n, dtype=row.dtype)[:, None]
    return jnp.take_along_axis(tab, idx, axis=0, mode="clip")


def _fail_row(T, ptr):
    """The failure row at ``ptr``: its time, XID delay and packed small
    fields."""
    ftd = _take(T["ftd"], 2 * ptr, 2)
    return {"f_t": ftd[0], "f_delay": ftd[1],
            "f_small": _take(T["fsmall"], ptr)[0]}


def _esc_row(T, ptr):
    """The escalation row at ``ptr``: its crash time and node."""
    return {"e_t": _take(T["et"], ptr)[0],
            "e_node": _take(T["enode"], ptr)[0]}


def _unpack_small(s):
    """(node, kind code, hardware, has XID) of a packed failure row."""
    return s & 0xFFFF, (s >> 16) & 0xF, (s >> 20) & 1 != 0, \
        (s >> 21) & 1 != 0


class _Reads:
    """One iteration's tape reads, fetched at the pointers' values on
    entry (see ``READ``)."""

    def __init__(self, T, st, night):
        self.T = T
        self.p0 = {k: st[k] for k in READ}
        self.ubits = _take(T["ubits"], st["u_ptr"])[0]
        # this iteration's one possible manual draw, day or night as the
        # clock gives it
        self.man = _take(T["man"], 2 * st["m_ptr"] + night)[0]
        self.x = _take(T["x"], 2 * st["x_ptr"], 2 * READ["x_ptr"] - 1)
        # a software follow-on reads x_full before x_ptr moves
        self.x_full = self.x[1]
        self.next_fail = _fail_row(T, st["fail_ptr"] + 1)

    def below(self, st, prob):
        """``u < P[prob]`` for the uniform at the current ``u_ptr``."""
        j = st["u_ptr"] - self.p0["u_ptr"]
        return (self.ubits >> (len(_PROBS) * j + _PROBS.index(prob))) & 1 \
            != 0

    def dur(self, st, col):
        """The load duration at the current ``u_ptr``: ``col`` 0 a failed
        load, 1 warm, 2 cold."""
        U = self.T["dur"].shape[0] // 3
        return _take(self.T["dur"], col * U + st["u_ptr"])[0]

    def x_half(self, st):
        first = st["x_ptr"] == self.p0["x_ptr"]
        return jnp.where(first, self.x[0], self.x[2])


def _pick_col(tab, idx):
    """tab[(l, idx[l])], the index clipped to the columns: a one-hot
    select over the (few) columns."""
    hot = lax.broadcasted_iota(jnp.int32, tab.shape, 1) \
        == jnp.clip(idx, 0, tab.shape[1] - 1)[:, None]
    return jnp.sum(jnp.where(hot, tab, 0), axis=1, dtype=tab.dtype)


def _gang_select_xla(free, job):
    csum = jnp.cumsum(free.astype(jnp.int32), axis=1)
    return free & (csum <= job[:, None])


def _gang_select(free, job, backend: str, interpret: bool):
    if backend == "pallas":
        from repro.kernels.wavefront.kernel import gang_select_pallas
        return gang_select_pallas(free, job, interpret=interpret)
    return _gang_select_xla(free, job)


def _record_close(st, P, mask):
    """Exclusion-tracker accounting for sessions closing now (integer
    only: non-participant counts, interval counts, deliberate counts)."""
    n = st["in_gang"].shape[1]
    out = ~st["in_gang"] & mask[:, None]
    st["npart_counts"] = st["npart_counts"] + out.astype(jnp.int32)
    st["n_intervals"] = st["n_intervals"] + jnp.where(
        mask, n - P["job"], 0)
    delib = jnp.sum((st["iso_reason"] > 0) & ~st["in_gang"], axis=1,
                    dtype=jnp.int32)
    st["n_delib"] = st["n_delib"] + jnp.where(mask, delib, 0)
    return st


def _fail_session(st, flags, P, mask, hw_new):
    st["last_hw"] = jnp.where(mask, hw_new, st["last_hw"])
    st = _record_close(st, P, mask)
    flags = flags | jnp.where(mask, F_SESS_FAIL, 0)
    st["cur_on"] = st["cur_on"] & ~mask
    return st, flags


def _sched_next(st, flags, P, rd, mask, t, evt_delay_h, evt_has_xid,
                structural: bool):
    """Vector form of ``_schedule_next``: retry-vs-manual decision and
    the next pending-start time, with the exact scalar draw discipline
    (noticed roll consumed iff attempt count >= 3; misfix roll always
    consumed on the manual branch; delays pre-divided so the device adds
    once)."""
    n_att = st["n_att"]
    roll = mask & (n_att >= 3)
    noticed = roll & rd.below(st, "notice_p")
    st["u_ptr"] = st["u_ptr"] + roll
    if structural:
        noticed = noticed | (mask & P["struct_stop"])
    dna_d = _pick_col(P["dna"], n_att)
    delay = jnp.where(P["policy_xid"] & evt_has_xid, evt_delay_h, dna_d)
    retry = mask & P["retry_on"] & _finite(delay) \
        & (n_att < P["max_r"]) & ~noticed
    st["pend"] = jnp.where(retry, f64_add(t, delay), st["pend"])

    man = mask & ~retry
    # manual-intervention branch: chain closes, operator responds with a
    # day/night exponential delay, and a misfixed root cause may extend
    # the structural-failure horizon
    md = rd.man
    st["m_ptr"] = st["m_ptr"] + man
    pend_man = f64_add(t, md)
    st["pend"] = jnp.where(man, pend_man, st["pend"])
    mis = man & rd.below(st, "p_misfix")
    st["u_ptr"] = st["u_ptr"] + man
    xh = rd.x_half(st)
    st["x_ptr"] = st["x_ptr"] + mis
    su = st["struct_until"]
    st["struct_until"] = jnp.where(
        mis, jnp.maximum(su, f64_add(pend_man, xh)),
        jnp.where(man, jnp.minimum(su, pend_man), su))
    st["n_att"] = jnp.where(man, 0, st["n_att"])
    flags = flags | jnp.where(man, F_CHAIN_CLOSE, 0)
    return st, flags


def _iteration(st, P, T, backend: str, interpret: bool):
    t = st["t"]
    rd = _Reads(T, st, f64_night(t))
    alive = st["alive"]
    L, n = st["healthy"].shape
    iota_n = lax.broadcasted_iota(jnp.int32, (L, n), 1)
    zero_b = jnp.zeros(L, dtype=bool)
    nan_v = jnp.full(L, _NAN, dtype=t.dtype)
    flags = jnp.zeros(L, dtype=jnp.int32)
    t_eps = f64_add(t, _EPS)            # "due now" tolerance

    # 1. repairs due (node returns, isolation entry cleared)
    rep_act = (st["repair"] <= t[:, None]) & alive[:, None]
    st["healthy"] = st["healthy"] | rep_act
    st["excl"] = st["excl"] & ~rep_act
    st["iso_reason"] = jnp.where(rep_act, 0, st["iso_reason"])
    st["iso_order"] = jnp.where(rep_act, _ORD_MAX, st["iso_order"])
    st["repair"] = jnp.where(rep_act, _INF, st["repair"])

    # 3. pending attempt starts
    free = st["healthy"] & ~st["excl"]
    counts = jnp.sum(free, axis=1, dtype=jnp.int32)
    due_start = alive & ~st["cur_on"] & (st["pend"] <= t)
    feasible = counts >= P["job"]
    okm = due_start & feasible
    afail = due_start & ~feasible
    chosen = _gang_select(free, P["job"], backend, interpret)

    # alloc-fail: pressure-readmit roll over the isolation list (dict
    # insertion order == smallest iso_order among still-unhealthy-free
    # candidates), then attempt bookkeeping and structural reschedule
    cand = (st["iso_reason"] > 0) & st["healthy"]
    has_cand = afail & jnp.any(cand, axis=1)
    readmit = has_cand & rd.below(st, "p_readmit")
    st["u_ptr"] = st["u_ptr"] + has_cand
    ordm = jnp.where(cand, st["iso_order"], _ORD_MAX)
    rm_node = jnp.argmin(ordm, axis=1).astype(jnp.int32)
    rm = readmit[:, None] & (iota_n == rm_node[:, None])
    st["excl"] = st["excl"] & ~rm
    st["healthy"] = st["healthy"] | rm
    st["repair"] = jnp.where(rm, _INF, st["repair"])
    st["iso_reason"] = jnp.where(rm, 0, st["iso_reason"])
    st["iso_order"] = jnp.where(rm, _ORD_MAX, st["iso_order"])

    st["n_att"] = st["n_att"] + due_start.astype(jnp.int32)
    flags = flags | jnp.where(afail, F_ALLOCFAIL, 0)
    st, flags = _sched_next(st, flags, P, rd, afail, t, nan_v, zero_b,
                            True)

    # gang-feasible: open the session (and record its packed gang where
    # the block carries session gang masks)
    st["in_gang"] = jnp.where(okm[:, None], chosen, st["in_gang"])
    if "se_gang" in st:
        NS = st["se_gang"].shape[1]
        sidx = jnp.where(okm, jnp.clip(st["n_sessions"], 0, NS - 1), NS)
        st["se_gang"] = st["se_gang"].at[jnp.arange(L), sidx].set(
            pack_gang(chosen), mode="drop")
    st["n_sessions"] = st["n_sessions"] + okm.astype(jnp.int32)
    flags = flags | jnp.where(okm, F_START, 0)
    # transient-retry roll + pre-transformed load-duration draw
    pf_pre = t < st["struct_until"]
    roll_tr = okm & ~pf_pre & ((st["n_att"] == 2) | (st["n_att"] == 3))
    trans = roll_tr & rd.below(st, "p_transient")
    st["u_ptr"] = st["u_ptr"] + roll_tr
    pf = pf_pre | trans
    dur = rd.dur(st, jnp.where(pf, 0, jnp.where(st["last_hw"], 2, 1)))
    st["u_ptr"] = st["u_ptr"] + okm
    st["prep_until"] = jnp.where(okm, f64_add(t, dur), st["prep_until"])
    st["prep_fails"] = jnp.where(okm, pf, st["prep_fails"])
    st["cur_on"] = st["cur_on"] | okm
    st["cur_run"] = st["cur_run"] & ~okm
    st["pend"] = jnp.where(okm, _INF, st["pend"])

    # 4. PREPARING completions (incl. sessions opened this iteration
    # whose load duration underruns — the numpy step order does the same)
    due_prep = alive & st["cur_on"] & ~st["cur_run"] \
        & (t >= st["prep_until"])
    pok = due_prep & ~st["prep_fails"]
    pfail = due_prep & st["prep_fails"]
    st["cur_run"] = st["cur_run"] | pok
    flags = flags | jnp.where(pok, F_PREP_OK, 0)
    st, flags = _fail_session(st, flags, P, pfail, zero_b)
    st, flags = _sched_next(st, flags, P, rd, pfail, t, nan_v, zero_b,
                            False)

    # 5. at most one failure event per lane per iteration
    nf = st["f_t"]
    fdue = alive & (nf <= t_eps)
    fnode, fk, fhw, fhx = _unpack_small(st["f_small"])
    fdel = st["f_delay"]
    node_m = iota_n == fnode[:, None]
    # fail_slow: deliberate perf-degradation isolation (overwrite keeps
    # dict insertion order; a fresh key takes the next order counter)
    sm = (fdue & (fk == 2))[:, None] & node_m
    newly = sm & (st["iso_reason"] == 0)
    st["iso_order"] = jnp.where(newly, st["iso_ctr"][:, None],
                                st["iso_order"])
    st["iso_ctr"] = st["iso_ctr"] + jnp.any(newly, axis=1)
    st["iso_reason"] = jnp.where(sm, 1, st["iso_reason"])
    st["excl"] = st["excl"] | sm
    st["repair"] = jnp.where(
        sm, f64_add(t, P["slow_iso_h"])[:, None], st["repair"])
    # hardware kills: node down + repair timer + setdefault isolation
    m_kill = fdue & (fk <= 1)
    hm = (m_kill & fhw)[:, None] & node_m
    st["healthy"] = st["healthy"] & ~hm
    st["repair"] = jnp.where(
        hm, f64_add(t, P["repair_h"])[:, None], st["repair"])
    newly2 = hm & (st["iso_reason"] == 0)
    st["iso_order"] = jnp.where(newly2, st["iso_ctr"][:, None],
                                st["iso_order"])
    st["iso_ctr"] = st["iso_ctr"] + jnp.any(newly2, axis=1)
    st["iso_reason"] = jnp.where(newly2, 2, st["iso_reason"])
    # gang hit: lost work (if RUNNING), software roll, session teardown
    hit = jnp.any(st["in_gang"] & node_m, axis=1)
    ghit = m_kill & st["cur_on"] & hit
    flags = flags | jnp.where(ghit & st["cur_run"], F_LOST, 0)
    soft = ghit & rd.below(st, "p_soft")
    st["u_ptr"] = st["u_ptr"] + ghit
    xf = rd.x_full
    st["struct_until"] = jnp.where(
        soft, jnp.maximum(st["struct_until"], f64_add(t, xf)),
        st["struct_until"])
    st["x_ptr"] = st["x_ptr"] + soft
    st, flags = _fail_session(st, flags, P, ghit, fhw)
    st, flags = _sched_next(st, flags, P, rd, ghit, t, fdel, fhx, False)
    st["fail_ptr"] = st["fail_ptr"] + fdue
    for k, v in rd.next_fail.items():
        st[k] = jnp.where(fdue, v, st[k])

    # 5b. escalation crash, only once the failure queue at t has drained
    # (the numpy loop processes failures then escalations per iteration)
    nf2 = st["f_t"]
    ne = st["e_t"]
    edue = alive & (ne <= t_eps) & ~(nf2 <= t_eps)
    en = st["e_node"]
    ehit_node = jnp.any(st["in_gang"] & (iota_n == en[:, None]), axis=1)
    ehit = edue & st["cur_on"] & ehit_node
    flags = flags | jnp.where(ehit & st["cur_run"], F_LOST, 0)
    soft2 = ehit & rd.below(st, "p_soft")
    st["u_ptr"] = st["u_ptr"] + ehit
    xf2 = rd.x_full
    st["struct_until"] = jnp.where(
        soft2, jnp.maximum(st["struct_until"], f64_add(t, xf2)),
        st["struct_until"])
    st["x_ptr"] = st["x_ptr"] + soft2
    st, flags = _fail_session(st, flags, P, ehit, zero_b)
    st, flags = _sched_next(st, flags, P, rd, ehit, t, nan_v, zero_b,
                            False)
    st["esc_ptr"] = st["esc_ptr"] + edue
    esc = {k: st[k] for k in ("e_t", "e_node")}
    st.update(lax.cond(jnp.any(edue), lambda: _esc_row(T, st["esc_ptr"]),
                       lambda: esc))
    ne2 = st["e_t"]

    # 6. next-event horizon (same-time candidates mask to +inf; the
    # duration term keeps the min finite, exactly the numpy fallback)
    c_pend = jnp.where(st["cur_on"], _INF, st["pend"])
    c_prep = jnp.where(st["cur_on"] & ~st["cur_run"], st["prep_until"],
                       _INF)
    t_next = P["duration"]
    for c in (jnp.min(st["repair"], axis=1), c_pend, c_prep, nf2, ne2):
        t_next = jnp.minimum(t_next, jnp.where(c <= t_eps, _INF, c))
    pending = (nf2 <= t_eps) | (ne2 <= t_eps)
    t_next = jnp.where(pending, t, t_next)

    # record + finalize
    flags = flags | jnp.where(alive, F_VALID, 0)
    adv = alive & ~pending
    flags = flags | jnp.where(adv, F_ADVANCE, 0)
    flags = flags | jnp.where(alive & st["cur_on"] & st["cur_run"],
                              F_RUNNING, 0)
    finishing = adv & (t_next >= P["duration"])
    flags = flags | jnp.where(finishing, F_FINALIZE, 0)
    st = _record_close(st, P, finishing & st["cur_on"])
    st["cur_on"] = st["cur_on"] & ~finishing

    it = st["it"]
    st["rec_t"] = st["rec_t"].at[it].set(t_next)
    st["rec_flags"] = st["rec_flags"].at[it].set(flags)

    st["alive"] = alive & ~finishing
    st["t"] = jnp.where(st["alive"], t_next, st["t"])

    # cap sentries: a lane within its margin of the end of any capped
    # tape is flagged and halted before a read can leave the tape
    lane_over = zero_b
    for ptr, tape in CAPPED.items():
        lane_over = lane_over \
            | (st[ptr] > P[tape].shape[1] - MARGIN[ptr])
    if "se_gang" in st:
        lane_over = lane_over \
            | (st["n_sessions"] > st["se_gang"].shape[1] - 2)
    st["overflow"] = st["overflow"] | (st["alive"] & lane_over)
    st["alive"] = st["alive"] & ~lane_over
    st["it"] = it + 1
    return st


@functools.partial(jax.jit, static_argnames=(
    "n_nodes", "n_sessions", "n_iters", "backend", "interpret"))
def wavefront_core(P, *, n_nodes: int, n_sessions: int, n_iters: int,
                   backend: str = "xla", interpret: bool = False):
    """Run the compiled wavefront over the lane tables ``P`` (the
    ``LaneTables.device`` dict as jnp arrays, every float table as the
    int64 bit patterns of its doubles).  Returns the record stream
    (``rec_t`` as bit patterns too), integer accumulators, overflow flags
    and the iteration count — everything the host replay needs — and,
    where ``n_sessions`` > 0, the first ``n_sessions`` sessions' gangs of
    every lane as ``se_gang`` (``(L, n_sessions, ceil(n_nodes / 32))``
    uint32, :func:`pack_gang`).  ``n_sessions`` 0 carries none: a block
    with no degradation window has no reader for them."""
    L = P["u"].shape[0]
    n, NS, I = n_nodes, n_sessions, n_iters
    if n > 0xFFFF:
        raise ValueError(f"{n} nodes: a failure row packs its node in 16 "
                         "bits")
    f64 = P["u"].dtype                 # int64 bit patterns
    st = {
        "t": jnp.zeros(L, f64),
        "alive": P["lane_on"],
        "pend": jnp.zeros(L, f64),     # first attempt queued at t=0
        "prep_until": jnp.zeros(L, f64),
        "struct_until": jnp.full(L, _NEG_ONE, f64),
        "cur_on": jnp.zeros(L, dtype=bool),
        "cur_run": jnp.zeros(L, dtype=bool),
        "prep_fails": jnp.zeros(L, dtype=bool),
        "last_hw": jnp.zeros(L, dtype=bool),
        "n_att": jnp.zeros(L, dtype=jnp.int32),
        "u_ptr": jnp.zeros(L, dtype=jnp.int32),
        "m_ptr": jnp.zeros(L, dtype=jnp.int32),
        "x_ptr": jnp.zeros(L, dtype=jnp.int32),
        "fail_ptr": jnp.zeros(L, dtype=jnp.int32),
        "esc_ptr": jnp.zeros(L, dtype=jnp.int32),
        "iso_ctr": jnp.zeros(L, dtype=jnp.int32),
        "healthy": jnp.ones((L, n), dtype=bool),
        "excl": jnp.zeros((L, n), dtype=bool),
        "in_gang": jnp.zeros((L, n), dtype=bool),
        "repair": jnp.full((L, n), _INF, f64),
        "iso_reason": jnp.zeros((L, n), dtype=jnp.int8),
        "iso_order": jnp.full((L, n), _ORD_MAX, dtype=jnp.int32),
        "npart_counts": jnp.zeros((L, n), dtype=jnp.int32),
        "n_intervals": jnp.zeros(L, dtype=jnp.int32),
        "n_delib": jnp.zeros(L, dtype=jnp.int32),
        "n_sessions": jnp.zeros(L, dtype=jnp.int32),
        "rec_t": jnp.zeros((I, L), f64),
        "rec_flags": jnp.zeros((I, L), dtype=jnp.int32),
        "overflow": jnp.zeros(L, dtype=bool),
        "it": jnp.int32(0),
    }
    if NS:
        st["se_gang"] = jnp.zeros((L, NS, -(-n // 32)), dtype=jnp.uint32)

    def cond(st):
        return jnp.any(st["alive"]) & (st["it"] < I)

    T = _tape_tables(P)
    st.update(f_t=T["ftd"][0], f_delay=T["ftd"][1], f_small=T["fsmall"][0],
              e_t=T["et"][0], e_node=T["enode"][0])

    def body(st):
        return _iteration(st, P, T, backend, interpret)

    st = lax.while_loop(cond, body, st)
    # lanes still alive at the iteration cap are cap overflows too
    st["overflow"] = st["overflow"] | st["alive"]
    return {k: st[k] for k in (
        "rec_t", "rec_flags", "se_gang", "npart_counts", "n_intervals",
        "n_delib", "n_sessions", "overflow", "it") if k in st}
