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


def _row(tab, ptr):
    """tab[(l, ptr[l])] with a clipped (overflow-safe) gather."""
    idx = jnp.clip(ptr, 0, tab.shape[1] - 1)
    return jnp.take_along_axis(tab, idx[:, None], axis=1)[:, 0]


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


def _sched_next(st, flags, P, mask, t, evt_delay_h, evt_has_xid,
                structural: bool):
    """Vector form of ``_schedule_next``: retry-vs-manual decision and
    the next pending-start time, with the exact scalar draw discipline
    (noticed roll consumed iff attempt count >= 3; misfix roll always
    consumed on the manual branch; delays pre-divided so the device adds
    once)."""
    n_att = st["n_att"]
    roll = mask & (n_att >= 3)
    u_not = _row(P["u"], st["u_ptr"])
    noticed = roll & (u_not < P["notice_p"])
    st["u_ptr"] = st["u_ptr"] + roll
    if structural:
        noticed = noticed | (mask & P["struct_stop"])
    dna_d = _row(P["dna"], n_att)
    delay = jnp.where(P["policy_xid"] & evt_has_xid, evt_delay_h, dna_d)
    retry = mask & P["retry_on"] & _finite(delay) \
        & (n_att < P["max_r"]) & ~noticed
    st["pend"] = jnp.where(retry, f64_add(t, delay), st["pend"])

    man = mask & ~retry
    # manual-intervention branch: chain closes, operator responds with a
    # day/night exponential delay, and a misfixed root cause may extend
    # the structural-failure horizon
    md = jnp.where(f64_night(t), _row(P["man_night"], st["m_ptr"]),
                   _row(P["man_day"], st["m_ptr"]))
    st["m_ptr"] = st["m_ptr"] + man
    pend_man = f64_add(t, md)
    st["pend"] = jnp.where(man, pend_man, st["pend"])
    u_mis = _row(P["u"], st["u_ptr"])
    mis = man & (u_mis < P["p_misfix"])
    st["u_ptr"] = st["u_ptr"] + man
    xh = _row(P["x_half"], st["x_ptr"])
    st["x_ptr"] = st["x_ptr"] + mis
    su = st["struct_until"]
    st["struct_until"] = jnp.where(
        mis, jnp.maximum(su, f64_add(pend_man, xh)),
        jnp.where(man, jnp.minimum(su, pend_man), su))
    st["n_att"] = jnp.where(man, 0, st["n_att"])
    flags = flags | jnp.where(man, F_CHAIN_CLOSE, 0)
    return st, flags


def _iteration(st, P, backend: str, interpret: bool):
    t = st["t"]
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
    u_adm = _row(P["u"], st["u_ptr"])
    readmit = has_cand & (u_adm < P["p_readmit"])
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
    st, flags = _sched_next(st, flags, P, afail, t, nan_v, zero_b, True)

    # gang-feasible: open the session (and record its packed gang where
    # the block carries session gang masks)
    st["in_gang"] = jnp.where(okm[:, None], chosen, st["in_gang"])
    if "se_gang" in st:
        rows = jnp.arange(L)
        sidx = jnp.clip(st["n_sessions"], 0, st["se_gang"].shape[1] - 1)
        st["se_gang"] = st["se_gang"].at[rows, sidx].set(jnp.where(
            okm[:, None], pack_gang(chosen), st["se_gang"][rows, sidx]))
    st["n_sessions"] = st["n_sessions"] + okm.astype(jnp.int32)
    flags = flags | jnp.where(okm, F_START, 0)
    # transient-retry roll + pre-transformed load-duration draw
    pf_pre = t < st["struct_until"]
    roll_tr = okm & ~pf_pre & ((st["n_att"] == 2) | (st["n_att"] == 3))
    u_tr = _row(P["u"], st["u_ptr"])
    trans = roll_tr & (u_tr < P["p_transient"])
    st["u_ptr"] = st["u_ptr"] + roll_tr
    pf = pf_pre | trans
    dur = jnp.where(pf, _row(P["dur_fail"], st["u_ptr"]),
                    jnp.where(st["last_hw"],
                              _row(P["dur_cold"], st["u_ptr"]),
                              _row(P["dur_warm"], st["u_ptr"])))
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
    st, flags = _sched_next(st, flags, P, pfail, t, nan_v, zero_b, False)

    # 5. at most one failure event per lane per iteration
    nf = _row(P["ft"], st["fail_ptr"])
    fdue = alive & (nf <= t_eps)
    fnode = _row(P["fnode"], st["fail_ptr"])
    fk = _row(P["fkcode"], st["fail_ptr"])
    fhw = _row(P["fhw"], st["fail_ptr"])
    fdel = _row(P["fdelay"], st["fail_ptr"])
    fhx = _row(P["fhas_xid"], st["fail_ptr"])
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
    hit = jnp.take_along_axis(st["in_gang"],
                              jnp.clip(fnode, 0, n - 1)[:, None],
                              axis=1)[:, 0]
    ghit = m_kill & st["cur_on"] & hit
    flags = flags | jnp.where(ghit & st["cur_run"], F_LOST, 0)
    u_sw = _row(P["u"], st["u_ptr"])
    soft = ghit & (u_sw < P["p_soft"])
    st["u_ptr"] = st["u_ptr"] + ghit
    xf = _row(P["x_full"], st["x_ptr"])
    st["struct_until"] = jnp.where(
        soft, jnp.maximum(st["struct_until"], f64_add(t, xf)),
        st["struct_until"])
    st["x_ptr"] = st["x_ptr"] + soft
    st, flags = _fail_session(st, flags, P, ghit, fhw)
    st, flags = _sched_next(st, flags, P, ghit, t, fdel, fhx, False)
    st["fail_ptr"] = st["fail_ptr"] + fdue

    # 5b. escalation crash, only once the failure queue at t has drained
    # (the numpy loop processes failures then escalations per iteration)
    nf2 = _row(P["ft"], st["fail_ptr"])
    ne = _row(P["et"], st["esc_ptr"])
    edue = alive & (ne <= t_eps) & ~(nf2 <= t_eps)
    en = _row(P["enode"], st["esc_ptr"])
    ehit_node = jnp.take_along_axis(st["in_gang"],
                                    jnp.clip(en, 0, n - 1)[:, None],
                                    axis=1)[:, 0]
    ehit = edue & st["cur_on"] & ehit_node
    flags = flags | jnp.where(ehit & st["cur_run"], F_LOST, 0)
    u_sw2 = _row(P["u"], st["u_ptr"])
    soft2 = ehit & (u_sw2 < P["p_soft"])
    st["u_ptr"] = st["u_ptr"] + ehit
    xf2 = _row(P["x_full"], st["x_ptr"])
    st["struct_until"] = jnp.where(
        soft2, jnp.maximum(st["struct_until"], f64_add(t, xf2)),
        st["struct_until"])
    st["x_ptr"] = st["x_ptr"] + soft2
    st, flags = _fail_session(st, flags, P, ehit, zero_b)
    st, flags = _sched_next(st, flags, P, ehit, t, nan_v, zero_b, False)
    st["esc_ptr"] = st["esc_ptr"] + edue
    ne2 = _row(P["et"], st["esc_ptr"])

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

    # cap sentries: a lane within one iteration's worth of consumption of
    # any cap is flagged and halted before a clipped read can corrupt it
    U, M, X = P["u"].shape[1], P["man_day"].shape[1], P["x_half"].shape[1]
    lane_over = (st["u_ptr"] > U - 8) | (st["m_ptr"] > M - 4) \
        | (st["x_ptr"] > X - 4)
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

    def body(st):
        return _iteration(st, P, backend, interpret)

    st = lax.while_loop(cond, body, st)
    # lanes still alive at the iteration cap are cap overflows too
    st["overflow"] = st["overflow"] | st["alive"]
    return {k: st[k] for k in (
        "rec_t", "rec_flags", "se_gang", "npart_counts", "n_intervals",
        "n_delib", "n_sessions", "overflow", "it") if k in st}
