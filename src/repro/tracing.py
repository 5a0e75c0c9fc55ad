"""Host spans and counters at the phase boundaries of the program.

    from repro import tracing
    tracing.enable()
    with tracing.span("grid.replay"):
        ...
    tracing.count("grid.cap_reruns")
    tracing.snapshot()
    # {"spans": {"grid.replay": {"calls": 1, "total_s": .., "self_s": ..}},
    #  "counters": {"grid.cap_reruns": 1}}

Off by default: ``span`` then hands back one shared no-op after a single
flag check and ``count`` returns at once, so instrumented code pays
nothing it would notice.  When on, each span enters
``jax.profiler.TraceAnnotation("repro.<name>")``, which writes it into a
running profiler's trace on the host clock the device ops are stamped
against, and adds to in-memory aggregates per name: calls, total seconds,
and self seconds (total less the time of the spans nested directly in it
on the same thread).  Memory stays bounded by the number of names; no
event is ever kept.  Spans sit at phase granularity (one per engine
phase, per telemetry round or per detector group), never per lane, seed
or event.

The state is one per process, as the profiler's is: the caller that
enables tracing owns it, and resets it at the start of what it measures.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List

__all__ = ["span", "count", "enable", "disable", "reset", "snapshot"]

_on = False
_profiler = None                      # jax.profiler, imported by enable()
_lock = threading.Lock()
_spans: Dict[str, List[float]] = {}   # name -> [calls, total_s, self_s]
_counters: Dict[str, float] = {}
_local = threading.local()


class _Off:
    """The span handed out while tracing is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "annotation", "t0", "child_s")

    def __init__(self, name: str):
        self.name = name
        self.annotation = _profiler.TraceAnnotation(f"repro.{name}")
        self.child_s = 0.0

    def __enter__(self):
        _stack().append(self)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.annotation.__exit__(*exc)
        stack = _stack()
        stack.pop()
        if stack:
            stack[-1].child_s += dt
        with _lock:
            agg = _spans.get(self.name)
            if agg is None:
                agg = _spans[self.name] = [0, 0.0, 0.0]
            agg[0] += 1
            agg[1] += dt
            agg[2] += dt - self.child_s
        return False


def span(name: str):
    """A context manager timing one phase under ``name``."""
    if not _on:
        return _OFF
    return _Span(name)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    if not _on:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def enable() -> None:
    global _on, _profiler
    import jax.profiler
    _profiler = jax.profiler
    _on = True


def disable() -> None:
    global _on
    _on = False


def reset() -> None:
    """Forget every aggregate and counter (spans open now still add
    their time when they close)."""
    with _lock:
        _spans.clear()
        _counters.clear()


def snapshot() -> dict:
    """``{"spans": {name: {calls, total_s, self_s}}, "counters": {..}}``
    as plain numbers, a copy."""
    with _lock:
        return {
            "spans": {k: {"calls": int(c), "total_s": t, "self_s": s}
                      for k, (c, t, s) in _spans.items()},
            "counters": dict(_counters),
        }
