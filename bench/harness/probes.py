"""Counters and host spans around the calls into the program's layers.

The benchmark wraps module attributes of the program (the pattern of a
``counted`` call wrapper): each wrapped call adds to a count and a host
time, and writes a ``jax.profiler.TraceAnnotation`` named ``bench.<span>``
into the profiler's trace, so a traced run can name what the host was
doing in each device idle gap.  An attribute that a later version of the
program no longer has is skipped: the metrics that read it then find
nothing and are left out.
"""
from __future__ import annotations

import importlib
import threading
import time
from collections import defaultdict
from typing import Callable, Dict, List, Optional

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Programs compiled, and programs read back from the persistent
    cache, while the clock is open (a ``jax.monitoring`` listener)."""

    def __init__(self):
        import jax.monitoring
        self.compiles = 0
        self.cache_loads = 0
        self.open = False
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, name, secs, **_):
        if self.open and name == COMPILE_EVENT:
            self.compiles += 1

    def _on_event(self, name, **_):
        if self.open and name == CACHE_HIT_EVENT:
            self.cache_loads += 1


class Probes:
    """Wrappers that count calls, time them and record their arguments."""

    def __init__(self):
        self.calls: Dict[str, int] = defaultdict(int)
        self.seconds: Dict[str, float] = defaultdict(float)
        self.args: Dict[str, List[tuple]] = defaultdict(list)
        self._undo: List[Callable[[], None]] = []
        self._lock = threading.Lock()
        self.recording = False

    def wrap(self, module: str, attr: str, span: str, *,
             keep: Optional[Callable] = None,
             on_result: Optional[Callable] = None) -> bool:
        """Wrap ``module.attr``; returns False where it does not exist.
        ``keep(args, kwargs)`` picks what to record of each call (shapes,
        not arrays); ``on_result(probes, args, kwargs, out)`` counts."""
        try:
            mod = importlib.import_module(module)
        except ImportError:
            return False
        orig = getattr(mod, attr, None)
        if orig is None:
            return False
        from jax.profiler import TraceAnnotation
        probes = self

        def wrapper(*args, **kwargs):
            t0 = time.perf_counter()
            with TraceAnnotation(f"bench.{span}"):
                out = orig(*args, **kwargs)
            dt = time.perf_counter() - t0
            if probes.recording:
                with probes._lock:
                    probes.calls[span] += 1
                    probes.seconds[span] += dt
                    if keep is not None:
                        probes.args[span].append(keep(args, kwargs))
                if on_result is not None:
                    on_result(probes, args, kwargs, out)
            return out

        setattr(mod, attr, wrapper)
        self._undo.append(lambda: setattr(mod, attr, orig))
        return True

    def add(self, name: str, value: float) -> None:
        with self._lock:
            self.calls[name] += value

    def reset(self) -> None:
        with self._lock:
            self.calls.clear()
            self.seconds.clear()
            self.args.clear()

    def unwrap(self) -> None:
        while self._undo:
            self._undo.pop()()
