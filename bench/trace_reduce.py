"""From a profiler trace (``.xplane.pb``) to busy time, idle gaps and
per-program device time.

A device op is an event on a device plane's op line (``XLA Ops`` on a
TPU).  Busy time is the union of their intervals inside the measured
window, which the benchmark marks with a ``bench.window`` host span; the
idle share is one minus busy over the window.  Each idle gap is named by
the innermost ``bench.*`` host span open at its midpoint: what the host
was doing while the device waited.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def tpu_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def tpu_ops_line(name: str) -> bool:
    return name == "XLA Ops"


def tpu_modules_line(name: str) -> bool:
    return name == "XLA Modules"


@dataclass
class Reduced:
    window: Tuple[float, float]            # seconds on the trace clock
    busy_s: float                          # per device, averaged
    n_devices: int
    ops_s: Dict[str, float] = field(default_factory=dict)
    modules_s: Dict[str, float] = field(default_factory=dict)
    module_calls: Dict[str, int] = field(default_factory=dict)
    gaps: List[Tuple[float, float, str]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, substring: str) -> Tuple[float, int]:
        """Device seconds and calls of the programs whose name holds
        ``substring``."""
        s = sum(v for k, v in self.modules_s.items() if substring in k)
        n = sum(v for k, v in self.module_calls.items() if substring in k)
        return s, n

    def op_seconds(self, substring: str) -> float:
        return sum(v for k, v in self.ops_s.items() if substring in k)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:top]
        by_span: Dict[str, float] = defaultdict(float)
        for a, b, label in self.gaps:
            by_span[label] += b - a
        idle = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def union_length(intervals: List[Tuple[float, float]]) -> Tuple[
        float, List[Tuple[float, float]]]:
    """Length of the union of ``intervals`` and its merged pieces."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [tuple(m) for m in merged]


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def start(directory: str) -> None:
    """Start the profiler with the host's TraceMe spans (the ``bench.*``
    annotations) and without its Python tracer, which would record every
    Python call of the program's host loops and slow them."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=options)


def find_xplane(directory: str) -> Optional[str]:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def reduce_trace(path: str, *,
                 device_plane: Callable[[str], bool] = tpu_plane,
                 ops_line: Callable[[str], bool] = tpu_ops_line,
                 modules_line: Callable[[str], bool] = tpu_modules_line,
                 keep_op: Callable[[str, float], bool] = None
                 ) -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    dev_events: List[List[Tuple[float, float, str]]] = []
    mod_events: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        is_dev = device_plane(plane.name)
        ops_here: List[Tuple[float, float, str]] = []
        for line in plane.lines:
            want_ops = is_dev and ops_line(line.name)
            want_mod = is_dev and modules_line(line.name)
            for ev in line.events:
                a = ev.start_ns * 1e-9
                b = a + ev.duration_ns * 1e-9
                name = ev.name
                if want_ops:
                    if keep_op is None or keep_op(name, b - a):
                        ops_here.append((a, b, name))
                elif want_mod:
                    mod_events.append((a, b, name))
                elif name.startswith(SPAN_PREFIX):
                    spans.append((a, b, name))
        if is_dev and ops_here:
            dev_events.append(ops_here)
    windows = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    else:
        every = [e for evs in dev_events for e in evs]
        lo = min((a for a, _, _ in every), default=0.0)
        hi = max((b for _, b, _ in every), default=0.0)
    out = Reduced(window=(lo, hi), busy_s=0.0, n_devices=len(dev_events))
    ops_s: Dict[str, float] = defaultdict(float)
    merged_all = []
    for evs in dev_events:
        clipped = []
        for a, b, name in evs:
            c = _clip(a, b, lo, hi)
            if c:
                clipped.append(c)
                ops_s[name] += c[1] - c[0]
        busy, merged = union_length(clipped)
        out.busy_s += busy / max(len(dev_events), 1)
        merged_all.append(merged)
    out.ops_s = dict(ops_s)
    mods: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    for a, b, name in mod_events:
        c = _clip(a, b, lo, hi)
        if c:
            mods[name] += c[1] - c[0]
            calls[name] += 1
    out.modules_s, out.module_calls = dict(mods), dict(calls)
    inner = [s for s in spans if s[2] != WINDOW_SPAN]
    pieces = []
    if merged_all:
        edges = [lo] + [x for ab in merged_all[0] for x in ab] + [hi]
        pieces = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    elif hi > lo:
        pieces = [(lo, hi)]
    labels = _labels(inner, [(a + b) / 2 for a, b in pieces])
    out.gaps = [(a, b, label) for (a, b), label in zip(pieces, labels)]
    return out


def _labels(spans: List[Tuple[float, float, str]],
            points: List[float]) -> List[str]:
    """For each of the ascending ``points``, the innermost (shortest)
    host span open there, in one sweep."""
    spans = sorted(spans)
    out, open_, k = [], [], 0
    for t in points:
        while k < len(spans) and spans[k][0] <= t:
            open_.append(spans[k])
            k += 1
        open_ = [sp for sp in open_ if sp[1] >= t]
        best = min(open_, key=lambda sp: sp[1] - sp[0], default=None)
        out.append(best[2] if best else "host:outside bench spans")
    return out
