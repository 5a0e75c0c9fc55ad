"""Split the device's idle gaps across the host spans open over them.

``trace_reduce`` names each whole idle gap by the span open at its
midpoint.  Between two device passes one gap can run through several
host phases (replay, findings, the next pass's draws and tapes), so that
label gives all of it to whichever phase holds the midpoint, or to the
outer span around them.  Here each gap is cut at the boundaries of the
host spans inside it, and each piece goes to the innermost (shortest)
span open over the whole piece; a piece under no span goes to
``host:outside spans``.  The spans are the benchmark's ``bench.*``
wrappers and the program's own ``repro.*`` phases (``repro.tracing``),
which share the profiler's host clock with the device ops.

    from trace_reduce import find_xplane, reduce_trace
    path = find_xplane(where)
    idle_by_span(reduce_trace(path), host_spans(path))
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

OUTSIDE = "host:outside spans"
WINDOW_SPAN = "bench.window"

Span = Tuple[float, float, str]


def host_spans(path: str, prefixes: Iterable[str] = ("bench.", "repro.")
               ) -> List[Span]:
    """Every host span of the trace whose name starts with one of
    ``prefixes``, in seconds on the trace clock, the window's own span
    left out."""
    from jax.profiler import ProfileData
    prefixes = tuple(prefixes)
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(prefixes) and name != WINDOW_SPAN:
                    a = ev.start_ns * 1e-9
                    out.append((a, a + ev.duration_ns * 1e-9, name))
    return out


def split_gaps(spans: List[Span], gaps: List[Tuple[float, float]]
               ) -> List[Span]:
    """Cut each of the ascending, disjoint ``gaps`` at the span
    boundaries inside it; label each piece with the shortest span that
    covers it whole, else ``OUTSIDE``.  One sweep over both lists."""
    spans = sorted(spans)
    out: List[Span] = []
    open_: List[Span] = []
    k = 0
    for a, b in gaps:
        while k < len(spans) and spans[k][0] < b:
            open_.append(spans[k])
            k += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        cuts = sorted({a, b} | {t for sp in open_ for t in sp[:2]
                                if a < t < b})
        for p, q in zip(cuts, cuts[1:]):
            over = [sp for sp in open_ if sp[0] <= p and sp[1] >= q]
            best = min(over, key=lambda sp: sp[1] - sp[0], default=None)
            out.append((p, q, best[2] if best else OUTSIDE))
    return out


def idle_by_span(reduced, spans: List[Span]) -> Dict[str, float]:
    """Idle seconds of a ``trace_reduce.Reduced`` per host span, largest
    first."""
    pieces = split_gaps(spans, [(a, b) for a, b, _ in reduced.gaps])
    by: Dict[str, float] = defaultdict(float)
    for a, b, label in pieces:
        by[label] += b - a
    return dict(sorted(by.items(), key=lambda kv: -kv[1]))
