"""Idle gaps split across the host spans open over them: on synthetic
spans, and on a trace recorded here with the program's ``repro.*``
spans beside the benchmark's ``bench.*`` wrappers."""
import time

import pytest

from idle_split import OUTSIDE, host_spans, idle_by_span, split_gaps
from trace_reduce import find_xplane, reduce_trace, start


def test_gap_across_two_spans_split_in_proportion():
    spans = [(0.0, 3.0, "bench.replay"), (3.0, 10.0, "bench.tapes")]
    pieces = split_gaps(spans, [(1.0, 5.0)])
    assert pieces == [(1.0, 3.0, "bench.replay"), (3.0, 5.0, "bench.tapes")]


def test_program_span_inside_a_bench_span_takes_its_piece():
    spans = [(0.0, 10.0, "bench.grid"), (2.0, 4.0, "repro.grid.replay"),
             (4.0, 5.0, "repro.grid.findings")]
    by = dict(((a, b), label) for a, b, label
              in split_gaps(spans, [(1.0, 4.5), (6.0, 7.0)]))
    assert by == {(1.0, 2.0): "bench.grid", (2.0, 4.0): "repro.grid.replay",
                  (4.0, 4.5): "repro.grid.findings",
                  (6.0, 7.0): "bench.grid"}


def test_piece_under_no_span_is_outside():
    pieces = split_gaps([(2.0, 3.0, "bench.tapes")], [(0.0, 1.0), (1.5, 4.0)])
    assert pieces == [(0.0, 1.0, OUTSIDE), (1.5, 2.0, OUTSIDE),
                      (2.0, 3.0, "bench.tapes"), (3.0, 4.0, OUTSIDE)]
    assert split_gaps([], [(0.0, 1.0)]) == [(0.0, 1.0, OUTSIDE)]


def test_pieces_cover_every_gap_exactly():
    spans = [(0.1 * i, 0.1 * i + 0.25, f"s{i % 3}") for i in range(40)]
    gaps = [(0.05 + 0.2 * i, 0.15 + 0.2 * i) for i in range(25)]
    pieces = split_gaps(spans, gaps)
    assert sum(b - a for a, b, _ in pieces) == pytest.approx(
        sum(b - a for a, b in gaps))
    assert all(b > a for a, b, _ in pieces)


def test_split_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro import tracing
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    where = str(tmp_path)
    tracing.enable()
    start(where)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
                    with tracing.span("host_work"):
                        time.sleep(0.03)
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
        tracing.disable()
        tracing.reset()
    path = find_xplane(where)
    red = reduce_trace(
        path, device_plane=lambda n: n == "/host:CPU",
        ops_line=lambda n: n.startswith("tf_XLAPjRtCpuClient"),
        modules_line=lambda n: False, keep_op=lambda name, s: s > 0)
    spans = host_spans(path)
    assert {n for _, _, n in spans} >= {"bench.step", "repro.host_work"}
    idle = idle_by_span(red, spans)
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)
    # the sleep inside the program's span is named by it, not by the
    # benchmark's wrapper around it
    assert max(idle, key=idle.get) == "repro.host_work"
    assert idle["repro.host_work"] >= 0.08
    assert idle.get(OUTSIDE, 0.0) >= 0.02
