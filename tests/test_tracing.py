"""The program's own host spans and counters (``repro.tracing``): off by
default and free there, exact self time when on, on the profiler's clock,
and invisible to the findings they time."""
import glob
import os
import time

import pytest

from repro import tracing
from repro.core.batch import run_findings_stacked
from repro.kernels.wavefront.ops import run_findings_grid
from repro.kernels.wavefront.tapes import WavefrontCaps
from repro.ops import get_scenario

GRID_SPANS = {"grid.draws", "grid.tapes", "grid.upload", "grid.run",
              "grid.replay", "grid.findings"}
PROACTIVE_SPANS = {"engine.draws", "engine.events", "engine.telemetry",
                   "engine.findings", "detector.pass1", "detector.device",
                   "detector.attribute", "control.apply"}


@pytest.fixture(autouse=True)
def tracing_off():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


class CountingAnnotation:
    made = 0

    def __init__(self, name):
        type(self).made += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def counting_annotation(monkeypatch):
    import jax.profiler
    CountingAnnotation.made = 0
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    return CountingAnnotation


def test_off_by_default_records_nothing(counting_annotation):
    with tracing.span("a"):
        with tracing.span("b"):
            tracing.count("c", 3)
    assert tracing.snapshot() == {"spans": {}, "counters": {}}
    assert counting_annotation.made == 0
    # one shared no-op: nothing is allocated per span
    assert tracing.span("a") is tracing.span("b")


def test_on_enters_one_annotation_per_span(counting_annotation):
    tracing.enable()
    for _ in range(3):
        with tracing.span("a"):
            pass
    assert counting_annotation.made == 3
    assert tracing.snapshot()["spans"]["a"]["calls"] == 3


def test_nested_spans_self_time():
    tracing.enable()
    with tracing.span("outer"):
        time.sleep(0.02)
        with tracing.span("inner"):
            time.sleep(0.03)
        with tracing.span("inner"):
            with tracing.span("leaf"):
                time.sleep(0.01)
    spans = tracing.snapshot()["spans"]
    outer, inner, leaf = spans["outer"], spans["inner"], spans["leaf"]
    assert (outer["calls"], inner["calls"], leaf["calls"]) == (1, 2, 1)
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], abs=1e-9)
    assert inner["self_s"] == pytest.approx(
        inner["total_s"] - leaf["total_s"], abs=1e-9)
    assert leaf["self_s"] == leaf["total_s"] >= 0.01
    assert 0.02 <= outer["self_s"] < outer["total_s"]
    assert inner["total_s"] >= 0.04


def test_counters_add_up_and_reset():
    tracing.enable()
    for n in (1, 2, 3.5):
        tracing.count("x", n)
    tracing.count("y")
    with tracing.span("s"):
        pass
    snap = tracing.snapshot()
    assert snap["counters"] == {"x": 6.5, "y": 1}
    tracing.reset()
    assert tracing.snapshot() == {"spans": {}, "counters": {}}
    tracing.count("x")
    assert tracing.snapshot()["counters"] == {"x": 1}
    tracing.disable()
    tracing.count("x")
    assert tracing.snapshot()["counters"] == {"x": 1}


def test_spans_land_in_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData
    tracing.enable()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(2):
            with tracing.span("outer"):
                time.sleep(0.01)
                with tracing.span("inner"):
                    time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    n, s = seen.get(ev.name, (0, 0.0))
                    seen[ev.name] = (n + 1, s + ev.duration_ns * 1e-9)
    spans = tracing.snapshot()["spans"]
    assert set(seen) == {"repro.outer", "repro.inner"}
    for name, (n, seconds) in seen.items():
        mine = spans[name[len("repro."):]]
        assert n == mine["calls"] == 2
        assert seconds == pytest.approx(mine["total_s"], abs=1e-3)


def _grid_configs():
    return [get_scenario(name).replace(duration_days=2.0)
            .to_campaign_config(0)
            for name in ("paper-faithful", "flaky-fabric")]


def test_grid_findings_identical_and_spans_named():
    cfgs, seeds = _grid_configs(), list(range(8))      # 16 lanes
    off = run_findings_grid(cfgs, seeds, backend="xla")
    assert tracing.snapshot() == {"spans": {}, "counters": {}}
    tracing.enable()
    on = run_findings_grid(cfgs, seeds, backend="xla")
    assert on == off
    spans = tracing.snapshot()["spans"]
    assert set(spans) == GRID_SPANS
    assert all(s["calls"] == 1 for s in spans.values())


def test_grid_cap_reruns_counted():
    tracing.enable()
    caps = WavefrontCaps(n_iters=16)
    run_findings_grid(_grid_configs(), list(range(8)), backend="xla",
                      caps=caps)
    snap = tracing.snapshot()
    reruns = snap["counters"]["grid.cap_reruns"]
    assert reruns >= 1
    assert snap["spans"]["grid.run"]["calls"] == reruns + 1
    assert snap["spans"]["grid.tapes"]["calls"] == reruns + 1


def test_grid_replay_counters():
    """The replay counts the event cells it folds without a loop and the
    steps of its checkpoint catch-up loop, which takes each lane's
    catch-up rows in turn, so never more steps than device iterations."""
    tracing.enable()
    run_findings_grid(_grid_configs(), list(range(8)), backend="xla")
    counters = tracing.snapshot()["counters"]
    assert counters["grid.replay_events"] > 0
    assert 0 < counters["grid.replay_ckpt_rows"] \
        <= counters["grid.iterations"]


def test_proactive_findings_identical_and_spans_named():
    cfg = get_scenario("proactive").replace(
        duration_days=1.0, detector_backend="xla").to_campaign_config(0)
    off = run_findings_stacked([cfg], [0])
    tracing.enable()
    on = run_findings_stacked([cfg], [0])
    assert on == off
    snap = tracing.snapshot()
    assert PROACTIVE_SPANS <= set(snap["spans"])
    assert not any(k.startswith("grid.") for k in snap["spans"])
    for name in ("engine.draws", "engine.events", "engine.findings"):
        assert snap["spans"][name]["calls"] == 1
    c = snap["counters"]
    assert 0 < c["detector.compiled_seed_ticks"] <= c["detector.seed_ticks"]
    # one simulated day of 30 s scrapes, each tick generated and through
    # pass 1 once; a lone seed's rounds are all its own ticks
    assert c["detector.seed_ticks"] == c["engine.telemetry_ticks"] \
        == c["engine.telemetry_path_ticks"] == 2880
    events = snap["spans"]["engine.events"]
    inner = sum(snap["spans"][k]["total_s"] for k in (
        "engine.telemetry", "detector.pass1", "detector.attribute",
        "control.apply"))
    assert events["self_s"] == pytest.approx(events["total_s"] - inner,
                                             abs=1e-6)


def test_telemetry_path_ticks_bound_concurrent_rounds():
    """With several seeds every generated seed-tick still reaches pass 1
    once, and the longest chunk of each round sums to fewer ticks."""
    cfg = get_scenario("proactive").replace(
        duration_days=1.0, detector_backend="xla",
        telemetry_pad_metrics=0).to_campaign_config(0)
    tracing.enable()
    run_findings_stacked([cfg], [0, 1, 2, 3])
    c = tracing.snapshot()["counters"]
    assert c["engine.telemetry_ticks"] == c["detector.seed_ticks"] \
        == 4 * 2880
    assert 2880 <= c["engine.telemetry_path_ticks"] \
        < c["engine.telemetry_ticks"]
