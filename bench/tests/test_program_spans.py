"""The program's own spans and counters (``repro.tracing``) read what the
benchmark's wrappers read, on a small window of each cell.

The window here is opened as ``harness.main.Window`` opens it, with the
program's tracing enabled and reset beside the probes, and its snapshot
taken on exit.  The wrappers' readers are the accepted metric files.
"""
from types import SimpleNamespace

import pytest

from harness import sweep
from harness.cell import metric_reader
from harness.main import install_probes
from harness.probes import Probes
from repro import tracing
from test_correct import small_cell


def traced_window(name, seconds=1.0):
    cell = small_cell(name)
    probes = Probes()
    install_probes(probes)
    try:
        run = sweep.build(cell, 2**31 + 11)
        cfgs = sweep.program_configs(run)
        sweep.warm(run, cfgs)
        probes.reset()
        probes.recording = True
        tracing.enable()
        tracing.reset()
        try:
            sweep.window(run, cfgs, seconds)
        finally:
            probes.recording = False
            snap = tracing.snapshot()
            tracing.disable()
            tracing.reset()
    finally:
        probes.unwrap()
    return SimpleNamespace(probes=probes, window_s=run.window_s), snap


def test_grid_program_agrees_with_wrappers():
    view, snap = traced_window("mc_grid.paper-63n")
    spans, counters = snap["spans"], snap["counters"]
    grids = spans["grid.draws"]["calls"]
    assert grids == view.probes.calls["grid"] >= 1
    assert spans["grid.run"]["calls"] == view.probes.calls["device_pass"]
    assert counters.get("grid.cap_reruns", 0) / grids == pytest.approx(
        metric_reader("cap_reruns.mc")(view), abs=1e-12)
    ours = spans["grid.replay"]["total_s"] + spans["grid.findings"]["total_s"]
    theirs = view.probes.seconds["replay"] \
        + view.probes.seconds["lane_findings"]
    assert ours == pytest.approx(theirs, rel=0.05)
    assert spans["stacked.resolve"]["calls"] == grids


def test_detector_program_agrees_with_wrappers():
    view, snap = traced_window("mc_proactive.paper-63n-proactive")
    counters = snap["counters"]
    share = 100.0 * counters.get("detector.compiled_seed_ticks", 0) \
        / counters["detector.seed_ticks"]
    assert share == pytest.approx(
        metric_reader("detector_compiled_share.mc")(view), abs=1e-9)
    assert counters["detector.seed_ticks"] \
        == view.probes.calls["detector_seed_ticks"]
    assert {"engine.events", "engine.telemetry", "detector.pass1",
            "control.apply"} <= set(snap["spans"])
