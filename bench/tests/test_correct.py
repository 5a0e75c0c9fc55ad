"""``correct``: true on a sound run, false on the control and on each
fault the cells can have, driven through the rest of a run at a size a
test run holds (the look for a chip skipped).

The faults are planted in the program underneath the timed path: a step
that returns its state unchanged (the grid's host replay; the detector's
pass 1, whose votes then never move), half of the seeds left out with
the rest standing in for them, and an answer altered where it is
produced.  The cells run on one chip, so no exchange between chips can
be left out.
"""
import io
import json
import time

import numpy as np
import pytest

from harness.cell import load_cell
from harness.main import execute


def small_cell(name):
    cell = load_cell(name)
    if name.startswith("mc_grid"):
        # 2 x 32 lanes: the grid pass's floor, so the device path runs
        cell.config = dict(cell.config, duration_days=5.0)
        cell.traffic = dict(cell.traffic, lanes_per_variant=32,
                            variants=["paper-faithful", "flaky-fabric"],
                            check_lanes=6)
    else:
        # 2 lanes of a quarter day; pass 1 still goes through hit_block,
        # as jitted XLA (the Pallas kernel only interprets on the CPU)
        variants = {k: dict(v, detector_backend="xla")
                    for k, v in cell.config["variants"].items()}
        cell.config = dict(cell.config, duration_days=0.25,
                           variants=variants)
        cell.traffic = dict(cell.traffic, lanes_per_variant=2,
                            check_lanes=2)
    return cell


def run(name, *, with_control=False):
    out, err = io.StringIO(), io.StringIO()
    rc = execute(["--workload", name, "--seed", str(2**31 + 11),
                  "--seconds", "1", "--trace", "0"], time.perf_counter(),
                 require_tpu=False, cell=small_cell(name),
                 with_control=with_control, out=out, err=err)
    assert rc == 0, err.getvalue()
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert err.getvalue().strip().splitlines()[-1].startswith("bench check:")
    return line


CELLS = ["mc_grid.paper-63n", "mc_proactive.paper-63n-proactive"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_and_control(name):
    line = run(name, with_control=True)
    assert line["correct"] is True
    assert line["control_correct"] is False
    for key, check in line["checks"].items():
        assert check["value"] <= check["limit"]
        # the control (the reference, rounded to float32) reads far above
        assert float(line["control"][key]) > 100 * check["limit"]


def _state_unchanged(monkeypatch):
    import repro.kernels.robust_stats.ops as rs
    import repro.kernels.wavefront.ops as wf
    monkeypatch.setattr(wf, "_replay",
                        lambda tables, host: wf._Replay(
                            host["rec_t"].shape[1]))
    hit_block = rs.hit_block

    def no_votes(block, active, **kw):
        return np.zeros_like(hit_block(block, active, **kw))
    monkeypatch.setattr(rs, "hit_block", no_votes)


def _half_the_seeds(monkeypatch):
    import repro.core.batch as batch
    stacked = batch.run_findings_stacked

    def half(cfgs, seeds, **kw):
        keep = list(seeds)[:max(len(seeds) // 2, 1)]
        out = stacked(cfgs, keep, **kw)
        return [{s: d[keep[i % len(keep)]] for i, s in enumerate(seeds)}
                for d in out]
    monkeypatch.setattr(batch, "run_findings_stacked", half)


def _answer_altered(monkeypatch):
    import repro.core.batch as batch
    import repro.kernels.wavefront.ops as wf
    lane = wf._lane_findings

    def altered(*a, **kw):
        out = lane(*a, **kw)
        out["goodput"] = out["goodput"] * (1 + 1e-9)
        return out
    monkeypatch.setattr(wf, "_lane_findings", altered)
    engine = batch.BatchedCampaignEngine._findings

    def altered_numpy(self, B, i):
        out = engine(self, B, i)
        out["goodput"] = out["goodput"] * (1 + 1e-9)
        return out
    monkeypatch.setattr(batch.BatchedCampaignEngine, "_findings",
                        altered_numpy)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_the_seeds": _half_the_seeds,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_caught(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    assert run(name)["correct"] is False
