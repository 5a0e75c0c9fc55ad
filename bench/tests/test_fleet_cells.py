"""The fleet cell (``mc_fleet.llama3-16k``) and the ragged cell
(``mc_ragged.paper-63n``) rehearsed on the CPU at a size a test run
holds, through the rest of a run (the look for a chip skipped): a sound
run reads ``correct``, the control does not, and planted faults are
caught.

The fleet cell keeps its 2,176-node pool and 2,048-node gang and cuts
its lanes and days; the ragged cell keeps a lane count that is not a
power of two, so idle padded lanes ride along.
"""
import io
import json
import time

import pytest

from harness.cell import load_cell
from harness.main import execute
from test_correct import FAULTS


def small_cell(name):
    cell = load_cell(name)
    if name.startswith("mc_fleet"):
        # 4 variants x 16 lanes of 3 days: the grid pass's floor
        cell.config = dict(cell.config, duration_days=3.0)
        cell.traffic = dict(cell.traffic, lanes_per_variant=16,
                            check_lanes=24)
    else:
        # 3 variants x 24 lanes of 5 days, padded from 72 to 128
        cell.config = dict(cell.config, duration_days=5.0)
        cell.traffic = dict(cell.traffic, lanes_per_variant=24,
                            check_lanes=24)
    return cell


# Each variant's busiest lane is checked, and the rest of the 24 checked
# lanes are drawn from the window's passes: about 20 draws, so a fault
# that spoils half the lanes goes unseen once in a million runs.


def run(name, *, with_control=False):
    out, err = io.StringIO(), io.StringIO()
    rc = execute(["--workload", name, "--seed", str(2**31 + 29),
                  "--seconds", "1", "--trace", "0"], time.perf_counter(),
                 require_tpu=False, cell=small_cell(name),
                 with_control=with_control, out=out, err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])


CELLS = ["mc_fleet.llama3-16k", "mc_ragged.paper-63n"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_and_control(name):
    line = run(name, with_control=True)
    assert line["correct"] is True
    assert line["control_correct"] is False
    assert line["attempted"] > 0 and line["failed"] == 0
    for key, check in line["checks"].items():
        assert check["value"] <= check["limit"]
        assert float(line["control"][key]) > 100 * check["limit"]


def _overflow_ignored(monkeypatch):
    """Caps far too small, and the overflow flag dropped: results read
    from a pass that stopped its lanes early."""
    import repro.kernels.wavefront.ops as wf
    from repro.kernels.wavefront.tapes import WavefrontCaps
    monkeypatch.setattr(WavefrontCaps, "sized", classmethod(
        lambda cls, n: cls(n_iters=4)))
    run_core = wf._run_core

    def no_overflow(*a, **kw):
        host = run_core(*a, **kw)
        host["overflow"] = host["overflow"] & False
        return host
    monkeypatch.setattr(wf, "_run_core", no_overflow)


PLANTED = dict(FAULTS, overflow_ignored=_overflow_ignored)


@pytest.mark.parametrize("fault", ["overflow_ignored", "half_the_seeds"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_caught(name, fault, monkeypatch):
    PLANTED[fault](monkeypatch)
    assert run(name)["correct"] is False
