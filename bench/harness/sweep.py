"""Closed-loop Monte Carlo sweep: one ``run_findings_stacked`` call over
every (variant, lane seed) of the cell per pass, passes back to back.

A mix names its lane seeds as a block, ``first_lane_seed`` onwards, as a
sweep asks for them (the what-if service and ``SweepRunner`` run seeds
``0..n-1``).  ``--seed`` orders the variants and the lanes of the call,
and draws the lanes the check compares.  The device program's event
tables are as wide as the most failures among the lanes, so every seed
then runs one set of programs, which set-up compiles once per checkout,
on the same work.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from harness import reference
from harness.cell import Cell, variant_specs


def deployment_spec(cell: Cell, spec: dict) -> dict:
    """A variant's scenario spec with the configuration's cluster and
    campaign length applied."""
    out = dict(spec)
    for k in ("n_nodes", "job_nodes", "duration_days"):
        if k in cell.config:
            out[k] = cell.config[k]
    return out


@dataclass
class SweepRun:
    specs: Dict[str, dict]
    seeds: List[int]
    passes: List[list] = field(default_factory=list)
    window_s: float = 0.0
    lane_days: float = 0.0
    refs: Dict[tuple, dict] = field(default_factory=dict)


def build(cell: Cell, seed: int) -> SweepRun:
    """The cell's variants and lane seeds, in the order ``seed`` gives."""
    t = cell.traffic
    rng = np.random.default_rng([int(seed), 0x5EED])
    specs = list(variant_specs(cell).items())
    first = int(t["first_lane_seed"])
    seeds = first + rng.permutation(int(t["lanes_per_variant"]))
    return SweepRun(
        specs={k: deployment_spec(cell, v)
               for k, v in (specs[i] for i in rng.permutation(len(specs)))},
        seeds=[int(s) for s in seeds])


def program_configs(run: SweepRun) -> list:
    from repro.ops.scenario import Scenario
    return [Scenario.from_dict(dict(s)).to_campaign_config(0)
            for s in run.specs.values()]


def one_pass(run: SweepRun, cfgs: list) -> list:
    from repro.core.batch import run_findings_stacked
    out = run_findings_stacked(cfgs, run.seeds)
    run.passes.append([[by_seed[s] for s in run.seeds] for by_seed in out])
    return out


def warm(run: SweepRun, cfgs: list) -> None:
    """The cell's first pass: it compiles (or reads back) every program
    the later passes use, on the same lanes.  Its findings are checked
    with the window's."""
    one_pass(run, cfgs)


def window(run: SweepRun, cfgs: list, seconds: float) -> None:
    """Passes back to back until ``seconds`` have passed; the pass in
    flight is finished and the window ends with it."""
    first = len(run.passes)
    t0 = time.perf_counter()
    while True:
        one_pass(run, cfgs)
        if time.perf_counter() - t0 >= seconds:
            break
    run.window_s = time.perf_counter() - t0
    days = float(next(iter(run.specs.values()))["duration_days"])
    n_passes = len(run.passes) - first
    run.lane_days = n_passes * len(run.specs) * len(run.seeds) * days


def sample(run: SweepRun, seed: int, k: int) -> List[tuple]:
    """(pass, variant, lane) triples to check, drawn from the seed; the
    lane with the most failures (the longest campaign) of every variant,
    in the last pass, is always among them."""
    rng = np.random.default_rng([int(seed), 0xC4EC])
    V, S, P = len(run.specs), len(run.seeds), len(run.passes)
    picks = set()
    for v, by_lane in enumerate(run.passes[-1]):
        n_fail = [f["n_failures"] for f in by_lane]
        picks.add((P - 1, v, int(np.argmax(n_fail))))
    for idx in rng.permutation(P * V * S):
        if len(picks) >= max(k, V):
            break
        p, rest = divmod(int(idx), V * S)
        picks.add((p, *divmod(rest, S)))
    return sorted(picks)


def check(run: SweepRun, seed: int, k: int, control: str = None) -> dict:
    """Widest relative gap of a sampled lane's findings from the plain
    reference's (``control``: the reference itself in the program's
    place, computed as the control says)."""
    names = list(run.specs)
    picks = sample(run, seed, k)
    ref = run.refs                      # the control reuses the reference
    for v, lane in sorted({(v, lane) for _, v, lane in picks}):
        if (v, lane) not in ref:
            ref[(v, lane)] = reference.lane_findings(
                run.specs[names[v]], run.seeds[lane])
    worst, n = 0.0, 0
    for p, v, lane in picks:
        got = run.passes[p][v][lane]
        if control is not None:
            got = reference.as_control(ref[(v, lane)], control)
        worst = max(worst, reference.findings_gap(got, ref[(v, lane)]))
        n += 1
    return {"findings_gap": worst, "lanes_checked": n}
