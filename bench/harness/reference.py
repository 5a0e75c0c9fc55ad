"""The plain reference (``bench/refsim``) and the comparison that decides
``correct``.

The reference runs each lane through the frozen scalar simulator and its
findings fold; nothing of the program is imported.  A gap is relative
to the reference's value (absolute where that is 0); a field present on
one side only, or a ``None`` on one side only, is an infinite gap.
"""
from __future__ import annotations

import dataclasses
import json
import math
from functools import lru_cache

import numpy as np


@lru_cache(maxsize=64)
def _campaign(spec_json: str):
    from refsim.ops.scenario import Scenario
    spec = json.loads(spec_json)
    spec["detector_backend"] = "numpy"      # the reference's only backend
    return Scenario.from_dict(spec).to_campaign_config(0)


def campaign_config(spec: dict):
    return _campaign(json.dumps(spec, sort_keys=True))


def lane_findings(spec: dict, seed: int) -> dict:
    from refsim.core.cluster import ClusterSim
    from refsim.findings import compute_findings
    cfg = dataclasses.replace(campaign_config(spec), seed=int(seed))
    return compute_findings(ClusterSim(cfg).run())


def _rel(got: float, ref: float, scale: float) -> float:
    if got == ref:
        return 0.0
    if not (math.isfinite(got) and math.isfinite(ref)):
        return math.inf
    return abs(got - ref) / scale if scale else abs(got - ref)


def findings_gap(got: dict, ref: dict) -> float:
    """Widest relative gap over the fields of one lane's findings."""
    if set(got) != set(ref):
        return math.inf
    worst = 0.0
    for k, r in ref.items():
        g = got[k]
        if (g is None) != (r is None):
            return math.inf
        if r is not None:
            worst = max(worst, _rel(float(g), float(r), abs(float(r))))
    return worst


def as_control(findings, precision: str):
    """The control's answer: the reference's findings, each float field
    rounded to ``precision`` (``float32`` for a float64 configuration).
    The simulator's clock and the fold stay in float64; only the fields
    it hands back are rounded."""
    dtype = np.dtype(precision)

    def cast(v):
        if isinstance(v, dict):
            return {k: cast(x) for k, x in v.items()}
        if isinstance(v, float):
            return float(dtype.type(v))
        return v
    return cast(findings)


def control_precision(config: dict) -> str:
    """The nearest precision below the one the configuration states."""
    below = {"float64": "float32"}
    return below[config["precision"]]
