"""The one findings fold (`repro.core.findings`): its formulas on
hand-built lane totals, the float-order rule the engines' bitwise parity
rests on, and the goodput every path reports for one campaign."""
import dataclasses

import numpy as np
import pytest

from repro.control.policy import ControlConfig, ControlStats
from repro.core.batch import BatchedCampaignEngine
from repro.core.cluster import CampaignConfig, CampaignResult, ClusterSim
from repro.core.exclusion import ExclusionTracker
from repro.core.findings import LaneTotals, findings, fold_left
from repro.core.session import Session
from repro.ops.sweep import compute_findings

# the infra-band parity config, control on: urgent saves, degraded
# hours and drains all reach the goodput fold
CONTROL_CFG = CampaignConfig(
    duration_h=5 * 24.0, mtbf_h=30.0,
    kind_weights={"resource_exhaust": 12.0, "ctrl_blind": 30.0},
    telemetry_pad_metrics=0, telemetry_store=False,
    control=ControlConfig(drain=True))


@pytest.fixture(scope="module")
def engine_keys():
    """The key lists the batched engine returns, control off and on."""
    off = CampaignConfig(duration_h=24.0)
    on = dataclasses.replace(CONTROL_CFG, duration_h=6.0)
    return {ctl: list(BatchedCampaignEngine(cfg).run_findings([0])[0])
            for ctl, cfg in ((False, off), (True, on))}


EMPTY = LaneTotals(
    duration_h=24.0, run_h=0.0, lost_h=[], ckpt_events=0, save_s=18.0,
    urgent_h=0.0, degraded_h=[], n_failures=0, n_sessions=0,
    excl_counts=np.zeros(63, dtype=int), n_deliberate=0, n_intervals=0,
    f4=(0, 0, 0), gaps_min=[], auto_down_h=[], manual_down_h=[],
    infra_n=0, corr_n=0, switch_ids=(), control=None, drain_excl=0)

BUSY = LaneTotals(
    duration_h=100.0, run_h=80.0, lost_h=[1.0, 3.0], ckpt_events=36,
    save_s=100.0, urgent_h=0.5, degraded_h=[0.25, 0.25], n_failures=9,
    n_sessions=6, excl_counts=np.array([5, 4, 3, 2, 1] + [0] * 58),
    n_deliberate=3, n_intervals=15, f4=(4, 10, 1),
    gaps_min=np.array([10.0, 14.0, 12.0]), auto_down_h=[1.0, 3.0, 2.0],
    manual_down_h=[4.0, 6.0], infra_n=7, corr_n=3, switch_ids=[2, 5, 2],
    control=ControlStats().summarize([], 100.0), drain_excl=2)

EXPECTED = {
    "empty": {
        "occupancy": 0.0, "goodput": 0.0, "n_failures": 0.0,
        "n_sessions": 0.0, "ckpt_events": 0.0, "mean_lost_h": 0.0,
        "f3_top3_share": 0.0, "f3_deliberate_fraction": 0.0,
        "f4_n_chains": 0.0, "f4_n_attempts": 0.0, "f4_success_rate": 0.0,
        "f4_gap_median_min": None, "f4_auto_downtime_h": None,
        "f4_manual_downtime_h": None, "infra_n_events": 0.0,
        "infra_degraded_h": 0.0, "corr_n_events": 0.0,
        "corr_top_switch_share": 0.0},
    # goodput: 80 - 4 lost - 36 x 100 s - 0.5 urgent - 0.5 degraded
    "busy": {
        "occupancy": 0.8, "goodput": 0.74, "n_failures": 9.0,
        "n_sessions": 6.0, "ckpt_events": 36.0, "mean_lost_h": 2.0,
        "f3_top3_share": 0.8, "f3_deliberate_fraction": 0.2,
        "f4_n_chains": 4.0, "f4_n_attempts": 10.0, "f4_success_rate": 0.25,
        "f4_gap_median_min": 12.0, "f4_auto_downtime_h": 2.0,
        "f4_manual_downtime_h": 5.0, "infra_n_events": 7.0,
        "infra_degraded_h": 0.5, "corr_n_events": 3.0,
        "corr_top_switch_share": 2 / 3, "ctrl_drain_excl_events": 2.0},
}


@pytest.mark.parametrize("name,totals", [("empty", EMPTY), ("busy", BUSY)])
def test_fold_on_hand_built_totals(name, totals, engine_keys):
    out = findings(totals)
    assert list(out) == engine_keys[totals.control is not None]
    for key, want in EXPECTED[name].items():
        assert out[key] == want, key
        assert want is None or type(out[key]) is float, key


def _ten_short_sessions() -> CampaignResult:
    """Ten multi-node sessions of 0.1 h each, plus one single-node
    session that occupancy leaves out."""
    sessions = []
    for n_nodes in [60] * 10 + [1]:
        s = Session(task_name="t", n_nodes=n_nodes)
        s.started_h, s.ended_h = 0.0, 0.1
        sessions.append(s)
    return CampaignResult(
        sessions=sessions, chains=[], failures=[],
        exclusions=ExclusionTracker(63), store=None, downtimes=[],
        checkpoint_events=0, lost_hours=[], duration_h=1.0)


@pytest.mark.parametrize("hours", [
    CampaignResult.running_h,
    lambda r: r.training_occupancy() * r.duration_h,
    CampaignResult.goodput_h,
], ids=["running_h", "occupancy", "goodput_h"])
def test_running_hours_fold_left_to_right(hours):
    """0.1 added ten times left to right is 0.9999999999999999; builtin
    ``sum()`` (compensated on Python 3.12+) would give 1.0 and put the
    scalar path an ulp off the engines' running ``+=``."""
    res = _ten_short_sessions()
    assert fold_left([0.1] * 10) == 0.9999999999999999
    assert hours(res) == 0.9999999999999999


@pytest.mark.parametrize("seed", [0, 2])
def test_goodput_agrees_on_every_path(seed):
    """Seeds whose control-on campaigns carry both urgent saves and
    degraded hours: the scalar result, its findings and the batched
    engine report one goodput, bit for bit."""
    res = ClusterSim(dataclasses.replace(CONTROL_CFG, seed=seed)).run()
    assert res.control.urgent_save_h > 0.0 and res.degraded_hours
    scalar = compute_findings(res)
    batched = BatchedCampaignEngine(CONTROL_CFG).run_findings([seed])[0]
    assert res.goodput() == scalar["goodput"] == batched["goodput"]
    assert res.training_occupancy() == scalar["occupancy"] \
        == batched["occupancy"]
    assert list(scalar) == list(batched)
