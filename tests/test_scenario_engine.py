"""Scenario engine + sweep runner: preset round-trips, config resolution,
sweep determinism, engine parity, batched telemetry, and the golden check
that the paper-faithful scenario still reproduces the seed's F3/F4
headline numbers."""
import numpy as np
import pytest

from repro.core.cluster import CampaignConfig, ClusterSim
from repro.core.retry import RetryPolicy, chain_stats
from repro.ops import (PRESETS, Scenario, SweepRunner, get_scenario,
                       list_scenarios, run_campaign)


# ---------------------------------------------------------------------------
# scenario spec
# ---------------------------------------------------------------------------

def test_presets_round_trip():
    for name, sc in PRESETS.items():
        assert sc.name == name
        rt = Scenario.from_dict(sc.to_dict())
        assert rt == sc, name


def test_get_scenario_isolated_and_presets_run_smoke():
    """Every preset survives canonicalize -> construct -> run without
    mutating the shared registry: get_scenario hands out an isolated
    deep copy (serialization round-trip), so callers tweaking nested
    config (kind_weights, control, storage) cannot corrupt PRESETS."""
    snapshot = {name: sc.to_dict() for name, sc in PRESETS.items()}
    for name in list_scenarios():
        sc = get_scenario(name)
        assert sc is not PRESETS[name], name
        smoke = sc.replace(duration_days=1.0, telemetry_pad_metrics=0)
        res = ClusterSim(smoke.to_campaign_config(seed=0)).run()
        assert res.goodput_h() >= 0.0, name
        if sc.kind_weights is not None:
            assert sc.kind_weights is not PRESETS[name].kind_weights, name
            sc.kind_weights["nvlink"] = 1e9          # poison the copy
    assert {n: sc.to_dict() for n, sc in PRESETS.items()} == snapshot


def test_preset_registry():
    assert "paper-faithful" in list_scenarios()
    with pytest.raises(KeyError, match="unknown scenario"):
        get_scenario("definitely-not-a-scenario")


def test_invalid_specs_rejected():
    with pytest.raises(ValueError):
        Scenario(name="bad", retry_policy="coin_flip")
    with pytest.raises(ValueError):
        Scenario(name="bad", checkpoint_strategy="hourly")


def test_paper_faithful_resolution():
    cfg = get_scenario("paper-faithful").to_campaign_config(seed=3)
    assert isinstance(cfg, CampaignConfig)
    assert (cfg.n_nodes, cfg.job_nodes) == (63, 60)
    assert cfg.duration_h == 73 * 24.0
    assert cfg.checkpoint_interval_h == pytest.approx(2.23)
    assert cfg.retry.policy is RetryPolicy.FIXED and cfg.retry.enabled
    assert cfg.seed == 3


def test_policy_and_scale_presets_resolve():
    assert not get_scenario("no-auto-retry").to_campaign_config().retry.enabled
    assert get_scenario("xid-branch").to_campaign_config().retry.policy \
        is RetryPolicy.XID_BRANCH
    assert get_scenario("smart-retry").to_campaign_config() \
        .retry.structural_stop
    big = get_scenario("big-cluster-252").to_campaign_config()
    assert (big.n_nodes, big.job_nodes) == (252, 240)
    assert big.mtbf_h == pytest.approx(56.2 * 63 / 252)


def test_young_daly_strategy_sets_optimal_interval():
    cfg = get_scenario("young-daly").to_campaign_config()
    assert cfg.checkpoint_interval_h == pytest.approx(44.9 / 60.0, rel=0.01)


def test_storage_model_drives_checkpoint_delta():
    sc = get_scenario("storage-degraded")
    base = sc.replace(storage_degradation=1.0)
    assert sc.resolve_delta_s() > 2 * base.resolve_delta_s()
    cfg = sc.to_campaign_config()
    assert cfg.checkpoint_save_s == pytest.approx(sc.resolve_delta_s())
    assert cfg.loading_time_h == pytest.approx(4.0 * 31.0 / 60.0)
    # Young-Daly stretches the interval to match the slower saves
    # (T_opt ~ sqrt(delta): 4x the service time -> ~2x the interval)
    assert cfg.checkpoint_interval_h > 1.5 * base.resolve_interval_h()


def test_kind_weights_tilt_mix():
    sc = get_scenario("flaky-fabric")
    evs = ClusterSim(sc.replace(duration_days=600)
                     .to_campaign_config(seed=0)).run().failures
    xids = [e.xid for e in evs if e.kind == "xid"]
    nvlink = sum(1 for x in xids if x in (145, 149))
    assert nvlink / max(len(xids), 1) > 0.5      # baseline mix: ~45%


# ---------------------------------------------------------------------------
# sweep runner
# ---------------------------------------------------------------------------

def _strip_wall(outcomes):
    return [(o.scenario, o.seed,
             {k: v for k, v in o.findings.items() if k != "wall_s"})
            for o in outcomes]


def test_sweep_deterministic_across_runs_and_executors():
    scs = [get_scenario(n).replace(duration_days=7.0)
           for n in ("paper-faithful", "no-auto-retry")]
    a = SweepRunner(scs, seeds=(0, 1), executor="serial").run()
    b = SweepRunner(scs, seeds=(0, 1), executor="serial").run()
    c = SweepRunner(scs, seeds=(0, 1), executor="thread").run()
    assert _strip_wall(a.outcomes) == _strip_wall(b.outcomes)
    assert _strip_wall(a.outcomes) == _strip_wall(c.outcomes)
    assert len(a.outcomes) == 4


def test_process_pool_gets_numpy_campaigns_only(monkeypatch):
    """A compiled detector backend calls JAX, so its campaigns run in the
    parent; the pool (spawned children) sees numpy-only campaigns."""
    import concurrent.futures

    submitted = []

    class RecordingPool(concurrent.futures.ThreadPoolExecutor):
        def __init__(self, max_workers=None, mp_context=None):
            assert mp_context.get_start_method() == "spawn"
            super().__init__(max_workers=max_workers)

        def submit(self, fn, spec, seed):
            submitted.append(spec["name"])
            return super().submit(fn, spec, seed)

    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor",
                        RecordingPool)
    scs = [get_scenario("paper-faithful").replace(duration_days=1.0),
           get_scenario("proactive").replace(
               name="proactive-xla", duration_days=0.5,
               telemetry_pad_metrics=0, detector_backend="xla")]
    res = SweepRunner(scs, seeds=(0,), executor="process").run()
    assert submitted == ["paper-faithful"]
    assert [o.scenario for o in res.outcomes] == [
        "paper-faithful", "proactive-xla"]


def test_sweep_aggregate_and_report(tmp_path):
    scs = [get_scenario(n).replace(duration_days=5.0)
           for n in ("paper-faithful", "smart-retry")]
    res = SweepRunner(scs, seeds=(0,), executor="serial").run()
    agg = res.aggregate()
    assert set(agg) == {"paper-faithful", "smart-retry"}
    assert 0.0 <= agg["paper-faithful"]["occupancy"] <= 1.0
    table = res.comparison_table()
    assert "paper-faithful" in table and "| paper" in table
    md = res.write(tmp_path / "sweep.md")
    assert (tmp_path / "sweep.md").read_text() == md
    assert "F1-F4 comparison" in md


def test_run_campaign_f1_subcampaign():
    sc = get_scenario("paper-faithful").replace(
        duration_days=2.0, telemetry_days=1.0, telemetry_pad_metrics=8)
    out = run_campaign(sc.to_dict(), seed=11)
    f = out["findings"]
    assert {"f1_detection_rate", "f1_fp_per_day"} <= set(f)
    assert f["f1_fp_per_day"] >= 0.0


def test_sweep_rejects_bad_inputs():
    with pytest.raises(ValueError, match="duplicate"):
        SweepRunner(["paper-faithful", "paper-faithful"])
    with pytest.raises(ValueError, match="executor"):
        SweepRunner(["paper-faithful"], executor="gpu")


# ---------------------------------------------------------------------------
# engines: parity + golden headline numbers
# ---------------------------------------------------------------------------

def test_event_and_tick_engines_agree():
    """Same seed -> identical failure schedule; campaign aggregates land
    within statistical tolerance of each other (the engines quantize event
    times differently but share the state machine)."""
    cfg = CampaignConfig(duration_h=14 * 24.0, seed=4)
    ev = ClusterSim(cfg).run()
    tk = ClusterSim(CampaignConfig(duration_h=14 * 24.0, seed=4,
                                   engine="tick")).run()
    assert [f.time_h for f in ev.failures] == [f.time_h for f in tk.failures]
    assert abs(ev.training_occupancy() - tk.training_occupancy()) < 0.05
    assert abs(ev.checkpoint_events - tk.checkpoint_events) \
        <= max(3, 0.1 * tk.checkpoint_events)
    assert len(ev.chains) == len(tk.chains)


def test_event_engine_campaign_invariants():
    res = ClusterSim(CampaignConfig(duration_h=21 * 24.0, seed=7)).run()
    for s in res.sessions:
        assert s.is_terminal and len(s.nodes) == 60
    for c in res.chains:
        for a in c.attempts[:-1]:
            assert a.end_h is not None
        for prev, nxt in zip(c.attempts, c.attempts[1:]):
            assert nxt.start_h >= (prev.end_h or prev.start_h) - 1e-9
    assert all(d["hours"] >= 0 for d in res.downtimes)
    assert res.checkpoint_events > 0


def test_golden_paper_faithful_f3_f4():
    """The refactored engine still reproduces the seed's F3/F4 headline
    numbers on the paper-faithful scenario (same bounds as the seed's
    system test, plus the F3 concentration check)."""
    sc = get_scenario("paper-faithful")
    succ = ch = 0
    gaps, top3 = [], []
    for seed in (0, 5):
        res = ClusterSim(sc.to_campaign_config(seed)).run()
        st = chain_stats(res.retry_chains())
        succ += st["success"]
        ch += st["n_chains"]
        gaps += [g for c in res.retry_chains() for g in c.gaps_min()]
        top3.append(res.exclusions.summary()["top3_share"])
    assert 0.1 < succ / max(ch, 1) < 0.8        # paper: 0.333
    assert abs(np.median(gaps) - 11.0) < 2.0    # paper: 11 min (IQR 10-11)
    assert np.mean(top3) > 0.4                  # paper F3: >50% on 3 nodes


# ---------------------------------------------------------------------------
# batched telemetry building blocks
# ---------------------------------------------------------------------------

def test_tick_batch_matches_signature_semantics():
    from repro.core.failures import FailureEvent
    from repro.telemetry.exporters import ExporterSuite, NodeStateBatch

    suite = ExporterSuite(8, seed=0, n_pad=4)
    T = 16
    ts = np.arange(T) * (30.0 / 3600.0)
    batch = NodeStateBatch.constant(T, 8, training=np.ones(8))
    ev = FailureEvent(time_h=float(ts[5]), node=3, kind="xid", xid=145)
    snap = suite.tick_batch(ts, batch, [(5, ev)])
    assert snap["node_intr_total"].shape == (T, 8)
    # NVLink signature only on the pinned tick (paper Fig 2)
    assert snap["node_intr_total"][5, 3] < 150e3
    assert snap["node_procs_running"][5, 3] == 0
    assert snap["DCGM_FI_DEV_XID_ERRORS"][5, 3] == 145
    assert np.all(snap["DCGM_FI_DEV_XID_ERRORS"][:5] == 0)
    healthy = np.delete(snap["node_intr_total"][5], 3)
    assert np.all(healthy > 250e3)
    # persistent counters are monotone within the batch and persist across
    # calls
    corr = snap["DCGM_FI_DEV_ROW_REMAP_CORRECTABLE"]
    assert np.all(np.diff(corr, axis=0) >= 0)
    snap2 = suite.tick_batch(ts + 1.0, batch)
    assert np.all(snap2["DCGM_FI_DEV_ROW_REMAP_CORRECTABLE"][0]
                  >= corr[-1])


def test_tick_batch_unreachable_zeroes_node():
    from repro.core.failures import FailureEvent
    from repro.telemetry.exporters import ExporterSuite, NodeStateBatch

    suite = ExporterSuite(4, seed=1, n_pad=0)
    batch = NodeStateBatch.constant(3, 4, training=np.ones(4))
    ev = FailureEvent(time_h=0.0, node=2, kind="unreachable")
    snap = suite.tick_batch(np.array([0.0, 0.01, 0.02]), batch, [(0, ev)])
    assert snap["DCGM_FI_DEV_GPU_UTIL"][0, 2] == 0.0
    assert snap["backendai_agent_heartbeat_age_s"][0, 2] == 600.0


def test_store_batch_and_single_append_interleave():
    from repro.telemetry.registry import TimeSeriesStore

    store = TimeSeriesStore(4)
    store.append(0.0, {"m": np.arange(4.0)})
    store.append_batch(np.array([1.0, 2.0]),
                       {"m": np.arange(8.0).reshape(2, 4)})
    store.append(3.0, {"m": np.full(4, 9.0)})
    s = store.series("m")
    assert s.shape == (4, 4)
    np.testing.assert_array_equal(s[0], np.arange(4.0))
    np.testing.assert_array_equal(s[3], np.full(4, 9.0))
    w = store.window("m", 1.0, 3.0)
    assert w.shape == (2, 4)
    np.testing.assert_array_equal(store.times(), [0.0, 1.0, 2.0, 3.0])
    assert store.nbytes() > 0


def test_event_engine_telemetry_feeds_detector():
    """End-to-end: batched telemetry from the event engine is scannable and
    the injected XID signatures alarm on the right node."""
    from repro.core.precursor import DetectorConfig, PrecursorDetector

    res = ClusterSim(CampaignConfig(duration_h=36.0, telemetry=True,
                                    telemetry_pad_metrics=16,
                                    seed=11)).run()
    assert len(res.store.ticks) == int(36.0 * 3600 / 30)
    alarms = PrecursorDetector(DetectorConfig()).scan(res.store)
    xid_fails = [f for f in res.failures if f.kind == "xid"]
    if xid_fails:                                  # seed 11: present
        hit_nodes = {a.node for a in alarms}
        assert any(f.node in hit_nodes for f in xid_fails)
