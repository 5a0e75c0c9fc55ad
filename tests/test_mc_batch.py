"""Seed-batched Monte Carlo campaign engine: the parity contract.

`BatchedCampaignEngine.run(seeds)[i]` must reproduce
`ClusterSim(replace(cfg, seed=seeds[i])).run()` field-for-field (sessions,
chains, failures, exclusion intervals, downtimes, lost-work hours,
checkpoint counts, control-plane ledger — everything except the
process-global ``session_id`` counter), and `run_findings` must match
`compute_findings` of the scalar results value-for-value.  The property
is exercised across retry policies, the proactive control plane
(urgent saves + counterfactual ledger) and executed predictive drains.
"""
import dataclasses
import sys

import numpy as np
import pytest

from repro import tracing
from repro.control.policy import ControlConfig
from repro.control.streaming import StreamingDetector
from repro.core import batch as batch_mod
from repro.core.batch import BatchedCampaignEngine
from repro.core.cluster import TICK_H, CampaignConfig, ClusterSim
from repro.core.failures import FailureEvent, FailureInjector
from repro.core.precursor import DetectorConfig
from repro.core.retry import chain_stats
from repro.ops import SweepRunner, get_scenario
from repro.ops.sweep import compute_findings
from repro.telemetry.exporters import ExporterSuite, NodeStateBatch


def assert_result_parity(ref, got, tag=""):
    """Field-for-field CampaignResult comparison (session_id exempt)."""
    assert len(ref.sessions) == len(got.sessions), tag
    for i, (a, b) in enumerate(zip(ref.sessions, got.sessions)):
        for f in ("task_name", "n_nodes", "state", "nodes", "created_h",
                  "started_h", "ended_h", "checkpoint_step", "error",
                  "history"):
            assert getattr(a, f) == getattr(b, f), (tag, i, f)
    assert len(ref.chains) == len(got.chains), tag
    for i, (a, b) in enumerate(zip(ref.chains, got.chains)):
        assert a.task_name == b.task_name, (tag, i)
        assert a.stopped_reason == b.stopped_reason, (tag, i)
        assert a.attempts == b.attempts, (tag, i)
    assert ref.failures == got.failures, tag
    assert ref.exclusions.intervals == got.exclusions.intervals, tag
    assert ref.downtimes == got.downtimes, tag
    assert ref.checkpoint_events == got.checkpoint_events, tag
    assert ref.lost_hours == got.lost_hours, tag
    assert ref.degraded_hours == got.degraded_hours, tag
    assert ref.duration_h == got.duration_h, tag
    assert ref.checkpoint_save_s == got.checkpoint_save_s, tag
    assert (ref.control is None) == (got.control is None), tag
    if ref.control is not None:
        a, b = ref.control, got.control
        assert a.alarms == b.alarms, tag
        assert a.urgent_saves == b.urgent_saves, tag
        assert a.drains == b.drains, tag
        assert a.urgent_save_h == b.urgent_save_h, tag
        assert a.lost_work_avoided_h == b.lost_work_avoided_h, tag
        assert a.failures_on_drained_node == b.failures_on_drained_node, tag
        assert a.throttles == b.throttles, tag
        assert a.alarms_deferred == b.alarms_deferred, tag


def scalar_results(cfg, seeds):
    return [ClusterSim(dataclasses.replace(cfg, seed=s)).run()
            for s in seeds]


# ---------------------------------------------------------------------------
# failure schedule batching
# ---------------------------------------------------------------------------

def test_sample_batch_matches_per_seed_sample():
    inj = FailureInjector(mtbf_h=40.0, kind_weights={"nvlink": 2.0})
    seeds = [0, 3, 11, 42]
    batch = inj.sample_batch(30 * 24.0, seeds)
    for i, seed in enumerate(seeds):
        solo = dataclasses.replace(inj, seed=seed).sample(30 * 24.0)
        assert batch.events(i) == solo, seed
        assert batch.count(i) == len(solo)
        hw = batch.hardware[batch.offsets[i]:batch.offsets[i + 1]]
        assert [bool(h) for h in hw] == [e.is_hardware for e in solo]


def test_sample_batch_empty_horizon():
    inj = FailureInjector()
    batch = inj.sample_batch(0.01, [0, 1])
    assert batch.count(0) == 0 and batch.events(1) == []


# ---------------------------------------------------------------------------
# reactive parity (the benchmark's configuration), >= 8 seeds
# ---------------------------------------------------------------------------

def test_reactive_parity_8_seeds():
    cfg = CampaignConfig(duration_h=15 * 24.0)
    seeds = list(range(8))
    batched = BatchedCampaignEngine(cfg).run(seeds)
    findings = BatchedCampaignEngine(cfg).run_findings(seeds)
    for i, (seed, ref) in enumerate(zip(seeds, scalar_results(cfg, seeds))):
        assert_result_parity(ref, batched[i], f"seed{seed}")
        # retry-chain stats are identical down to the float
        assert chain_stats(ref.retry_chains()) == \
            chain_stats(batched[i].retry_chains()), seed
        assert findings[i] == compute_findings(ref), seed


def test_parity_across_retry_policies():
    """Non-FIXED retry paths (exp backoff, structural stop) stay exact."""
    seeds = [1, 5, 9]
    for preset in ("exp-backoff", "smart-retry", "no-auto-retry"):
        sc = get_scenario(preset).replace(duration_days=12.0)
        cfg = sc.to_campaign_config(0)
        batched = BatchedCampaignEngine(cfg).run(seeds)
        for i, seed in enumerate(seeds):
            ref = ClusterSim(sc.to_campaign_config(seed)).run()
            assert_result_parity(ref, batched[i], f"{preset}-seed{seed}")


def test_parity_storage_fabric_resolution():
    """Fabric-resolved checkpoint timing flows through the batched path."""
    sc = get_scenario("storage-fabric").replace(duration_days=10.0)
    cfg = sc.to_campaign_config(0)
    seeds = [0, 4]
    batched = BatchedCampaignEngine(cfg).run(seeds)
    for i, seed in enumerate(seeds):
        ref = ClusterSim(sc.to_campaign_config(seed)).run()
        assert_result_parity(ref, batched[i], f"fabric-seed{seed}")


# ---------------------------------------------------------------------------
# proactive parity: urgent saves, ledger, drains (>= 8 seeds combined)
# ---------------------------------------------------------------------------

def test_proactive_parity_with_ledger():
    sc = get_scenario("proactive").replace(duration_days=2.0,
                                           telemetry_pad_metrics=0)
    cfg = sc.to_campaign_config(0)
    seeds = list(range(8))
    batched = BatchedCampaignEngine(cfg).run(seeds)
    findings = BatchedCampaignEngine(cfg).run_findings(seeds)
    n_alarms = 0
    for i, seed in enumerate(seeds):
        ref = ClusterSim(sc.to_campaign_config(seed)).run()
        assert_result_parity(ref, batched[i], f"proactive-seed{seed}")
        # the counterfactual ledger summarizes identically
        assert ref.control.summarize(ref.failures, ref.duration_h) == \
            batched[i].control.summarize(batched[i].failures,
                                         batched[i].duration_h), seed
        assert findings[i] == compute_findings(ref), seed
        n_alarms += len(ref.control.alarms)
    assert n_alarms > 0, "window produced no alarms — parity untested"


def test_drain_parity():
    """Executed predictive drains (span truncation, graceful handoff,
    exclusion attribution) reproduce exactly."""
    cfg = CampaignConfig(duration_h=7 * 24.0, telemetry_pad_metrics=0,
                         telemetry_store=False,
                         control=ControlConfig(drain=True))
    seeds = [25, 7]
    batched = BatchedCampaignEngine(cfg).run(seeds)
    n_drains = 0
    for i, seed in enumerate(seeds):
        ref = ClusterSim(dataclasses.replace(cfg, seed=seed)).run()
        assert_result_parity(ref, batched[i], f"drain-seed{seed}")
        n_drains += ref.control.n_drains
    assert n_drains > 0, "window executed no drains — parity untested"


# ---------------------------------------------------------------------------
# concurrent telemetry rounds: each seed's chunks stay its own
# ---------------------------------------------------------------------------

def _concurrent_case(case):
    if case == "proactive":
        cfg = get_scenario("proactive").replace(
            duration_days=2.0, telemetry_pad_metrics=16).to_campaign_config(0)
        return cfg, [0, 1, 2, 3]
    # seed 35 drains at 45.5 h, in a round shared with another seed, and
    # the rest of that `_emit` call goes on without it
    cfg = CampaignConfig(duration_h=2 * 24.0, telemetry_pad_metrics=0,
                         telemetry_store=False,
                         control=ControlConfig(drain=True))
    return cfg, [35, 0, 1, 2]


@pytest.mark.parametrize("case", ["proactive", "drain"])
def test_concurrent_rounds_match_single_seed_runs(case):
    """Four seeds whose chunks are generated together on the pool give
    the alarms, results and findings of four single-seed runs of the
    same engine, whose chunks all run inline."""
    cfg, seeds = _concurrent_case(case)
    tracing.enable()
    tracing.reset()
    try:
        batched = BatchedCampaignEngine(cfg).run(seeds)
        counters = tracing.snapshot()["counters"]
    finally:
        tracing.disable()
        tracing.reset()
    # some round generated several chunks at once
    assert counters["engine.telemetry_path_ticks"] \
        < counters["engine.telemetry_ticks"]
    findings = BatchedCampaignEngine(cfg).run_findings(seeds)
    for i, seed in enumerate(seeds):
        alone = BatchedCampaignEngine(cfg).run([seed])[0]
        assert alone.control.alarms == batched[i].control.alarms, seed
        assert_result_parity(alone, batched[i], f"{case}-seed{seed}")
        assert findings[i] == \
            BatchedCampaignEngine(cfg).run_findings([seed])[0], seed
    if case == "drain":
        assert sum(r.control.n_drains for r in batched) > 0
    else:
        assert sum(len(r.control.alarms) for r in batched) > 0


def _exporter_rounds(seed):
    """Two chunks of one exporter's telemetry, with precursor and XID
    rows, so every draw family and the remap counters are exercised."""
    n = 16
    exp = ExporterSuite(n, seed=seed, n_pad=32)
    exp.begin_gradual_precursor(3, 0.1, until_h=1.5)
    jobs = []
    for k0, k1 in ((0, 150), (150, 300 + 10 * seed)):
        ts = np.arange(k0, k1) * TICK_H
        training = np.ones(n)
        training[5] = 0.0
        batch = NodeStateBatch.constant(len(ts), n, training=training)
        rows = [(7, FailureEvent(time_h=float(ts[7]), node=3, kind="xid",
                                 xid=94)),
                (20, FailureEvent(time_h=float(ts[20]), node=9, kind="xid",
                                  xid=79))]
        jobs.append((ts, batch, rows))
    return exp, jobs


def test_pool_chunks_bit_equal_to_serial_calls():
    """Four `ExporterSuite`s on the pool, round after round, give arrays
    bit-equal to serial calls and leave the same remap counters."""
    serial, pooled = [], []
    for seed in range(4):
        exp, jobs = _exporter_rounds(seed)
        serial.append([exp.tick_batch(*job) for job in jobs])
        serial[-1].append((exp.remap_corr, exp.remap_uncorr))
    fresh = [_exporter_rounds(seed) for seed in range(4)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for r in range(2):
            pooled.append(batch_mod._tick_chunks(
                [(exp, *jobs[r]) for exp, jobs in fresh]))
    finally:
        sys.setswitchinterval(switch)
    for i, (exp, _) in enumerate(fresh):
        for r in range(2):
            want, got = serial[i][r], pooled[r][i]
            assert want.keys() == got.keys()
            for key in want:
                assert want[key].dtype == got[key].dtype, key
                assert np.array_equal(want[key], got[key]), (i, r, key)
        assert np.array_equal(exp.remap_corr, serial[i][2][0]), i
        assert np.array_equal(exp.remap_uncorr, serial[i][2][1]), i
        assert exp.remap_corr.sum() > 0 and exp.remap_uncorr.sum() > 0


def test_pool_chunk_error_reaches_the_caller():
    class Broken:
        def tick_batch(self, ts, batch, rows):
            raise RuntimeError("exporter failed")

    exp, jobs = _exporter_rounds(0)
    with pytest.raises(RuntimeError, match="exporter failed"):
        batch_mod._tick_chunks([(exp, *jobs[0]), (Broken(), *jobs[0])])


def test_infra_band_parity_8_seeds():
    """The infra fault band (degradation windows + ledger, escalation
    crashes, blind-window deferral and replay, net throttles, predictive
    drains) reproduces field-for-field across 8 seeds — the weights are
    tilted so every new mechanism actually fires somewhere in the batch."""
    cfg = CampaignConfig(
        duration_h=5 * 24.0, mtbf_h=30.0,
        kind_weights={"resource_exhaust": 12.0, "ctrl_blind": 30.0},
        telemetry_pad_metrics=0, telemetry_store=False,
        control=ControlConfig(drain=True))
    seeds = list(range(8))
    batched = BatchedCampaignEngine(cfg).run(seeds)
    findings = BatchedCampaignEngine(cfg).run_findings(seeds)
    cov = dict(deferred=0, degraded=0, esc_fails=0, drains=0)
    for i, seed in enumerate(seeds):
        ref = ClusterSim(dataclasses.replace(cfg, seed=seed)).run()
        assert_result_parity(ref, batched[i], f"infra-seed{seed}")
        assert ref.control.summarize(ref.failures, ref.duration_h) == \
            batched[i].control.summarize(batched[i].failures,
                                         batched[i].duration_h), seed
        assert findings[i] == compute_findings(ref), seed
        cov["deferred"] += ref.control.alarms_deferred
        cov["degraded"] += len(ref.degraded_hours)
        cov["esc_fails"] += sum(
            1 for s in ref.sessions
            if s.error and "resource_exhaust" in s.error)
        cov["drains"] += ref.control.n_drains
    # the parity claim is only as strong as what the batch exercised
    for k, v in cov.items():
        assert v > 0, f"no {k} in any seed — infra parity untested"


def test_degraded_hours_reduce_goodput():
    """A degrade-band window overlapping a RUNNING span must show up in
    the ledger and be charged against goodput exactly once, after every
    other deduction (the documented fold order)."""
    kw = {"net_degrade": 8.0, "resource_exhaust": 8.0}
    infra = CampaignConfig(duration_h=4 * 24.0, seed=2, kind_weights=kw)
    b = ClusterSim(infra).run()
    assert b.degraded_hours, "no degradation window landed on the gang"
    assert all(d > 0 for d in b.degraded_hours)
    assert b.goodput_h() == pytest.approx(
        sum(s.elapsed_running_h(b.duration_h) for s in b.sessions
            if s.n_nodes > 1)
        - float(np.sum(b.lost_hours))
        - b.checkpoint_events * b.checkpoint_save_s / 3600.0
        - float(np.sum(b.degraded_hours)))


def test_engine_rejects_tick_engine():
    with pytest.raises(ValueError, match="event engine"):
        BatchedCampaignEngine(CampaignConfig(engine="tick"))


# ---------------------------------------------------------------------------
# detector seed axis
# ---------------------------------------------------------------------------

def test_push_group_matches_per_seed_push():
    rng0 = np.random.default_rng(7)
    T, n, S = 30, 12, 4
    cfg = DetectorConfig()

    def span(r):
        v = {"DCGM_FI_DEV_GPU_UTIL": 99.0 + r.normal(0, 0.3, (T, n))}
        for m in range(10):
            a = 50 + r.normal(0, 1, (T, n))
            if r.random() < 0.6:
                a[T // 2:, 2] += 80.0
            v[f"m{m}"] = a
        return v

    vals = [span(np.random.default_rng(100 + i)) for i in range(S)]
    ts = [np.arange(T) * 30 / 3600 + i for i in range(S)]
    ref = []
    for i in range(S):
        det = StreamingDetector(cfg)
        out = []
        for a in range(0, T, 7):
            out += det.push(ts[i][a:a + 7],
                            {k: v[a:a + 7] for k, v in vals[i].items()})
        ref.append((out, det._streak.copy(), det._tick_offset))
    dets = [StreamingDetector(cfg) for _ in range(S)]
    outs = [[] for _ in range(S)]
    for a in range(0, T, 7):
        got = StreamingDetector.push_group(
            dets, [ts[i][a:a + 7] for i in range(S)],
            [{k: v[a:a + 7] for k, v in vals[i].items()}
             for i in range(S)])
        for i in range(S):
            outs[i] += got[i]
    assert sum(len(o) for o in outs) > 0
    for i in range(S):
        assert outs[i] == ref[i][0], i
        assert np.array_equal(dets[i]._streak, ref[i][1])
        assert dets[i]._tick_offset == ref[i][2]
        assert dets[i].n_alarms == len(ref[i][0])


# ---------------------------------------------------------------------------
# SweepRunner Monte Carlo mode (the tier-1 batched-path selection)
# ---------------------------------------------------------------------------

def test_sweep_runner_mc_mode_matches_serial():
    sc = get_scenario("paper-faithful").replace(duration_days=10.0)
    mc = SweepRunner([sc], mc_seeds=10).run()
    serial = SweepRunner([sc], seeds=range(10), executor="serial").run()
    assert mc.seeds == list(range(10))
    for a, b in zip(mc.outcomes, serial.outcomes):
        fa = {k: v for k, v in a.findings.items() if k != "wall_s"}
        fb = {k: v for k, v in b.findings.items() if k != "wall_s"}
        assert a.seed == b.seed and fa == fb, a.seed


def test_sweep_runner_mc_distribution_report():
    sc = get_scenario("paper-faithful").replace(duration_days=8.0)
    res = SweepRunner([sc], mc_seeds=10).run()
    dist = res.distribution()[sc.name]
    g = dist["goodput"]
    assert g["n"] == 10
    assert g["q25"] <= g["median"] <= g["q75"]
    assert g["ci_lo"] <= g["mean"] <= g["ci_hi"]
    md = res.to_markdown()
    assert "## Distributional findings (10 seeds)" in md
    assert "±" in md and "F4 succ %" in md
    # below the threshold the section stays out of the report
    few = SweepRunner([sc], seeds=(0, 1), executor="serial").run()
    assert "Distributional findings" not in few.to_markdown()


# ---------------------------------------------------------------------------
# compiled wavefront (XLA/Pallas device core): bitwise findings parity
# ---------------------------------------------------------------------------

def _wavefront_ops():
    pytest.importorskip("jax")
    from repro.kernels.wavefront import ops
    return ops


def test_compiled_wavefront_reactive_parity_8_seeds():
    """The jitted while-loop core reproduces the scalar findings dict
    bitwise (every float, every median, every None) on both compiled
    backends — the benchmark configuration, 8 seeds."""
    _wavefront_ops()
    cfg = CampaignConfig(duration_h=15 * 24.0)
    seeds = list(range(8))
    ref = [compute_findings(r) for r in scalar_results(cfg, seeds)]
    for backend in ("xla", "pallas"):
        eng = BatchedCampaignEngine(cfg, wavefront_backend=backend)
        got = eng.run_findings(seeds)
        for i, seed in enumerate(seeds):
            assert got[i] == ref[i], (backend, seed)


def test_compiled_wavefront_retry_presets_parity():
    """Non-FIXED retry paths (exp backoff, structural stop, no-retry)
    stay exact through the device core."""
    _wavefront_ops()
    seeds = [1, 5, 9, 13]
    for preset in ("exp-backoff", "smart-retry", "no-auto-retry"):
        sc = get_scenario(preset).replace(duration_days=12.0)
        cfg = sc.to_campaign_config(0)
        got = BatchedCampaignEngine(
            cfg, wavefront_backend="xla").run_findings(seeds)
        for i, seed in enumerate(seeds):
            ref = ClusterSim(sc.to_campaign_config(seed)).run()
            assert got[i] == compute_findings(ref), (preset, seed)


def test_compiled_wavefront_infra_band_parity():
    """Control-free infra fault band: degradation windows, escalation
    crashes and fail-slow isolation all fold identically on device."""
    _wavefront_ops()
    cfg = CampaignConfig(
        duration_h=5 * 24.0, mtbf_h=30.0,
        kind_weights={"resource_exhaust": 10.0, "net_degrade": 8.0})
    seeds = list(range(8))
    got = BatchedCampaignEngine(
        cfg, wavefront_backend="xla").run_findings(seeds)
    refs = scalar_results(cfg, seeds)
    for i, seed in enumerate(seeds):
        assert got[i] == compute_findings(refs[i]), seed
    # the claim is only as strong as what the band exercised
    assert any(r.degraded_hours for r in refs), "no degradation landed"
    assert any("resource_exhaust" in (s.error or "")
               for r in refs for s in r.sessions), "no escalation crash"


def _f64_bits(x):
    return np.asarray(x, dtype=np.float64).view(np.int64)


def test_device_add_is_ieee_round_half_even():
    """The device core adds doubles as int64 bit patterns (a TPU does not
    hold a double as f64 bit for bit); the result equals numpy's add bit for
    bit: clocks plus delays, exact ties, carries into the next binade,
    subnormals, zeros and +inf."""
    import jax
    from repro.kernels.wavefront.ref import f64_add
    rng = np.random.default_rng(0)
    n = 100_000
    scale = 2.0 ** rng.integers(-60, 60, n)
    a = np.concatenate([
        rng.uniform(0, 2000, n), rng.exponential(3.0, n),
        rng.uniform(0, 1, n) * scale,
        [0.0, 0.0, 1.0, 1.0, 1.0, 2.0 - 2.0 ** -52, 5e-324, np.inf, 3.0]])
    b = np.concatenate([
        rng.exponential(1.0, n), rng.uniform(0, 1e-9, n),
        rng.uniform(0, 1, n) * scale[::-1],
        [0.0, 1e-12, 2.0 ** -53, 3 * 2.0 ** -53, 2.0 ** -52, 2.0 ** -52,
         5e-324, 1.0, np.inf]])
    with jax.enable_x64(True):
        got = np.asarray(f64_add(_f64_bits(a), _f64_bits(b)))
        swapped = np.asarray(f64_add(_f64_bits(b), _f64_bits(a)))
    assert np.array_equal(got, _f64_bits(a + b))
    assert np.array_equal(swapped, got)


def test_device_night_matches_manual_delay_rule():
    """The off-hours test on bit patterns agrees with the scalar engine's
    ``t % 24`` / ``t // 24`` rule, including clocks exactly on and one
    ulp either side of the 8:00 and 20:00 boundaries and the weekend."""
    import jax
    from repro.kernels.wavefront.ref import f64_night
    rng = np.random.default_rng(1)
    edges = np.array([24.0 * d + h for d in range(14)
                      for h in (0.0, 8.0, 20.0, 21.0)])
    t = np.concatenate([
        rng.uniform(0, 2000, 50_000), np.arange(0, 400, 0.25), edges,
        np.nextafter(edges, np.inf), np.nextafter(edges[1:], 0.0),
        [0.0, 5e-324, 1e-12, 0.5, 1.0]])
    expect = np.array([(int(x // 24.0) % 7 >= 5) or (x % 24.0 < 8)
                       or (x % 24.0 > 20) for x in t])
    with jax.enable_x64(True):
        got = np.asarray(f64_night(_f64_bits(t)))
    assert np.array_equal(got, expect)


def test_compiled_backend_rejects_ineligible_config():
    """Explicitly forcing the device core on a control-plane config is a
    hard error; auto silently stays on the numpy wavefront."""
    ops = _wavefront_ops()
    sc = get_scenario("proactive").replace(duration_days=2.0,
                                           telemetry_pad_metrics=0)
    cfg = sc.to_campaign_config(0)
    assert not ops.compiled_eligible(cfg)
    with pytest.raises(ValueError, match="control-free campaign"):
        BatchedCampaignEngine(
            cfg, wavefront_backend="xla").run_findings([0, 1])
    assert ops.resolve_wavefront_backend("auto", cfg, 512) == "numpy"
    with pytest.raises(ValueError, match="unknown wavefront backend"):
        BatchedCampaignEngine(cfg, wavefront_backend="cuda")


def test_compiled_auto_floor():
    """auto routes small batches to numpy (compile cost dominates) and
    large eligible batches to the device core; explicit backends ignore
    the floor."""
    ops = _wavefront_ops()
    from repro.kernels.common import WAVEFRONT_MIN_SEEDS
    cfg = CampaignConfig(duration_h=24.0)
    assert ops.compiled_eligible(cfg)
    assert ops.resolve_wavefront_backend(
        "auto", cfg, WAVEFRONT_MIN_SEEDS - 1) == "numpy"
    assert ops.resolve_wavefront_backend(
        "auto", cfg, WAVEFRONT_MIN_SEEDS) == "xla"
    assert ops.resolve_wavefront_backend("xla", cfg, 2) == "xla"
    assert ops.resolve_wavefront_backend("numpy", cfg, 4096) == "numpy"


def test_run_findings_grid_matches_single_config_runs():
    """The dense grid pass (every config x seed as one lane axis) returns
    exactly what per-config compiled runs return."""
    ops = _wavefront_ops()
    cfgs = [CampaignConfig(duration_h=6 * 24.0),
            CampaignConfig(duration_h=6 * 24.0, mtbf_h=30.0,
                           kind_weights={"net_degrade": 6.0})]
    seeds = [0, 1, 2, 3]
    grid = ops.run_findings_grid(cfgs, seeds, backend="xla")
    for g, cfg in enumerate(cfgs):
        solo = ops.run_findings_compiled(cfg, seeds, backend="xla")
        for i, seed in enumerate(seeds):
            assert grid[g][i] == solo[i], (g, seed)
            assert grid[g][i] == compute_findings(
                ClusterSim(dataclasses.replace(cfg, seed=seed)).run()), \
                (g, seed)


def test_sweep_runner_grid_pass_matches_numpy():
    """SweepRunner's whole-sweep grid pass feeds the same findings into
    the outcome rows as the pure-numpy path (control scenarios fall back
    transparently)."""
    _wavefront_ops()
    scs = [get_scenario("paper-faithful").replace(duration_days=6.0),
           get_scenario("smart-retry").replace(duration_days=6.0)]
    dev = SweepRunner(scs, mc_seeds=8, wavefront_backend="xla").run()
    ref = SweepRunner(scs, mc_seeds=8, wavefront_backend="numpy").run()
    assert len(dev.outcomes) == len(ref.outcomes) == 16
    for a, b in zip(dev.outcomes, ref.outcomes):
        fa = {k: v for k, v in a.findings.items() if k != "wall_s"}
        fb = {k: v for k, v in b.findings.items() if k != "wall_s"}
        assert a.seed == b.seed and fa == fb, (a.scenario, a.seed)


def test_sweep_runner_mc_storage_fabric_f2_columns():
    sc = get_scenario("storage-fabric").replace(duration_days=5.0)
    res = SweepRunner([sc], mc_seeds=8).run()
    for o in res.outcomes:
        assert o.findings["f2_load_util"] == pytest.approx(0.215, abs=0.01)
        assert o.findings["f2_save_util"] == pytest.approx(0.160, abs=0.01)
