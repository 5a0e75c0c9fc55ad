"""The compiled grid path past one 128-node row, against the scalar
engine (``ClusterSim`` and its findings fold): caps sized from the
lanes' failure counts, and session gang masks carried, bit packed, only
where some lane has degradation windows.

Findings are compared by their widest relative gap, to 1e-11: the grid's
findings fold is a reordered float64 fold, which drifts by an ulp from
the scalar fold (ROADMAP Design 1); a float32 fold misses by about 5e-8.
"""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from repro import tracing
from repro.core.batch import run_findings_stacked
from repro.core.cluster import CampaignConfig, ClusterSim
from repro.core.failures import FailureInjector, degraded_overlap_h
from repro.kernels.wavefront.ref import pack_gang, unpack_gang
from repro.kernels.wavefront.tapes import WavefrontCaps, max_failures
from repro.ops.scenario import Scenario
from repro.ops.sweep import compute_findings

CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"
GAP = 1e-11


@pytest.fixture(autouse=True)
def traced():
    tracing.disable()
    tracing.reset()
    tracing.enable()
    yield
    tracing.disable()
    tracing.reset()


def findings_gap(got: dict, ref: dict) -> float:
    """Widest relative gap over the fields of one lane's findings (an
    absolute one where the reference reads 0; a None on one side only
    is an infinite gap)."""
    assert set(got) == set(ref)
    worst = 0.0
    for k, r in ref.items():
        g = got[k]
        if (g is None) != (r is None):
            return float("inf")
        if r is not None and g != r:
            worst = max(worst, abs(g - r) / (abs(r) or 1.0))
    return worst


def scalar_findings(cfg: CampaignConfig, seed: int) -> dict:
    return compute_findings(
        ClusterSim(dataclasses.replace(cfg, seed=seed)).run())


def deployment(name: str, **cut) -> dict:
    """The variants of ``bench/configs/<name>.json`` as campaign
    configs, with the file's cluster and campaign applied, then ``cut``."""
    conf = json.loads((CONFIGS / f"{name}.json").read_text())
    out = {}
    for v, spec in conf["variants"].items():
        spec = dict(spec, **{k: conf[k] for k in
                             ("n_nodes", "job_nodes", "duration_days")})
        spec.update(cut)
        out[v] = Scenario.from_dict(spec).to_campaign_config(0)
    return out


def lane_failures(cfg: CampaignConfig, seeds) -> int:
    rcfg = ClusterSim(cfg).cfg
    inj = FailureInjector(
        n_nodes=rcfg.n_nodes, mtbf_h=rcfg.mtbf_h,
        hot_fraction=rcfg.hot_fraction, hot_weight=rcfg.hot_weight,
        kind_weights=rcfg.kind_weights,
        topology_fanout=rcfg.topology_fanout, seed=rcfg.seed)
    return max_failures(inj.sample_batch(rcfg.duration_h, seeds))


def test_infra_band_masks_packed_at_300_nodes():
    """Three 128-node rows, not a multiple of 32: the degrade band opens
    windows, so the pass carries packed session gangs, and every lane's
    degraded hours and findings agree with the scalar engine."""
    cfg = CampaignConfig(n_nodes=300, job_nodes=256, duration_h=4 * 24.0,
                         mtbf_h=6.0, kind_weights={"net_degrade": 8.0,
                                                   "resource_exhaust": 6.0})
    seeds = list(range(64))
    got = run_findings_stacked([cfg], seeds)[0]
    counters = tracing.snapshot()["counters"]
    caps = WavefrontCaps.sized(lane_failures(cfg, seeds))
    words = -(-300 // 32)
    assert counters["grid.gang_mask_bytes"] == \
        64 * caps.n_sessions * words * 4
    assert counters.get("grid.cap_reruns", 0) == 0
    degraded = 0
    for seed in seeds:
        ref = scalar_findings(cfg, seed)
        assert findings_gap(got[seed], ref) <= GAP, seed
        assert got[seed]["infra_degraded_h"] == ref["infra_degraded_h"]
        degraded += ref["infra_degraded_h"] > 0
    assert degraded >= 16, "too few lanes lost hours to degradation"


def test_llama3_16k_variants_no_rerun_and_no_masks():
    """``llama3-16k``'s four variants at 2,176 nodes and a 2,048-node
    gang over the whole 54 days, 16 seeds: one device pass (the sized
    caps hold where the defaults overflow), no session gang mask
    allocated or fetched, and each variant's busiest lane's findings
    those of the scalar engine."""
    cfgs = deployment("llama3-16k")
    assert all(c.n_nodes == 2176 and c.job_nodes == 2048
               for c in cfgs.values())
    seeds = list(range(16))
    got = run_findings_stacked(list(cfgs.values()), seeds)
    snap = tracing.snapshot()
    assert snap["spans"]["grid.run"]["calls"] == 1
    assert snap["counters"].get("grid.cap_reruns", 0) == 0
    assert snap["counters"]["grid.gang_mask_bytes"] == 0
    assert snap["counters"]["grid.iterations"] > WavefrontCaps().n_iters
    for (name, cfg), by_seed in zip(cfgs.items(), got):
        busiest = max(seeds, key=lambda s: by_seed[s]["n_failures"])
        assert findings_gap(by_seed[busiest],
                            scalar_findings(cfg, busiest)) <= GAP, name


def test_llama3_16k_kind_weights_follow_table5():
    """The configuration's failure mix is its Table 5 mapping over the
    Table 2 base masses, as its ``assumed`` block states."""
    conf = json.loads((CONFIGS / "llama3-16k.json").read_text())
    share = conf["assumed"]["table5_share_pct"]
    base = conf["assumed"]["table2_base_mass"]
    mapping = conf["assumed"]["table5_to_category"]
    assert sorted(sum(mapping.values(), [])) == sorted(share)
    for spec in conf["variants"].values():
        for cat, rows in mapping.items():
            want = sum(share[r] for r in rows) / 100.0 / base[cat]
            assert spec["kind_weights"][cat] == pytest.approx(want,
                                                              rel=1e-12)


def test_paper_63n_grid_lanes_keep_default_caps():
    """The sized caps of ``mc_grid.paper-63n``'s lanes (10 variants x
    seeds 0..99) are the defaults, so its device programs are
    unchanged."""
    seeds = list(range(100))
    worst = max(lane_failures(c, seeds)
                for c in deployment("paper-63n").values())
    assert WavefrontCaps.sized(worst) == WavefrontCaps(
        n_uniform=2048, n_manual=512, n_struct=512, n_sessions=512,
        n_iters=4096)


@pytest.mark.parametrize("max_failures_", [0, 81, 486])
def test_sized_caps_are_powers_of_two_over_the_floor(max_failures_):
    caps = WavefrontCaps.sized(max_failures_)
    floor = WavefrontCaps()
    for f in dataclasses.fields(caps):
        v = getattr(caps, f.name)
        assert v >= getattr(floor, f.name) and v & (v - 1) == 0


@pytest.mark.parametrize("n", [1, 31, 32, 300, 2176])
def test_pack_gang_round_trips(n):
    import jax
    rng = np.random.default_rng(n)
    m = rng.random((3, n)) < 0.5
    words = np.asarray(jax.jit(pack_gang)(m))
    assert words.shape == (3, -(-n // 32)) and words.dtype == np.uint32
    for row, w in zip(m, words):
        assert np.array_equal(unpack_gang(w, n), row)


def test_degraded_overlap_bool_row_equals_list():
    """Membership against a boolean row gives the list's hours, bit for
    bit."""
    rng = np.random.default_rng(3)
    n = 2176
    gang = np.sort(rng.choice(n, 2048, replace=False))
    row = np.zeros(n, dtype=bool)
    row[gang] = True
    windows = [(int(rng.integers(n)), t0, t0 + rng.uniform(0.5, 3.0),
                rng.uniform(1.2, 2.0), "net_degrade", "spike")
               for t0 in rng.uniform(0, 100, 400)]
    for t0, t1 in [(0.0, 100.0), (10.5, 60.25), (99.0, 99.5)]:
        want = degraded_overlap_h(windows, t0, t1, gang.tolist())
        assert degraded_overlap_h(windows, t0, t1, row) == want
        assert degraded_overlap_h(windows, t0, t1, set(gang.tolist())) \
            == want


def test_tables_skip_events_only_where_no_window_kind():
    """Lanes whose failures hold no window or escalation kind get empty
    degradation windows and escalations without their events being
    materialized; every other lane's are those of its events."""
    from repro.core.failures import (degradation_windows,
                                     escalation_events)
    from repro.kernels.wavefront.tapes import build_lane_tables
    cfg = ClusterSim(CampaignConfig(
        n_nodes=300, job_nodes=256, duration_h=4 * 24.0, mtbf_h=6.0,
        kind_weights={"resource_exhaust": 0.3})).cfg
    seeds = list(range(32))
    fails = FailureInjector(
        n_nodes=cfg.n_nodes, mtbf_h=cfg.mtbf_h,
        hot_fraction=cfg.hot_fraction, hot_weight=cfg.hot_weight,
        kind_weights=cfg.kind_weights,
        topology_fanout=cfg.topology_fanout,
        seed=cfg.seed).sample_batch(cfg.duration_h, seeds)
    tables = build_lane_tables(cfg, fails, seeds)
    materialized = set(fails._cache)
    with_windows = escalations = 0
    for i in range(len(seeds)):
        evs = fails.events(i)
        assert tables.deg_windows[i] == degradation_windows(evs)
        esc = escalation_events(evs)
        got = [(t, n) for t, n in zip(tables.device["et"][i],
                                      tables.device["enode"][i])
               if t != np.inf]
        assert got == [(t, n) for t, n in esc]
        escalations += len(esc)
        if not tables.deg_windows[i]:
            assert i not in materialized
        with_windows += bool(tables.deg_windows[i])
    assert 0 < with_windows < len(seeds) and escalations > 0


def test_replay_lists_keep_each_lanes_order():
    """The replay's per-lane lists, appended as (lanes, values) chunks,
    read back per lane in append order."""
    from repro.kernels.wavefront.ops import _Lists
    rng = np.random.default_rng(5)
    L = 7
    want = [[] for _ in range(L)]
    lists = _Lists(L, 2)
    for step in range(50):
        lanes = np.sort(rng.choice(L, rng.integers(0, L + 1),
                                   replace=False))
        vals = rng.random(len(lanes))
        lists.add(lanes, vals, np.full(len(lanes), step))
        for s, v in zip(lanes, vals):
            want[s].append((v, step))
    for s in range(L):
        assert lists.lane(s, 0).tolist() == [v for v, _ in want[s]]
        assert lists.lane(s, 1).tolist() == [k for _, k in want[s]]
    assert _Lists(3, 1).lane(2).tolist() == []


def test_policy_variants_share_one_draw(monkeypatch):
    """Variants that differ only in policy draw their failure schedules
    once, and their findings are those of separate calls."""
    from repro.core.failures import FailureInjector as Injector
    from repro.kernels.wavefront.ops import run_findings_grid
    draws = []
    sample = Injector.sample_batch

    def counted(self, duration_h, seeds):
        draws.append(self.mtbf_h)
        return sample(self, duration_h, seeds)
    monkeypatch.setattr(Injector, "sample_batch", counted)
    base = CampaignConfig(n_nodes=63, job_nodes=60, duration_h=5 * 24.0)
    cfgs = [base, dataclasses.replace(base, checkpoint_interval_h=1.0),
            dataclasses.replace(base, mtbf_h=20.0)]
    seeds = list(range(8))
    together = run_findings_grid(cfgs, seeds)
    assert sorted(draws) == sorted([base.mtbf_h, 20.0])
    for cfg, got in zip(cfgs, together):
        assert got == run_findings_grid([cfg], seeds)[0]
