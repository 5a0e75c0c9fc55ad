"""What-if service: canonical keys, cache, coalescer, surface, HTTP.

The load-bearing contracts:

* `Scenario.canonical_key` collapses every spelling of the same campaign
  (dict order, to_dict/from_dict round trips through `run_campaign`'s
  wire format, preset-vs-explicit construction, int-vs-float, identity
  tilts) to one key — the cache's correctness hinges on it;
* the coalescer under concurrency: N threads submitting mixed
  duplicate/distinct queries produce exactly one engine pass per
  distinct canonical key, and every caller's answer is bitwise equal to
  a per-request serial pass on the same seeds;
* the surface answers only surface-shaped queries inside its error
  bound, exactly on grid nodes, and never bleeds into the engine
  parity path (``source`` labels stay honest);
* the distributional cutoff (`MIN_DIST_SEEDS`) gates the report section
  and the service's ``distributional`` flag at the same threshold.
"""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.core.batch import BatchedCampaignEngine, run_findings_stacked
from repro.ops import (MIN_DIST_SEEDS, Scenario, SweepOutcome, SweepResult,
                       findings_distribution, get_scenario, run_campaign)
from repro.serve import (Coalescer, DistributionCache, ServiceConfig,
                         SurfaceSpec, SweepSurface, WhatIfService,
                         scenario_from_request)
from repro.serve.http import make_server

from tests._hypothesis_support import given, settings, st

DAYS = 3.0          # all engine passes here run short campaigns


def short(name="paper-faithful", **kw):
    return get_scenario(name).replace(duration_days=DAYS, **kw)


def numpy_service(**cfg_kw):
    cfg_kw.setdefault("wavefront_backend", "numpy")
    cfg_kw.setdefault("default_seeds", 8)
    return WhatIfService(ServiceConfig(**cfg_kw))


def serial_reference(scenario, n_seeds):
    """Per-request answer with no service in the loop: one numpy engine
    pass + the shared distribution extraction."""
    eng = BatchedCampaignEngine(scenario.to_campaign_config(0),
                                wavefront_backend="numpy")
    return findings_distribution(eng.run_findings(list(range(n_seeds))))


# ---------------------------------------------------------------------------
# canonical key
# ---------------------------------------------------------------------------

def test_canonical_key_round_trip_all_presets():
    """Scenario -> to_dict -> from_dict (the `run_campaign` wire format)
    preserves the canonical key for every preset."""
    from repro.ops import list_scenarios
    for name in list_scenarios():
        sc = get_scenario(name)
        assert Scenario.from_dict(sc.to_dict()).canonical_key() \
            == sc.canonical_key(), name


def test_canonical_key_ignores_labels_and_spelling():
    sc = get_scenario("paper-faithful")
    assert sc.canonical_key() == Scenario(name="explicit-twin").canonical_key()
    assert sc.replace(description="renamed").canonical_key() \
        == sc.canonical_key()
    # int-vs-float spelling of the same campaign
    assert sc.replace(duration_days=73).canonical_key() \
        == sc.replace(duration_days=73.0).canonical_key()
    # identity tilts multiply a weight by one: the same mix
    assert sc.replace(kind_weights={"nvlink": 1.0}).canonical_key() \
        == sc.canonical_key()
    assert sc.replace(kind_weights={}).canonical_key() \
        == sc.canonical_key()
    # different campaigns stay distinct
    assert sc.replace(mtbf_h=28.0).canonical_key() != sc.canonical_key()
    assert sc.replace(kind_weights={"nvlink": 2.0}).canonical_key() \
        != sc.canonical_key()


def test_canonical_key_dict_order_insensitive():
    a = Scenario(name="a", kind_weights={"nvlink": 2.0, "ecc": 3.0})
    b = Scenario(name="b", kind_weights={"ecc": 3.0, "nvlink": 2.0})
    assert a.canonical_key() == b.canonical_key()
    # shuffled top-level dict order through from_dict
    d = a.to_dict()
    shuffled = dict(reversed(list(d.items())))
    assert Scenario.from_dict(shuffled).canonical_key() == a.canonical_key()


def test_run_campaign_key_stable_across_wire_format():
    """The sweep's process-pool worker consumes `to_dict` payloads; the
    reconstructed scenario must hit the same cache line as the original
    (and still produce the same findings)."""
    sc = short()
    wire = sc.to_dict()
    assert Scenario.from_dict(wire).canonical_key() == sc.canonical_key()
    out = run_campaign(wire, seed=0)["findings"]
    ref = run_campaign(sc.to_dict(), seed=0)["findings"]
    out.pop("wall_s", None), ref.pop("wall_s", None)
    assert out == ref


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_canonical_key_property(data):
    """Property: random label edits, kind-weight orderings/identity
    tilts and int-vs-float spellings never change the key; a real tilt
    change always does."""
    weights = data.draw(st.dictionaries(
        st.sampled_from(["nvlink", "ecc", "dropout", "exec"]),
        st.floats(0.5, 4.0, allow_nan=False), max_size=3))
    sc = Scenario(name=data.draw(st.text(max_size=8)),
                  description=data.draw(st.text(max_size=8)),
                  duration_days=data.draw(st.sampled_from([3, 3.0])),
                  kind_weights=weights or None)
    twin = Scenario(
        name="twin", description="other label",
        duration_days=float(sc.duration_days),
        kind_weights=dict(reversed(list(weights.items()))) if weights
        else None)
    assert sc.canonical_key() == twin.canonical_key()
    assert Scenario.from_dict(sc.to_dict()).canonical_key() \
        == sc.canonical_key()
    tilted = sc.replace(kind_weights={**(weights or {}), "app": 2.5})
    assert tilted.canonical_key() != sc.canonical_key()


# ---------------------------------------------------------------------------
# distributional cutoff (MIN_DIST_SEEDS)
# ---------------------------------------------------------------------------

def _fake_sweep(n_seeds):
    sc = get_scenario("paper-faithful")
    outcomes = [SweepOutcome(sc.name, s, {"goodput": 0.9 + 0.001 * s,
                                          "occupancy": 0.95})
                for s in range(n_seeds)]
    return SweepResult(scenarios=[sc], seeds=list(range(n_seeds)),
                       outcomes=outcomes)


def test_distribution_section_cutoff():
    """The report's distributional section renders exactly from
    MIN_DIST_SEEDS up — the named constant, not a drifting literal."""
    assert SweepResult.MIN_SEEDS_FOR_DISTRIBUTION == MIN_DIST_SEEDS
    below = _fake_sweep(MIN_DIST_SEEDS - 1).to_markdown()
    at = _fake_sweep(MIN_DIST_SEEDS).to_markdown()
    assert "## Distributional findings" not in below
    assert f"## Distributional findings ({MIN_DIST_SEEDS} seeds)" in at


def test_service_distributional_flag_cutoff():
    svc = numpy_service(coalesce=False)
    try:
        lo = svc.query(short(), n_seeds=MIN_DIST_SEEDS - 1)
        hi = svc.query(short(), n_seeds=MIN_DIST_SEEDS)
        assert not lo.distributional and hi.distributional
        assert lo.distribution["goodput"]["n"] == MIN_DIST_SEEDS - 1
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# cache layer
# ---------------------------------------------------------------------------

def test_cache_lru_eviction_and_stats():
    c = DistributionCache(capacity=2)
    c.put("a", 1), c.put("b", 2)
    assert c.get("a") == 1          # refreshes a
    c.put("c", 3)                   # evicts b (LRU)
    assert c.get("b") is None and c.get("a") == 1 and c.get("c") == 3
    s = c.stats()
    assert (s["size"], s["evictions"]) == (2, 1)
    assert DistributionCache(capacity=0).get("x") is None


def test_cache_hit_equivalent_specs_and_latency():
    """Equivalent spellings of one campaign share a cache line; hits
    answer without an engine pass in well under the 5 ms budget."""
    svc = numpy_service()
    try:
        cold = svc.query(short())
        assert cold.source == "engine"
        # a differently-spelled equivalent spec
        twin = short().replace(name="respelled", duration_days=int(DAYS),
                               kind_weights={"nvlink": 1.0})
        lat = []
        for _ in range(50):
            t0 = time.perf_counter()
            hit = svc.query(twin)
            lat.append(time.perf_counter() - t0)
            assert hit.source == "cache"
            assert hit.distribution == cold.distribution
        assert svc.stats()["engine_configs"] == 1
        assert np.percentile(lat, 99) < 0.005
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# coalescer
# ---------------------------------------------------------------------------

def test_coalescer_windows_and_dedup():
    calls = []

    def runner(batch):
        calls.append([k for k, _ in batch])
        return {k: f"r:{k}" for k, _ in batch}

    co = Coalescer(runner, window_s=0.05)
    futs = [co.submit(k, None) for k in ("a", "b", "a", "a", "b")]
    assert [f.result(timeout=5) for f in futs] \
        == ["r:a", "r:b", "r:a", "r:a", "r:b"]
    co.close()
    # one window, deduped to the two distinct keys (first-come order)
    assert calls == [["a", "b"]]
    s = co.stats()
    assert (s["requests"], s["dispatched"], s["deduped"]) == (5, 2, 3)


def test_coalescer_queue_waits_bounded_by_window_plus_one_pass():
    window_s, pass_s = 0.03, 0.2
    passes = []

    def runner(batch):
        t0 = time.monotonic()
        time.sleep(pass_s)
        passes.append(time.monotonic() - t0)
        return {k: k for k, _ in batch}

    co = Coalescer(runner, window_s=window_s)
    futs = []
    threads = [threading.Thread(
        target=lambda i=i: futs.append(co.submit(f"k{i % 5}", None)))
        for i in range(24)]
    try:
        for i, th in enumerate(threads):
            th.start()
            if i % 6 == 5:              # later arrivals land mid-pass
                time.sleep(0.02)
        for th in threads:
            th.join(timeout=5)
            assert not th.is_alive()
        for f in futs:
            f.result(timeout=5)
    finally:
        co.close()
    s = co.stats()
    assert s["queue_waits"] == 24 and s["windows"] >= 2
    assert s["queue_wait_total_s"] >= 0.0
    assert 0.0 <= s["queue_wait_max_s"] <= window_s + max(passes) + 0.1
    assert s["queue_wait_total_s"] <= 24 * s["queue_wait_max_s"]


def test_coalescer_runner_error_fails_all_futures():
    def runner(batch):
        raise RuntimeError("engine exploded")
    co = Coalescer(runner, window_s=0.01)
    futs = [co.submit("k", None), co.submit("k2", None)]
    for f in futs:
        with pytest.raises(RuntimeError, match="engine exploded"):
            f.result(timeout=5)
    co.close()
    with pytest.raises(RuntimeError, match="closed"):
        co.submit("late", None)


def test_coalesced_concurrency_one_pass_per_key_bitwise_parity():
    """The satellite contract: 16 threads x mixed duplicate/distinct
    queries -> exactly one engine pass per distinct canonical key, and
    every caller's slice is bitwise equal to its per-request serial
    answer.

    Concurrent duplicates attach to the in-flight pass (or coalesce in
    the same window); once a key's pass has finished, repeats hit the
    cache — so across all 48 queries the engine sees each of the 4
    distinct keys exactly once, with no timing assumptions."""
    distinct = [short(checkpoint_interval_h=h)
                for h in (1.5, 2.23, 3.0, 4.0)]
    n_seeds, n_threads, per_thread = 8, 16, 3

    passes = []

    def counting_engine(cfgs, seeds):
        passes.append(len(cfgs))
        return run_findings_stacked(cfgs, seeds,
                                    wavefront_backend="numpy")

    svc = WhatIfService(
        ServiceConfig(window_s=0.05, default_seeds=n_seeds,
                      wavefront_backend="numpy"),
        engine_fn=counting_engine)
    results = [[None] * per_thread for _ in range(n_threads)]
    barrier = threading.Barrier(n_threads)

    def worker(i):
        barrier.wait()
        for j in range(per_thread):
            sc = distinct[(i + j) % len(distinct)]
            results[i][j] = (sc, svc.query(sc))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        # exactly one engine pass per distinct canonical key despite
        # 48 queries: concurrent duplicates rode the in-flight pass or
        # a shared coalescer window, later repeats the cache
        assert sum(passes) == len(distinct), (passes, svc.stats())
        refs = {sc.canonical_key(): serial_reference(sc, n_seeds)
                for sc in distinct}
        sources = set()
        for row in results:
            for sc, ans in row:
                sources.add(ans.source)
                assert ans.n_seeds == n_seeds
                assert ans.distribution == refs[sc.canonical_key()], \
                    "coalesced answer diverged from serial reference"
        assert "engine" in sources
    finally:
        svc.close()


def test_grouped_stacked_pass_matches_per_config():
    """`run_findings_stacked` on a mixed config bag returns, per config,
    exactly what a solo pass returns (lanes never interact)."""
    scs = [short(), short(checkpoint_interval_h=1.5),
           # correlated fault band: host-only, never grid-able
           short(kind_weights={"switch_degrade": 1.5})]
    cfgs = [sc.to_campaign_config(0) for sc in scs]
    seeds = list(range(4))
    stacked = run_findings_stacked(cfgs, seeds, wavefront_backend="numpy")
    for cfg, by_seed in zip(cfgs, stacked):
        solo = BatchedCampaignEngine(
            cfg, wavefront_backend="numpy").run_findings(seeds)
        assert by_seed == dict(zip(seeds, solo))


# ---------------------------------------------------------------------------
# surface layer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_surface():
    base = get_scenario("paper-faithful").replace(duration_days=2.0)
    spec = SurfaceSpec(base=base, n_nodes=(31, 63, 95),
                       tilts=(1.0, 2.0, 4.0), ckpt_hours=(1.0, 2.23, 4.0),
                       seeds=8)
    return SweepSurface(spec, wavefront_backend="numpy").build()


def test_surface_exact_on_grid(small_surface):
    """A query landing on a grid node reproduces the precomputed
    distribution exactly (interpolation weights collapse to one corner),
    with a zero error estimate."""
    surf = small_surface
    sc = surf.spec.point(63, 2.0, 1.0)
    hit = surf.lookup(sc)
    assert hit is not None and hit["interp_err_goodput"] == 0.0
    ref = serial_reference(sc, surf.spec.seeds)
    g = hit["distribution"]["goodput"]
    assert g["median"] == ref["goodput"]["median"]
    assert g["q25"] == ref["goodput"]["q25"]


def test_surface_near_miss_interpolates_between_neighbors(small_surface):
    surf = small_surface
    lo = surf.lookup(surf.spec.point(63, 2.0, 1.0))
    hi = surf.lookup(surf.spec.point(63, 2.0, 2.23))
    mid_sc = surf.spec.point(63, 2.0, 1.6)
    mid = surf.lookup(mid_sc)
    assert mid is not None
    a, b = sorted([lo["distribution"]["goodput"]["median"],
                   hi["distribution"]["goodput"]["median"]])
    assert a <= mid["distribution"]["goodput"]["median"] <= b


def test_surface_rejects_off_grid_and_out_of_hull(small_surface):
    surf = small_surface
    base = surf.spec.base
    # off-axis field change: not surface-shaped
    assert surf.lookup(base.replace(retry_policy="exp_backoff")) is None
    assert surf.lookup(base.replace(mtbf_h=28.0)) is None
    # outside the hull
    assert surf.lookup(base.replace(n_nodes=200, job_nodes=197)) is None
    assert surf.lookup(base.replace(checkpoint_interval_h=9.0)) is None
    # gang size breaking the base's spare count
    assert surf.lookup(base.replace(n_nodes=63, job_nodes=50)) is None


def test_surface_error_bound_falls_back_to_engine(small_surface):
    """Mid-cell queries fall back to a live pass when the curvature
    bound exceeds the spec tolerance (here: forced to 0), while grid
    nodes still serve (their interpolation is exact)."""
    surf = small_surface
    old = surf.spec.max_goodput_err
    surf.spec.max_goodput_err = 0.0
    try:
        mid = surf.spec.point(63, 2.0, 1.6)
        if surf.error_estimate(surf.coords(mid)) > 0.0:
            assert surf.lookup(mid) is None
        assert surf.lookup(surf.spec.point(63, 2.0, 1.0)) is not None
    finally:
        surf.spec.max_goodput_err = old
    svc = WhatIfService(ServiceConfig(coalesce=False, default_seeds=8,
                                      wavefront_backend="numpy"),
                        surface=surf)
    try:
        assert svc.query(surf.spec.point(63, 2.0, 1.0)).source == "surface"
        off = surf.spec.base.replace(retry_policy="exp_backoff")
        assert svc.query(off).source == "engine"
    finally:
        svc.close()


def test_surface_spec_validation():
    base = get_scenario("paper-faithful")
    with pytest.raises(ValueError, match="ascending"):
        SurfaceSpec(base=base, n_nodes=(63,))
    with pytest.raises(ValueError, match="fixed"):
        SurfaceSpec(base=base.replace(checkpoint_strategy="young_daly"))
    with pytest.raises(ValueError, match="spares"):
        SurfaceSpec(base=base, n_nodes=(2, 63))


# ---------------------------------------------------------------------------
# request parsing + HTTP transport
# ---------------------------------------------------------------------------

def test_scenario_from_request():
    sc = scenario_from_request({"preset": "flaky-fabric"})
    assert sc.canonical_key() == get_scenario("flaky-fabric").canonical_key()
    sc = scenario_from_request({"scenario": {"mtbf_h": 28.0}})
    assert sc.name == "adhoc" and sc.mtbf_h == 28.0
    sc = scenario_from_request({"preset": "paper-faithful",
                                "overrides": {"duration_days": 7.0}})
    assert sc.duration_days == 7.0
    for bad in ({}, {"preset": "x", "scenario": {}},
                {"scenario": {"not_a_field": 1}},
                {"preset": "paper-faithful", "overrides": {"nope": 1}}):
        with pytest.raises((ValueError, KeyError)):
            scenario_from_request(bad)


@pytest.fixture()
def http_service():
    svc = numpy_service()
    server = make_server(svc)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield svc, f"http://127.0.0.1:{server.server_address[1]}"
    server.shutdown()
    svc.close()


def _post(url, payload):
    req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                 headers={"Content-Type":
                                          "application/json"})
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(url):
    try:
        with urllib.request.urlopen(url) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_http_endpoints(http_service):
    svc, root = http_service
    assert _get(f"{root}/healthz") == (200, {"ok": True})
    code, ans = _post(f"{root}/whatif", {
        "preset": "paper-faithful", "seeds": 8,
        "overrides": {"duration_days": DAYS}})
    assert code == 200 and ans["source"] == "engine"
    assert ans["n_seeds"] == 8 and "goodput" in ans["distribution"]
    ref = serial_reference(short(), 8)
    assert ans["distribution"]["goodput"]["median"] \
        == ref["goodput"]["median"]
    # the HTTP layer shares the one service: repeat hits the cache
    code, again = _post(f"{root}/whatif", {
        "preset": "paper-faithful", "seeds": 8,
        "overrides": {"duration_days": DAYS}})
    assert code == 200 and again["source"] == "cache"
    code, stats = _get(f"{root}/stats")
    assert code == 200 and stats["queries"] == 2
    assert stats["cache"]["hits"] == 1
    code, surf = _get(f"{root}/surface")
    assert code == 200 and surf["surface"] is None


def test_http_errors(http_service):
    _, root = http_service
    assert _get(f"{root}/nope")[0] == 404
    code, err = _post(f"{root}/whatif", {"preset": "no-such-preset"})
    assert code == 400 and "unknown scenario" in err["error"]
    code, err = _post(f"{root}/whatif", {"scenario": {"bogus_field": 1}})
    assert code == 400
    code, err = _post(f"{root}/whatif",
                      {"preset": "paper-faithful", "seeds": 0})
    assert code == 400 and "n_seeds" in err["error"]
