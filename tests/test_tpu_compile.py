"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the chip's compiler refuses what interpret mode accepts
(unaligned blocks, mixed index types), at no chip time.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.  Nothing here runs a kernel; each test lowers and compiles one.
"""
import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU library otherwise logs to a fixed directory shared by
    # every checkout on the host
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def quiet_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def wavefront_tables():
    """Lane tables of the paper campaign (63 nodes, 73 days), 1024 lanes:
    the shape one coalesced four-preset what-if pass hands the device."""
    from repro.core.cluster import ClusterSim
    from repro.core.failures import FailureInjector
    from repro.kernels.wavefront.tapes import (build_lane_tables,
                                               pad_lanes_pow2)
    from repro.ops import get_scenario
    cfg = ClusterSim(get_scenario("paper-faithful").to_campaign_config(0)).cfg
    seeds = list(range(1024))
    inj = FailureInjector(
        n_nodes=cfg.n_nodes, mtbf_h=cfg.mtbf_h,
        hot_fraction=cfg.hot_fraction, hot_weight=cfg.hot_weight,
        kind_weights=cfg.kind_weights,
        topology_fanout=cfg.topology_fanout, seed=cfg.seed)
    fails = inj.sample_batch(cfg.duration_h, seeds)
    return pad_lanes_pow2(build_lane_tables(cfg, fails, seeds))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_wavefront_core_compiles(backend, one_chip, quiet_cache,
                                 wavefront_tables):
    from repro.kernels.wavefront.ops import device_tables
    from repro.kernels.wavefront.ref import wavefront_core
    t = wavefront_tables
    assert t.n_nodes == 63
    with jax.enable_x64(True):
        P = {k: _spec(v.shape, v.dtype, one_chip)
             for k, v in device_tables(t).items()}
        assert P["u"].dtype == jnp.int64      # doubles as bit patterns
        compiled = wavefront_core.lower(
            P, n_nodes=t.n_nodes, n_sessions=t.caps.n_sessions,
            n_iters=t.caps.n_iters, backend=backend,
            interpret=False).compile()
    hlo = compiled.as_text()
    assert ("tpu_custom_call" in hlo) == (backend == "pallas")


def test_wavefront_core_compiles_at_fleet_size(one_chip, quiet_cache):
    """The device pass of ``mc_fleet.llama3-16k`` as the grid path runs
    it: 4 variants x 64 lanes of a 2,176-node pool over 54 days, caps
    sized from the lanes' failures, no session gang masks."""
    import json
    from pathlib import Path

    from repro.core.cluster import ClusterSim
    from repro.core.failures import FailureInjector
    from repro.kernels.wavefront.ops import device_tables
    from repro.kernels.wavefront.ref import wavefront_core
    from repro.kernels.wavefront.tapes import (WavefrontCaps,
                                               build_lane_tables,
                                               concat_lane_tables,
                                               max_failures,
                                               pad_lanes_pow2)
    from repro.ops.scenario import Scenario
    conf = json.loads((Path(__file__).resolve().parents[1] / "bench"
                       / "configs" / "llama3-16k.json").read_text())
    seeds = list(range(64))
    drawn = []
    for spec in conf["variants"].values():
        cfg = ClusterSim(Scenario.from_dict(spec).to_campaign_config(0)).cfg
        inj = FailureInjector(
            n_nodes=cfg.n_nodes, mtbf_h=cfg.mtbf_h,
            hot_fraction=cfg.hot_fraction, hot_weight=cfg.hot_weight,
            kind_weights=cfg.kind_weights,
            topology_fanout=cfg.topology_fanout, seed=cfg.seed)
        drawn.append((cfg, inj.sample_batch(cfg.duration_h, seeds)))
    caps = WavefrontCaps.sized(max(max_failures(f) for _, f in drawn))
    t = pad_lanes_pow2(concat_lane_tables(
        [build_lane_tables(c, f, seeds, caps=caps) for c, f in drawn]))
    assert (t.n_lanes, t.n_nodes) == (256, 2176) and not any(t.deg_windows)
    with jax.enable_x64(True):
        P = {k: _spec(v.shape, v.dtype, one_chip)
             for k, v in device_tables(t).items()}
        compiled = wavefront_core.lower(
            P, n_nodes=t.n_nodes, n_sessions=0, n_iters=caps.n_iters,
            backend="xla", interpret=False).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < 2**30


def test_robust_hit_blocks_compiles(one_chip, quiet_cache):
    from repro.kernels.robust_stats.kernel import robust_hit_blocks
    S, B, T, n = 16, 8, 256, 128
    fn = jax.jit(functools.partial(robust_hit_blocks, z_threshold=3.0,
                                   interpret=False))
    fn.lower(_spec((S, B, T, n), jnp.float32, one_chip),
             _spec((S, T, n), jnp.bool_, one_chip)).compile()


@pytest.mark.parametrize("x64", [False, True])
def test_gang_blocks_compiles(x64, one_chip, quiet_cache):
    from repro.kernels.wavefront.kernel import N_LANES, _gang_blocks
    L = 1024
    with jax.enable_x64(x64):
        _gang_blocks.lower(_spec((L, N_LANES), jnp.float32, one_chip),
                           _spec((L, 1), jnp.float32, one_chip),
                           interpret=False).compile()


def test_ckpt_pack_blocks_compiles(one_chip, quiet_cache):
    from repro.kernels.ckpt_pack.kernel import ckpt_pack_blocks
    fn = jax.jit(functools.partial(ckpt_pack_blocks, interpret=False))
    compiled = fn.lower(_spec((4096, 2048), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip, quiet_cache):
    from repro.kernels.flash_attention.ops import flash_attention
    B, S, H, D = 1, 2048, 8, 128
    q = _spec((B, S, H, D), jnp.bfloat16, one_chip)
    compiled = flash_attention.lower(q, q, q, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()

