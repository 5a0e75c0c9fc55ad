"""The compiled wavefront's tape reads: each word an iteration can use,
fetched once, and the record stream and accumulators they give.

The digests pin what the device pass returns (``rec_t[:it]``,
``rec_flags[:it]``, the integer accumulators, the overflow flags, the
iteration count and, where carried, the session gangs) on lanes at the
extremes of per-iteration consumption: no automatic retry (manual draws),
the infra band (escalations, degradation windows, session gangs), a small
pool that retries through long alloc-fail chains, and lanes halted by
each cap sentry.  The device math is integer only, so the digests are
the same on every platform; they are those of the one-gather-per-read
loop that the packed tables replaced.
"""
import dataclasses
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core.cluster import CampaignConfig, ClusterSim
from repro.core.failures import FailureInjector
from repro.kernels.wavefront import ref
from repro.kernels.wavefront.ops import _run_core, device_tables
from repro.kernels.wavefront.tapes import (WavefrontCaps,
                                           build_lane_tables,
                                           concat_lane_tables,
                                           max_failures)
from repro.ops.scenario import Scenario

CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"
SEEDS = list(range(8))


def paper_variant(name: str) -> CampaignConfig:
    conf = json.loads((CONFIGS / "paper-63n.json").read_text())
    return Scenario.from_dict(conf["variants"][name]).to_campaign_config(0)


INFRA = dataclasses.replace(
    paper_variant("paper-faithful"), duration_h=20 * 24.0, mtbf_h=8.0,
    kind_weights={"net_degrade": 4.0, "resource_exhaust": 6.0})
ALLOC_FAIL = CampaignConfig(n_nodes=10, job_nodes=10, duration_h=20 * 24.0,
                            mtbf_h=30.0)
SHORT = dataclasses.replace(paper_variant("no-auto-retry"),
                            duration_h=20 * 24.0)


def lane_tables(cfgs, seeds, caps=None):
    """The grid path's tables for ``cfgs`` x ``seeds``, without the lane
    padding (``ops.run_findings_grid``'s build)."""
    resolved = []
    for cfg in cfgs:
        rcfg = ClusterSim(cfg).cfg
        fails = FailureInjector(
            n_nodes=rcfg.n_nodes, mtbf_h=rcfg.mtbf_h,
            hot_fraction=rcfg.hot_fraction, hot_weight=rcfg.hot_weight,
            kind_weights=rcfg.kind_weights,
            topology_fanout=rcfg.topology_fanout,
            seed=rcfg.seed).sample_batch(rcfg.duration_h, seeds)
        resolved.append((rcfg, fails))
    if caps is None:
        caps = WavefrontCaps.sized(
            max(max_failures(fails) for _, fails in resolved))
    return concat_lane_tables([build_lane_tables(rcfg, fails, seeds,
                                                 caps=caps)
                               for rcfg, fails in resolved])


def digest(host) -> str:
    it = int(host["it"])
    h = hashlib.sha256()
    parts = [host["rec_t"][:it].view(np.int64), host["rec_flags"][:it],
             host["npart_counts"], host["n_intervals"], host["n_delib"],
             host["n_sessions"], host["overflow"], np.int64(it)]
    if "se_gang" in host:
        parts.append(host["se_gang"])
    for a in parts:
        a = np.ascontiguousarray(a)
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# (configurations, caps, whether some lane overflows); caps None sizes
# them from the lanes' failure counts, as the grid path does
CASES = {
    "no-auto-retry": ([paper_variant("no-auto-retry")], None, False),
    "storage-fabric-degraded":
        ([paper_variant("storage-fabric-degraded")], None, False),
    "infra-band": ([INFRA], None, False),
    "alloc-fail-chains": ([ALLOC_FAIL], None, False),
    "cap-uniforms": ([ALLOC_FAIL], WavefrontCaps(n_uniform=64), True),
    "cap-manual": ([SHORT], WavefrontCaps(n_manual=16), True),
    "cap-struct": ([SHORT, INFRA], WavefrontCaps(n_struct=8), True),
    "cap-sessions": ([INFRA], WavefrontCaps(n_sessions=16), True),
    "cap-iterations": ([SHORT], WavefrontCaps(n_iters=32), True),
}

DIGESTS = {
    "alloc-fail-chains":
        "05675a41ba1325df60ae0840fe2b6004b6bedc43671fc5181318ef5bbe4e40ca",
    "cap-iterations":
        "8b7a73380577d254602091f0792b16c727829da08605fa77800b6b72bafe52db",
    "cap-manual":
        "0289cd3aede738472d608b1e9a407ac65c0f46f4cabf4e81c4ef4be1d9111de9",
    "cap-sessions":
        "ff0f16d2c6acaadb91314919f18ac74584e5b487f2cffb58f976fcf394594a8d",
    "cap-struct":
        "7976170d5f723ac11ffddd7353662c96a88ee84eae08df4aa520de30648dcaa2",
    "cap-uniforms":
        "5c9a7a9c9299193b223518eb32520b023e8e382602eff5edd1da9ebff0724c24",
    "infra-band":
        "3afdf39125e688a16626ef3aad061647017ec1833d85df7cf292263b6767d15a",
    "no-auto-retry":
        "91dc9fc57d818948ad40724290798a16acedd812fb18ceb3727b13d08d1b5655",
    "storage-fabric-degraded":
        "08c088589528d137a4fd314cc089eb19dd32c6a0b68ebce88dde099a2f75099c",
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_pass_digest(case):
    cfgs, caps, overflows = CASES[case]
    tables = lane_tables(cfgs, SEEDS, caps)
    host = _run_core(tables, "xla", False)
    assert bool(host["overflow"].any()) == overflows
    if case == "infra-band":
        assert "se_gang" in host and tables.device["et"].shape[1] > 1
    assert digest(host) == DIGESTS[case]


@pytest.mark.parametrize("n_sessions", [0, 512])
def test_core_reads_tapes_with_at_most_8_gathers(n_sessions):
    """The compiled loop fetches each tape once an iteration: 8 gathers
    in the whole program, where one gather per read made 45 (46 with
    session gangs)."""
    import jax
    import jax.numpy as jnp
    tables = lane_tables([paper_variant("paper-faithful")], SEEDS)
    with jax.enable_x64(True):
        P = {k: jnp.asarray(v) for k, v in device_tables(tables).items()}
        hlo = ref.wavefront_core.lower(
            P, n_nodes=tables.n_nodes, n_sessions=n_sessions,
            n_iters=tables.caps.n_iters).compile().as_text()
    assert hlo.count(" gather(") <= 8


def test_reads_fit_inside_the_sentry_margins():
    """Each capped tape's per-iteration read lies inside the margin its
    cap sentry keeps, and the margins are the caps' (8 uniforms, 4
    manual and 4 structural draws)."""
    assert set(ref.READ) == set(ref.MARGIN) == set(ref.CAPPED)
    assert ref.MARGIN == {"u_ptr": 8, "m_ptr": 4, "x_ptr": 4}
    for ptr, n in ref.READ.items():
        assert 1 <= n <= ref.MARGIN[ptr]
    # the uniforms' comparison bits of one read fit one int32
    assert ref.READ["u_ptr"] * len(ref._PROBS) < 32
