"""Integration tests: fault-tolerant trainer end-to-end, cluster campaign,
serving loop, and a subprocess dry-run cell (512 fake devices)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


def test_trainer_recovers_from_injected_xid(tmp_path):
    from repro.launch.train import run_training

    rep = run_training("gemma2-2b", steps=24, batch=2, seq=32,
                       ckpt_dir=str(tmp_path), fail_at=(10,), fail_xid=94,
                       verbose=False)
    assert rep.steps_done == 24
    assert rep.n_failures == 1 and rep.n_restarts == 1
    assert np.isfinite(rep.final_loss)
    # resumed strictly from a checkpointed step
    assert all(r % max(24 // 5, 5) == 0 for r in rep.restore_steps)


def test_trainer_takes_arch_config_as_given(tmp_path):
    """An `ArchConfig` trains as given (no reduction), and the restore
    after an injected XID lands on the last checkpoint."""
    import dataclasses

    from repro.configs import get_config
    from repro.launch.train import run_training

    cfg = dataclasses.replace(get_config("stablelm-3b").reduced(),
                              n_periods=1)
    rep = run_training(cfg, steps=12, batch=2, seq=32,
                       ckpt_dir=str(tmp_path), fail_at=(7,), fail_xid=94,
                       verbose=False)
    assert rep.steps_done == 12 and rep.restore_steps == [5]
    assert np.isfinite(rep.final_loss)
    assert (tmp_path / cfg.name).is_dir()


def test_trainer_xid79_stops_for_operator(tmp_path):
    """RESTART_BM (XID 79) halts auto-retry — operator action required."""
    from repro.launch.train import run_training

    rep = run_training("gemma2-2b", steps=24, batch=2, seq=32,
                       ckpt_dir=str(tmp_path), fail_at=(10,), fail_xid=79,
                       retry_policy="xid_branch", verbose=False)
    assert rep.steps_done < 24
    assert rep.n_failures == 1 and rep.n_restarts == 0


def test_training_learns(tmp_path):
    """The optimizer + model actually learn: overfitting a fixed batch
    drives the loss well below the uniform-distribution entropy ln(V)."""
    import jax
    import math

    from repro.configs import get_config
    from repro.launch.steps import make_train_step, synthetic_batch
    from repro.models import model as model_mod
    from repro.optim import AdamW
    from repro.models.model import RunOptions

    cfg = get_config("stablelm-3b").reduced()
    optimizer = AdamW(lr=3e-3, warmup_steps=2, total_steps=40)
    params = model_mod.init_params(jax.random.PRNGKey(0), cfg)
    opt_state = optimizer.init(params)
    step = jax.jit(make_train_step(cfg, RunOptions(q_chunk=16, kv_chunk=16),
                                   optimizer))
    batch = synthetic_batch(jax.random.PRNGKey(1), cfg, 4, 32)
    losses = []
    for _ in range(40):
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < math.log(cfg.vocab_size) - 0.5, losses[-5:]
    assert losses[-1] < losses[0]


def test_serving_loop():
    from repro.launch.serve import run_serving

    out = run_serving("gemma2-2b", batch=2, prompt_len=16, gen_len=8,
                      verbose=False)
    assert out["decode_tokens_per_s"] > 0
    assert len(out["sample"]) == 8


def test_cluster_campaign_invariants():
    from repro.core.cluster import CampaignConfig, ClusterSim

    res = ClusterSim(CampaignConfig(duration_h=14 * 24.0, seed=4)).run()
    # every session is terminal and never exceeded the node budget
    for s in res.sessions:
        assert s.is_terminal
        assert len(s.nodes) == 60
    # chain bookkeeping is self-consistent
    for c in res.chains:
        for a in c.attempts[:-1]:
            assert a.end_h is not None
    # downtime episodes are positive
    assert all(d["hours"] >= 0 for d in res.downtimes)
    assert res.checkpoint_events > 0


def test_occupancy_near_paper():
    from repro.core.cluster import CampaignConfig, ClusterSim

    occ = []
    for seed in (0, 1):
        res = ClusterSim(CampaignConfig(duration_h=30 * 24.0,
                                        seed=seed)).run()
        occ.append(res.training_occupancy())
    assert np.mean(occ) > 0.85         # paper: 96.6%


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """One real dry-run cell in a subprocess (512 host devices, 16x16 mesh +
    2x16x16 multi-pod gate).  Slow (~2 min) but proves the deliverable."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    code = (
        "from repro.launch.dryrun import run_cell;"
        "import json;"
        "r1 = run_cell('gemma2-2b','train_4k',multi_pod=False,verbose=False);"
        "r2 = run_cell('gemma2-2b','decode_32k',multi_pod=True,"
        "skip_cost=True,verbose=False);"
        "print(json.dumps([r1['status'], r2['status'],"
        " r1['roofline']['dominant']]))"
    )
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    status1, status2, dominant = json.loads(out.stdout.strip().splitlines()[-1])
    assert status1 == "OK" and status2 == "OK"
    assert dominant in ("compute", "memory", "collective")


def test_dryrun_results_cover_all_cells():
    """The shipped dry-run artifacts cover every (arch x shape x mesh) cell
    with OK or a documented SKIP."""
    p = REPO / "benchmarks" / "results" / "dryrun_baseline.json"
    if not p.exists():
        pytest.skip("dry-run artifacts not generated yet")
    results = json.loads(p.read_text())
    from repro.configs import ASSIGNED_ARCHS, SHAPES
    missing, failed = [], []
    for arch in ASSIGNED_ARCHS:
        for shape in SHAPES:
            for mesh in ("16x16", "2x16x16"):
                key = f"{arch}|{shape}|{mesh}"
                rec = results.get(key)
                if rec is None:
                    missing.append(key)
                elif rec["status"] == "FAIL":
                    failed.append(key)
    assert not missing, missing
    assert not failed, failed
