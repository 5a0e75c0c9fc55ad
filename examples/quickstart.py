"""Quickstart: build a model from a config, train a few steps, checkpoint,
restore, and decode — the whole public API in ~60 lines.

    PYTHONPATH=src python examples/quickstart.py [--arch gemma2-2b]
"""
import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.launch.steps import (make_serve_step, make_train_step,
                                synthetic_batch, synthetic_decode_inputs)
from repro.models import model as model_mod
from repro.models.model import RunOptions
from repro.optim import AdamW


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    # 1. config (reduced for CPU; drop .reduced() on real hardware)
    cfg = get_config(args.arch).reduced()
    opts = RunOptions(q_chunk=64, kv_chunk=64)
    print(f"{cfg.name}: {cfg.n_layers} layers (reduced), "
          f"{cfg.n_params()/1e6:.1f} M params")

    # 2. init + train
    rng = jax.random.PRNGKey(0)
    params = model_mod.init_params(rng, cfg)
    optimizer = AdamW(lr=1e-3, warmup_steps=2, total_steps=args.steps)
    opt_state = optimizer.init(params)
    step_fn = jax.jit(make_train_step(cfg, opts, optimizer))
    batch = synthetic_batch(rng, cfg, batch=2, seq=64)
    for i in range(args.steps):
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        print(f"  step {i}: loss={float(metrics['loss']):.4f}")

    # 3. two-phase async checkpoint + restore
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, simulate_rpc=False)
        rec = mgr.save(args.steps, {"params": params}, blocking=True)
        print(f"checkpoint: {rec.bytes/1e6:.1f} MB, "
              f"blocking phase {rec.timeline.blocking_s*1e3:.1f} ms, "
              f"async phase {rec.timeline.async_s*1e3:.1f} ms")
        restored, step = mgr.restore(like={"params": params})
        assert step == args.steps

    # 4. decode a few tokens
    serve = jax.jit(make_serve_step(cfg, opts))
    cache, tok, pos = synthetic_decode_inputs(rng, cfg, batch=2, seq=64,
                                              pos=0)
    for i in range(5):
        logits, cache = serve(restored["params"], cache, tok, pos + i)
        if cfg.embed_inputs:
            tok = jnp.argmax(logits[:, -1], -1)[:, None]
    print("decoded ok:", logits.shape)


if __name__ == "__main__":
    main()
