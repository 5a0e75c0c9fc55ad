"""Kernel benchmarks: Pallas (interpret on CPU / compiled on TPU, each
call taking its ``ops`` default) vs the pure-jnp oracle — correctness +
us/call at validation shapes."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed


def bench_flash_attention() -> list:
    from repro.kernels.flash_attention import ref
    from repro.kernels.flash_attention.ops import flash_attention

    rng = np.random.default_rng(0)
    B, S, H, Hkv, D = 2, 256, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)

    out, us_k = timed(lambda: flash_attention(
        q, k, v, block_q=64, block_k=64)
        .block_until_ready(), repeats=2)
    exp, us_r = timed(lambda: ref.attention_bhsd(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2))
        .block_until_ready(), repeats=2)
    err = float(jnp.max(jnp.abs(out.swapaxes(1, 2) - exp)))
    return [("kernel_flash_attention", us_k,
             f"ref_us={us_r:.0f} max_err={err:.2e} shape=B{B}xS{S}xH{H}x{D} "
             f"(TPU target: pl.pallas_call, VMEM q/kv blocks 128x128)")]


def bench_rwkv6_scan() -> list:
    from repro.kernels.rwkv6_scan import ref
    from repro.kernels.rwkv6_scan.ops import wkv6

    rng = np.random.default_rng(1)
    B, S, H, D = 2, 256, 2, 16
    r = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32) * 0.5
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32) * 0.5
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32) * 0.5
    w = jnp.asarray(rng.uniform(0.9, 0.999, size=(B, S, H, D)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, D)), jnp.float32) * 0.3
    s0 = jnp.zeros((B, H, D, D), jnp.float32)

    (y, s_f), us_k = timed(lambda: jax.block_until_ready(
        wkv6(r, k, v, w, u, s0, chunk=64)), repeats=2)
    (y_r, s_r), us_r = timed(lambda: jax.block_until_ready(
        ref.wkv6_sequential(r, k, v, w, u, s0)), repeats=2)
    err = float(jnp.max(jnp.abs(y - y_r)))
    return [("kernel_rwkv6_scan", us_k,
             f"seq_ref_us={us_r:.0f} max_err={err:.2e} "
             f"(chunked matmul form; state carried in VMEM scratch)")]


def bench_ckpt_pack() -> list:
    from repro.kernels.ckpt_pack.ops import ckpt_pack
    from repro.kernels.ckpt_pack.ref import ckpt_pack_blocks_ref

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(1 << 16,)), jnp.float32)
    (y, chk), us_k = timed(lambda: jax.block_until_ready(
        ckpt_pack(x, block=2048)), repeats=2)
    (y_r, chk_r), us_r = timed(lambda: jax.block_until_ready(
        ckpt_pack_blocks_ref(x.reshape(-1, 2048))), repeats=2)
    ok = bool(jnp.all(y.reshape(-1, 2048) == y_r)) and \
        bool(jnp.all(chk == chk_r.reshape(-1)))
    return [("kernel_ckpt_pack", us_k,
             f"ref_us={us_r:.0f} exact_match={ok} "
             f"(fp32->bf16 cast + u32 block checksum, one VMEM pass; "
             f"halves the NFS WRITE volume through the 128-slot layer)")]


def all_benches():
    return [bench_flash_attention, bench_rwkv6_scan, bench_ckpt_pack]
