"""Checkpoint-shard packing Pallas TPU kernel (serves the F2 save path).

Fuses the two per-shard operations of checkpoint phase 2 in one VMEM pass:
  1. dtype cast fp32 -> bf16 (halves the RPC-constrained NFS write volume —
     the single biggest lever on the paper's 128-slot bottleneck), and
  2. a per-block additive uint32 checksum over the ORIGINAL fp32 bits
     (integrity verification at restore; bitcast + modular sum).

Input is reshaped by ops.py to (n_blocks, block); each grid step takes
``ROWS`` checksum blocks at once (the TPU tiles the second-to-last axis
by 8, so a one-row block does not lower), and the block count is padded
to a multiple of ``ROWS`` here and sliced back.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 8            # checksum blocks per grid step (TPU sublane tile)


def _kernel(x_ref, y_ref, chk_ref):
    x = x_ref[...]                                   # (ROWS, block) f32
    y_ref[...] = x.astype(jnp.bfloat16)
    # modular (wrapping) sum per block: Mosaic reduces int32 but not
    # uint32, and a wrapping int32 sum has the same bits
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    acc = jnp.sum(bits, axis=1, keepdims=True, dtype=jnp.int32)
    chk_ref[...] = jax.lax.bitcast_convert_type(acc, jnp.uint32)


def ckpt_pack_blocks(x, *, interpret: bool = False):
    """x: (n_blocks, block) float32 -> (bf16 same shape, uint32 (n_blocks,1))."""
    nb, blk = x.shape
    pad = (-nb) % ROWS
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    y, chk = pl.pallas_call(
        _kernel,
        grid=((nb + pad) // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, blk), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((ROWS, blk), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb + pad, blk), jnp.bfloat16),
            jax.ShapeDtypeStruct((nb + pad, 1), jnp.uint32),
        ],
        interpret=interpret,
    )(x)
    return y[:nb], chk[:nb]
