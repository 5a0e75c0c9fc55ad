"""Pallas kernel for the wavefront's contended inner pass.

Integer/compare-exact, so it is drop-in on any backend (TPU Mosaic, or
``interpret=True`` on CPU for parity tests):

* **gang selection** — the allocation row scan ``free & (rowcumsum(free)
  <= job)`` that picks the first ``job`` free nodes of every lane.  The
  cumsum is computed as a matmul against an upper-triangular ones matrix
  (MXU-friendly; counts are small integers, exact in f32), then compared
  against the per-lane gang size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

__all__ = ["gang_select_pallas", "GANG_ROWS", "N_LANES"]

GANG_ROWS = 8        # lanes per gang-select block
N_LANES = 128        # node-axis pad (TPU lane width)


def _row_block(i):
    """Block index of row-block ``i``.  The column index is an explicit
    int32: the wavefront traces this kernel inside its x64 pass, where a
    bare ``0`` would lower as i64 and Mosaic refuses mixed index types."""
    return i, jnp.int32(0)


# -- gang selection ----------------------------------------------------------

def _gang_kernel(free_ref, job_ref, out_ref):
    free = free_ref[...]                                   # (R, npad) f32
    npad = free.shape[-1]
    row = lax.broadcasted_iota(jnp.int32, (npad, npad), 0)
    col = lax.broadcasted_iota(jnp.int32, (npad, npad), 1)
    tri = (row <= col).astype(jnp.float32)                 # inclusive scan
    csum = jnp.dot(free, tri, preferred_element_type=jnp.float32)
    sel = (free > 0.5) & (csum <= job_ref[...])
    out_ref[...] = sel.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gang_blocks(free_f32, job_f32, *, interpret):
    L, npad = free_f32.shape
    return pl.pallas_call(
        _gang_kernel,
        grid=(L // GANG_ROWS,),
        in_specs=[pl.BlockSpec((GANG_ROWS, npad), _row_block),
                  pl.BlockSpec((GANG_ROWS, 1), _row_block)],
        out_specs=pl.BlockSpec((GANG_ROWS, npad), _row_block),
        out_shape=jax.ShapeDtypeStruct((L, npad), jnp.float32),
        interpret=interpret,
    )(free_f32, job_f32)


def gang_select_pallas(free, job, *, interpret: bool = False):
    """``free`` (L, n) bool, ``job`` (L,) int -> chosen (L, n) bool.
    Bit-identical to the cumsum reference: the arithmetic is exact
    small-integer work carried in f32."""
    L, n = free.shape
    npad = max(N_LANES, n)
    f = jnp.zeros((L, npad), dtype=jnp.float32)
    f = f.at[:, :n].set(free.astype(jnp.float32))
    j = job.astype(jnp.float32)[:, None]
    out = _gang_blocks(f, j, interpret=interpret)
    return out[:, :n] > 0.5
