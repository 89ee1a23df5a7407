"""Communication-optimal (psum-stationary) matmul Pallas kernel.

The R=1 instantiation of the paper's dataflow on the TPU hierarchy
(DESIGN.md §2): the f32 accumulator block (bm x bn — the paper's u x z
with u ~= z from the balance condition) stays resident in VMEM across
the whole reduction sweep; A-panels and B-panels stream through VMEM in
bk slices (the paper's k-streaming, MXU-aligned).  HBM traffic per
output block is exactly one read of each operand panel plus one output
write — Eq. (14) with R = 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.tpu_adapter import BlockShape, lb_block_shape


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_lb_call(x: jax.Array, w: jax.Array,
                   blk: BlockShape | None = None,
                   out_dtype=None,
                   interpret: bool = True) -> jax.Array:
    """x: (M, K) @ w: (K, N) -> (M, N) with lower-bound block shapes.

    Dimensions must be multiples of the block shape (ops.py pads)."""
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    if blk is None:
        blk = lb_block_shape(m, n, k, dtype_bytes=x.dtype.itemsize)
    bm, bn, bk = (min(blk.bm, m), min(blk.bn, n), min(blk.bk, k))
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (m, n, k, bm, bn, bk)
    nm, nn, nk = m // bm, n // bn, k // bk
    out_dtype = out_dtype or x.dtype
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)
