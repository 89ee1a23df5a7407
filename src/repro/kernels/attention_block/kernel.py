"""Blocked (flash-style) attention Pallas kernel.

The paper's psum-stationary principle applied to attention: the online
softmax accumulator (acc, m, l) for a query block is the resident
output block; K/V panels stream through VMEM exactly once per query
block.  Causal + sliding-window masking via absolute positions, GQA by
indexing the kv head as q_head // group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 nkv: int, scale: float, bq: int, bk: int,
                 seq_kv: int, window: int, causal: bool):
    kv_i = pl.program_id(2)
    q_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                   # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    q_pos = q_i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_kv                              # kv padding
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > (q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] \
        + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_ref[:, 0] = m_new

    @pl.when(kv_i == nkv - 1)
    def _flush():
        l_safe = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def attention_call(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   groups: int, bq: int, bk: int, seq_kv: int,
                   window: int = 0, causal: bool = True,
                   interpret: bool = True) -> jax.Array:
    """q: (B*H, Sq, hd); k, v: (B*KV, Skv, hd) with H = KV * groups.

    Sq % bq == 0 and Skv % bk == 0 (ops.py pads); ``seq_kv`` is the real
    (unpadded) KV length for masking."""
    bh, sq, hd = q.shape
    skv = k.shape[1]
    assert sq % bq == 0 and skv % bk == 0
    nq, nk = sq // bq, skv // bk
    scale = 1.0 / (hd ** 0.5)
    kern = functools.partial(_attn_kernel, nkv=nk, scale=scale, bq=bq,
                             bk=bk, seq_kv=seq_kv, window=window,
                             causal=causal)
    g = groups
    return pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, qi, ki: (b // g, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, qi, ki: (b // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
