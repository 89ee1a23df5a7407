"""jit'd wrapper: (B, S, H, hd) attention through the Pallas kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.exec_target import resolve_target
from repro.kernels.attention_block.kernel import attention_call
from repro.obs.tracer import active_tracer


def _lax_attention(q, k, v, *, window: int, causal: bool) -> jax.Array:
    """Reference attention with the kernel's exact semantics: scores
    scaled by 1/sqrt(hd), GQA via kv head = head // groups, key mask
    over the true KV length, optional causal and sliding-window
    masks."""
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    g = h // kv
    kx = jnp.repeat(k, g, axis=2)
    vx = jnp.repeat(v, g, axis=2)
    scale = 1.0 / (hd ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kx.astype(jnp.float32)) * scale
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      vx.astype(jnp.float32)).astype(q.dtype)


@partial(jax.jit, static_argnames=("window", "causal", "bq", "bk",
                                   "interpret", "target"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: int = 0, causal: bool = True,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = True, target=None) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd) -> (B, Sq, H, hd).

    ``target`` (an :class:`~repro.core.exec_target.ExecTarget` or
    name) selects the backend; ``LAX`` runs the reference math, and an
    oversized grid under ``COMPILED`` on CPU degrades loudly to it
    (traced ``exec.fallback``) rather than melting the unrolled
    lowering."""
    tgt = None if target is None else resolve_target(target)
    if tgt is not None:
        if not tgt.compute:
            raise ValueError("account-only target cannot execute "
                             "attention")
        if not tgt.kernel:
            return _lax_attention(q, k, v, window=window, causal=causal)
        interpret = tgt.interpret
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    groups = h // kv
    bq = min(bq, max(8, sq))
    bk = min(bk, max(8, skv))
    pad_q = -sq % bq
    pad_k = -skv % bk
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, skv, hd)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    out = attention_call(qf, kf, vf, groups=groups, bq=bq, bk=bk,
                         seq_kv=skv, window=window, causal=causal,
                         interpret=interpret)
    out = out[:, :sq].reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
    return out
