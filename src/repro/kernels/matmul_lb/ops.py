"""jit'd public wrapper for the lower-bound matmul kernel.

Pads operands to block multiples (zero padding is exact for matmul),
invokes the Pallas kernel, and slices the result.  The execution
backend is an :class:`~repro.core.exec_target.ExecTarget`: ``target=``
picks interpret/compiled/lax; the legacy ``interpret=`` boolean is
still honored when no target is given.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.exec_target import resolve_target
from repro.core.tpu_adapter import BlockShape, lb_block_shape
from repro.kernels.matmul_lb.kernel import matmul_lb_call
from repro.obs.tracer import active_tracer


def _pad_to(a: jax.Array, mults: tuple[int, int]) -> jax.Array:
    pads = [(0, -a.shape[i] % mults[i]) for i in range(2)]
    if any(p[1] for p in pads):
        a = jnp.pad(a, pads)
    return a


def _lax_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """The kernel's exact math on XLA's schedule (f32 psums)."""
    return jnp.dot(x, w,
                   preferred_element_type=jnp.float32).astype(x.dtype)


@partial(jax.jit, static_argnames=("blk", "interpret", "target"))
def matmul_lb(x: jax.Array, w: jax.Array,
              blk: BlockShape | None = None,
              interpret: bool = True, target=None) -> jax.Array:
    """Communication-optimal matmul: (M, K) @ (K, N) -> (M, N).

    The clamped block shape rides the same legality pass as the conv
    planner (:func:`repro.analysis.plan_check.check_matmul_block`):
    structural violations — a degenerate block or a working set over
    the VMEM budget — raise at trace time rather than failing inside
    Mosaic.  Alignment findings are advisory under ``interpret`` but
    *binding* under ``target="compiled"``: a misaligned block degrades
    loudly to the lax path (traced ``exec.fallback`` event) instead of
    handing Mosaic an illegal shape or silently interpreting."""
    from repro.analysis.plan_check import (PlanLegalityError,
                                           check_matmul_block, errors)
    tgt = None if target is None else resolve_target(target)
    if tgt is not None:
        if not tgt.compute:
            raise ValueError("account-only target cannot execute a "
                             "matmul")
        if not tgt.kernel:
            return _lax_matmul(x, w)
        interpret = tgt.interpret
    m, k = x.shape
    n = w.shape[1]
    if blk is None:
        blk = lb_block_shape(m, n, k, dtype_bytes=x.dtype.itemsize)
    bm, bn, bk = (min(blk.bm, max(8, m)), min(blk.bn, max(8, n)),
                  min(blk.bk, max(8, k)))
    blk = BlockShape(bm, bn, bk)
    plan_target = tgt.plan_target if tgt is not None else "interpret"
    diags = check_matmul_block(blk, m, n, k,
                               dtype_bytes=x.dtype.itemsize,
                               target=plan_target,
                               where=f"matmul_lb {m}x{k}@{k}x{n}")
    if errors(diags):
        if plan_target == "interpret":
            raise PlanLegalityError(errors(diags))
        active_tracer().event("exec.fallback", target=tgt.name,
                              to="lax", layer=f"matmul {m}x{k}@{k}x{n}",
                              reason="block shape not mosaic-legal")
        return _lax_matmul(x, w)
    xp = _pad_to(x, (bm, bk))
    wp = _pad_to(w, (bk, bn))
    out = matmul_lb_call(xp, wp, blk=blk,
                         out_dtype=x.dtype, interpret=interpret)
    return out[:m, :n]
