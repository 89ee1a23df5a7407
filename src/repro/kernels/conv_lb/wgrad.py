"""dW-stationary wgrad Pallas kernel — the executing form of WgradPlan.

dW is the conv of the padded input with the incoming gradient as the
kernel plane (batch folds into the reduction):

  dW[ky, kx, ci, co] = sum_{b, oy, ox}
      x_pad[b, ky*dil + oy*stride, kx*dil + ox*stride, ci]
      * dy[b, oy, ox, co]

The dataflow is the mirror image of the forward's psum-stationary
u x z block: a ``(Hk, Wk, ci_b, co_b)`` block of *dW* stays resident
in VMEM scratch across the whole (batch, strip) sweep — OutR on the
weight gradient, written exactly once — while matching spatial strips
of x and dy stream through.

  grid = (Ci-blocks, Co-blocks, batch, strips + lag)   (strips inner)

Rolling strips with a lagged carry: each grid step fetches a
*disjoint* ``R = strip*stride``-row x block (every touched x row
enters the chip exactly once per plane pass — the once-per-word
claim WgradPlan charges), while the ``K = ekh - stride`` halo rows
consecutive strips share live at the front of a ``(K + R)``-row slab
scratch (the carry, shifted there from the previous slab's tail,
then the fetch behind it).  Because the
halo of strip ``j`` extends *past* its own fetch, the compute lags the
fetch by ``lag = ceil(K/R)`` steps: step ``si`` reduces dy strip
``j = si - lag`` against carry + fetch — rows ``[j*R, j*R + R + K)``
of the conv-padded plane, shifted by ``P0 = lag*R - K`` leading zeros
so the fetch grid tiles exactly.  ``K <= 0`` (``ekh <= stride``,
e.g. 1x1 stride-2) drops the carry and lag entirely.

The dy strip BlockSpec indexes ``max(si - lag, 0)``: Pallas re-fetches
only on index-map change, so each strip is fetched once per
(ci-block, co-block, image) — the ``reads_dy`` the plan charges.

Runs under ``interpret=True`` (the CPU reference) or compiled by
Mosaic on a TPU (``interpret=False``).  The window reads are strided
loads from the slab scratch, so a strided layer's windows never
materialize a value-level strided slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tpu_adapter import VMEM_LIMIT_BYTES


def _wgrad_kernel(x_ref, dy_ref, o_ref, acc_ref, slab_ref, *,
                  ns: int, lag: int, k_rows: int, strip: int,
                  stride: tuple[int, int], dilation: tuple[int, int],
                  hk: int, wk: int, wo: int, nb: int):
    bi = pl.program_id(2)
    si = pl.program_id(3)
    sy, sx = stride
    dly, dlx = dilation
    cib = x_ref.shape[-1]
    cob = dy_ref.shape[-1]
    r_rows = strip * sy

    @pl.when((bi == 0) & (si == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # slab = carry ++ fetch: conv-padded rows [si*R - K, (si+1)*R); the
    # carry is the previous slab's last K rows, shifted to the front
    if k_rows > 0:
        slab_ref[:k_rows] = slab_ref[r_rows:]
    slab_ref[k_rows:] = x_ref[0]              # (R, WX, cib), disjoint

    @pl.when(si >= lag)
    def _compute():                           # dy strip j = si - lag
        dys = dy_ref[0].reshape(strip * wo, cob)
        for ky in range(hk):                  # unrolled window sweep:
            for kx in range(wk):              # WndR served from VMEM
                xs = slab_ref[pl.ds(ky * dly, strip, stride=sy),
                              pl.ds(kx * dlx, wo, stride=sx), :]
                acc_ref[ky, kx] += jax.lax.dot_general(
                    xs.reshape(strip * wo, cib), dys,
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    @pl.when((bi == nb - 1) & (si == ns + lag - 1))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def wgrad_lb_call(x: jax.Array, dy: jax.Array, wplan, *,
                  interpret: bool = True) -> jax.Array:
    """x: (B, H, W, Ci) true input plane; dy: (B, Ho, Wo, Co) incoming
    gradient; ``wplan`` a :class:`repro.kernels.conv_lb.ops.WgradPlan`
    carrying the executing-kernel geometry (stride/dilation/padding).
    Returns dW as (Hk, Wk, ci_pad, co_pad) f32 — callers crop the
    channel padding."""
    b, h, w_in, ci = x.shape
    b2, ho, wo, co = dy.shape
    assert b == b2 and ho == wplan.ho and wo == wplan.wo, (
        (b, ho, wo), (b2, wplan.ho, wplan.wo))
    nci, nco, ns = wplan.grid
    lag = wplan.lag
    r_rows = wplan.strip * wplan.sy
    k_rows = max(0, wplan.ekh - wplan.sy)
    assert lag * r_rows >= k_rows
    hx = (ns + lag) * r_rows                  # fetched plane rows
    wx, wo_pad = wplan.wx, wplan.wo_pad
    # the deepest window column must stay inside the fetched width
    assert (wk_cols := (wplan.wk - 1) * wplan.dlx
            + (wo_pad - 1) * wplan.sx + 1) <= wx, (wk_cols, wx)
    ci_pad, co_pad = nci * wplan.ci_b, nco * wplan.co_b

    # shifted conv-padded x plane: P0 = lag*R - K alignment zeros, then
    # the conv padding, then the true rows (a strided forward's
    # leftover trailing rows past the last window fall off the fetch
    # range — they contribute no gradient), zero tail to the fetch grid
    top = (lag * r_rows - k_rows) + wplan.py
    rows = min(h, hx - top)
    xp = jnp.pad(x[:, :rows],
                 ((0, 0), (top, hx - top - rows),
                  (wplan.px, wx - w_in - wplan.px), (0, 0)))
    if ci_pad > ci:
        xp = jnp.pad(xp, ((0, 0), (0, 0), (0, 0), (0, ci_pad - ci)))
    # zero dy rows/cols past the plane contribute nothing to dW
    dyp = jnp.pad(dy, ((0, 0), (0, wplan.ho_pad - ho),
                       (0, wo_pad - wo), (0, co_pad - co)))

    # execution-site traffic: words moved by *this* call, derived from
    # the realized grid and operand block shapes (x's disjoint index
    # map changes every step; dy's clamped map takes ns distinct
    # values per (ci-block, co-block, image); dW flushes once) — the
    # measured side of the wgrad-vs-bound gate, independent of
    # WgradPlan.traffic
    moved = ((nci * nco * b) * ((ns + lag) * r_rows * wx * wplan.ci_b
                                + ns * wplan.strip * wo_pad
                                * wplan.co_b)
             + wplan.hk * wplan.wk * ci_pad * co_pad)
    from repro.obs.tracer import active_tracer
    active_tracer().event(
        "kernel.wgrad", grid=f"({nci},{nco},{b},{ns + lag})",
        words_moved=moved, bytes_moved=moved * x.dtype.itemsize,
        interpret=interpret)

    kern = functools.partial(
        _wgrad_kernel, ns=ns, lag=lag, k_rows=k_rows,
        strip=wplan.strip, stride=(wplan.sy, wplan.sx),
        dilation=(wplan.dly, wplan.dlx),
        hk=wplan.hk, wk=wplan.wk, wo=wo_pad, nb=b)
    scratch = [pltpu.VMEM((wplan.hk, wplan.wk, wplan.ci_b, wplan.co_b),
                          jnp.float32),
               pltpu.VMEM((k_rows + r_rows, wx, wplan.ci_b), xp.dtype)]
    return pl.pallas_call(
        kern,
        grid=(nci, nco, b, ns + lag),
        in_specs=[
            pl.BlockSpec((1, r_rows, wx, wplan.ci_b),
                         lambda cii, coi, bi, si: (bi, si, 0, cii)),
            pl.BlockSpec((1, wplan.strip, wo_pad, wplan.co_b),
                         lambda cii, coi, bi, si:
                         (bi, jnp.maximum(si - lag, 0), 0, coi)),
        ],
        out_specs=pl.BlockSpec((wplan.hk, wplan.wk, wplan.ci_b,
                                wplan.co_b),
                               lambda cii, coi, bi, si: (0, 0, cii, coi)),
        out_shape=jax.ShapeDtypeStruct(
            (wplan.hk, wplan.wk, ci_pad, co_pad), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="conv_wgrad",
    )(xp, dyp)
