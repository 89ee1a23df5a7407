"""Depthwise convolution (groups == Ci == Co) as one channel-blocked
Pallas kernel, ``conv_dw``, with its plan and HBM-traffic accountant.

A depthwise layer has no reduction over input channels: output channel
c reads input channel c alone, through its own k x k taps.  There is
no reuse across channels to buy with on-chip memory, so the Eq. (15)
machinery reduces to its once-per-word floor
(:func:`repro.core.lower_bound.q_dram_depthwise`): read x once, the
weights once, write y once.  The kernel attains it:

  grid = (channel-blocks, images)

Per grid step one image's whole (H, W) plane of a ``c_block`` channel
slice enters VMEM unpadded, is copied row by row into a zeroed scratch
that holds the conv padding (the halo is made on chip, never fetched),
and each output row accumulates its k x k taps on the VPU in float32
(exact products: no MXU operand rounding), then takes the fused
epilogue of :func:`repro.kernels.conv_lb.kernel.epilogue` (bias, GELU,
layer scale, ReLU and, where the channel block spans every channel, a
LayerNorm) before its one write.  Images run innermost, so each weight
block is fetched once for the whole batch.

A LayerNorm the channel block cannot hold runs as a separate pass over
the output (``DepthwisePlan.norm_pass``), and the plan charges its read
and write.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dataflow import Traffic
from repro.core.tpu_adapter import (LANE, VMEM_LIMIT_BYTES, round_up,
                                    sublane_for)
from repro.kernels.conv_lb.kernel import epilogue, layer_norm


@dataclasses.dataclass(frozen=True)
class DepthwisePlan:
    """Blocks of one depthwise layer: a ``c_block`` channel slice of one
    whole image plane per grid step.  Shares the accounting surface of
    :class:`~repro.kernels.conv_lb.ops.ConvPlan` (``traffic``,
    ``bound_words``, ``footprint_elems``), so the serve ledger and the
    plan audit take either."""

    h: int              # input plane
    w: int
    c: int              # channels (= groups)
    hk: int
    wk: int
    py: int             # conv padding
    px: int
    c_block: int
    norm: bool = False       # LayerNorm fused in the epilogue
    norm_pass: bool = False  # LayerNorm as a pass of its own
    target: str = "interpret"

    @property
    def residual(self) -> bool:
        """No residual join is fused (the ledger asks every plan)."""
        return False

    @property
    def ho(self) -> int:
        return self.h + 2 * self.py - self.hk + 1

    @property
    def wo(self) -> int:
        return self.w + 2 * self.px - self.wk + 1

    @property
    def wo_pad(self) -> int:
        """Output row width the taps accumulate, sublane-aligned."""
        return round_up(self.wo, 8)

    @property
    def scratch_w(self) -> int:
        """Columns of the padded on-chip plane: every tap's row slice
        of ``wo_pad`` columns stays inside it."""
        return max(self.w + 2 * self.px, self.wo_pad + self.wk - 1)

    @property
    def n_blocks(self) -> int:
        return self.c // self.c_block

    def traffic(self, batch: int) -> Traffic:
        """HBM words at ``batch`` images: each image plane of each
        channel block once, the weights once per channel block (images
        run innermost), each output once; an unfused norm reads and
        writes the output once more."""
        out = batch * self.ho * self.wo * self.c
        return Traffic(
            reads_in=float(batch * self.h * self.w * self.c
                           + (out if self.norm_pass else 0)),
            reads_w=float(self.hk * self.wk * self.c),
            reads_out=0.0,
            writes_out=float(out * (2 if self.norm_pass else 1)))

    def traffic_bytes(self, batch: int, dtype_bytes: int = 4) -> float:
        return self.traffic(batch).total * dtype_bytes

    def footprint_elems(self) -> int:
        """On-chip words S: the input plane, its padded copy, one
        output plane and the weights of a channel block."""
        return self.c_block * (self.h * self.w
                               + (self.h + 2 * self.py) * self.scratch_w
                               + self.ho * self.wo + self.hk * self.wk)

    def vmem_bytes(self, dtype_bytes: int = 4) -> int:
        """Words held with double-buffered blocks (the paper-model
        budget the interpret profile checks)."""
        cb = self.c_block
        return dtype_bytes * (
            2 * cb * (self.h * self.w + self.ho * self.wo
                      + self.hk * self.wk)
            + cb * (self.h + 2 * self.py) * self.scratch_w)

    def mosaic_working_set(self, dtype_bytes: int = 4) -> tuple[int, int]:
        """``(vmem_bytes, tile_bytes)`` of the compiled kernel on
        (sublane, LANE) tiles: double-buffered input, weight, epilogue
        rows and output blocks, the padded scratch, and the largest
        value, one output row's accumulator."""
        s = sublane_for(dtype_bytes)
        cl = round_up(self.c_block, LANE)
        db = dtype_bytes
        vmem = (2 * self.h * round_up(self.w, s) * cl * db
                + 2 * self.ho * round_up(self.wo, s) * cl * db
                + 2 * round_up(self.hk * self.wk, s) * cl * db
                + 2 * 4 * s * cl * 4
                + (self.h + 2 * self.py) * round_up(self.scratch_w, s)
                * cl * db)
        return vmem, self.wo_pad * cl * 4

    def bound_words(self, layer) -> float:
        """The depthwise bound (once per word), with the unfused norm's
        pass on neither side: the plan is judged on the conv alone."""
        from repro.core.lower_bound import q_dram_depthwise

        return q_dram_depthwise(layer)


def _dw_candidates(c: int, mosaic: bool) -> list[int]:
    """Channel blocks that divide ``c``, largest first: the whole of it,
    then (under Mosaic) LANE multiples."""
    divs = [d for d in range(c, 0, -1) if c % d == 0]
    return [d for d in divs if d == c or d % LANE == 0] if mosaic else divs


@lru_cache(maxsize=1024)
def plan_conv_dw(h: int, w: int, c: int, hk: int, wk: int, *,
                 padding=(0, 0), norm: bool = False,
                 dtype_bytes: int = 4, vmem_budget: int | None = None,
                 target: str = "interpret") -> DepthwisePlan:
    """The largest channel block whose working set fits the budget
    (fewest grid steps and, with ``norm``, the block that spans every
    channel fuses the LayerNorm).  Raises
    :class:`~repro.analysis.plan_check.PlanLegalityError` where no
    block fits."""
    from repro.analysis.plan_check import (Diagnostic, PlanLegalityError,
                                           check_dw_plan, errors)

    budget = VMEM_LIMIT_BYTES if vmem_budget is None else vmem_budget
    py, px = padding
    mosaic = target == "mosaic"
    for cb in _dw_candidates(c, mosaic):
        plan = DepthwisePlan(h=h, w=w, c=c, hk=hk, wk=wk, py=py, px=px,
                             c_block=cb, norm=norm and cb == c,
                             norm_pass=norm and cb < c, target=target)
        if not errors(check_dw_plan(plan, dtype_bytes=dtype_bytes,
                                    vmem_budget=budget, target=target)):
            return plan
    raise PlanLegalityError([Diagnostic(
        rule="dw.vmem", severity="error",
        message=f"no {target}-legal channel block fits the {budget} B "
                f"budget for a depthwise k{hk}x{wk} conv of {c} "
                f"channels on {h}x{w}",
        hint="a plane this large needs row tiles")])


def _dw_kernel(*refs, hk: int, wk: int, py: int, px: int, h: int,
               ho: int, wo: int, wo_pad: int, relu: bool,
               gelu_act: bool, has_bias: bool, has_scale: bool,
               has_norm: bool):
    refs = list(refs)
    x_ref, w_ref = refs[:2]
    rest = refs[2:]
    b_ref = rest.pop(0) if has_bias else None
    s_ref = rest.pop(0) if has_scale else None
    n_ref = rest.pop(0) if has_norm else None
    o_ref, pad_ref = rest
    cb = pad_ref.shape[-1]
    zero_row = jnp.zeros(pad_ref.shape[1:], pad_ref.dtype)

    # the conv padding lives on chip: zero the padded plane, then lay
    # the image's rows into its interior
    def zero(r, _):
        pad_ref[r] = zero_row
    jax.lax.fori_loop(0, pad_ref.shape[0], zero, None)

    def fill(r, _):
        pad_ref[py + r, pl.ds(px, x_ref.shape[2]), :] = x_ref[0, r]
    jax.lax.fori_loop(0, h, fill, None)

    bias = None if b_ref is None else b_ref[...]
    scale = None if s_ref is None else s_ref[...]
    norm = None if n_ref is None else (n_ref[0:1], n_ref[1:2])

    def row(r, _):
        acc = jnp.zeros((wo_pad, cb), jnp.float32)
        for ky in range(hk):               # unrolled k x k taps, each a
            for kx in range(wk):           # shifted row times one weight
                xs = pad_ref[r + ky, pl.ds(kx, wo_pad), :]
                acc = acc + xs.astype(jnp.float32) * w_ref[
                    pl.ds(ky * wk + kx, 1), :].astype(jnp.float32)
        y = epilogue(acc, bias=bias, gelu_act=gelu_act, scale=scale,
                     relu=relu, norm=norm)
        o_ref[0, r] = y[:wo].astype(o_ref.dtype)
    jax.lax.fori_loop(0, ho, row, None)


def conv_dw_call(x: jax.Array, w: jax.Array, plan: DepthwisePlan, *,
                 bias: jax.Array | None = None,
                 scale: jax.Array | None = None,
                 norm: jax.Array | None = None,
                 relu: bool = False, gelu_act: bool = False,
                 interpret: bool = True) -> jax.Array:
    """x: (B, H, W, C) unpadded NHWC; w: (Hk, Wk, 1, C); ``bias`` and
    ``scale``: (C,) or None; ``norm``: the (2, C) LayerNorm weight and
    bias rows, fused when the plan holds every channel, else applied as
    a pass of its own.  -> (B, Ho, Wo, C) through one ``pallas_call``
    named ``conv_dw``."""
    b, h, wd, c = x.shape
    p = plan
    assert (h, wd, c) == (p.h, p.w, p.c), ((h, wd, c), p)
    cb = p.c_block
    kern = functools.partial(
        _dw_kernel, hk=p.hk, wk=p.wk, py=p.py, px=p.px, h=h, ho=p.ho,
        wo=p.wo, wo_pad=p.wo_pad, relu=relu, gelu_act=gelu_act,
        has_bias=bias is not None, has_scale=scale is not None,
        has_norm=norm is not None and p.norm)
    in_specs = [
        pl.BlockSpec((1, h, wd, cb), lambda ci, bi: (bi, 0, 0, ci)),
        pl.BlockSpec((p.hk * p.wk, cb), lambda ci, bi: (0, ci)),
    ]
    operands = [x, w.reshape(p.hk * p.wk, c)]
    for rows in (bias, scale):
        if rows is not None:
            in_specs.append(pl.BlockSpec((1, cb), lambda ci, bi: (0, ci)))
            operands.append(rows.reshape(1, c).astype(jnp.float32))
    if norm is not None and p.norm:
        in_specs.append(pl.BlockSpec((2, cb), lambda ci, bi: (0, ci)))
        operands.append(norm.astype(jnp.float32))
    y = pl.pallas_call(
        kern,
        grid=(p.n_blocks, b),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, p.ho, p.wo, cb),
                               lambda ci, bi: (bi, 0, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((b, p.ho, p.wo, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((h + 2 * p.py, p.scratch_w, cb),
                                   x.dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="conv_dw",
    )(*operands)
    if norm is not None and p.norm_pass:
        with jax.named_scope("layer_norm"):
            y = layer_norm(y.astype(jnp.float32), norm[0],
                           norm[1]).astype(y.dtype)
    return y
