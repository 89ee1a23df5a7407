"""Paper-dataflow convolution Pallas kernel — batch-folded u x z tiling
with a fused epilogue (Fig. 6/7 + Eq. 13-15).

Realizes the paper's psum-stationary u x z output block on TPU.  The
bound (Eq. 13-15) is over *output elements* u = B*Ho*Wo, so batch is a
first-class tiling dimension, not a degenerate outermost grid axis: a
``b_block`` of images folds into the u-dimension of every psum tile.

  grid = (batch-blocks, y-tiles, x-tiles, Co-blocks, Ci-blocks)

Per grid step:
  * the psum block — ``(bb, ty, tx, co_b)``, i.e. the paper's u x z
    block with u = bb*ty*tx — is resident in VMEM scratch across the
    whole Ci sweep (OutR: psums never touch HBM, every output is
    written exactly once);
  * a Ci-slice of the *halo-extended* input tile for all ``bb`` images
    is streamed in through an overlapping, element-indexed
    (``pl.Element``) BlockSpec — neighbouring spatial tiles re-read
    only the (Wk-1)/(Hk-1) halo rows/cols, and all Wk*Hk shifted
    windows are strided loads from the one VMEM-resident tile (WndR on
    chip); batch rows add u without adding halo;
  * the matching z-kernel weight slice is streamed **once per u x z
    block regardless of bb** — ``reads_w`` stops scaling with batch:
    folding b images into one block divides the weight traffic of the
    layer by ``b_block`` (the batch-reuse term of Eq. (14)).

The Hk x Wk window loop is unrolled in-kernel: each offset is one
(bb*ty*tx, ci_b) x (ci_b, co_b) MXU matmul — the implicit-GEMM form of
the convolution-to-MM conversion of paper Fig. 3.  Stride and dilation
are folded into the in-VMEM strided slice, so WndR survives both.

Fused epilogue (applied inside the flush step, while the psum tile is
still in VMEM), in this order: optional ``bias`` add, exact (erf)
``gelu``, a per-channel layer ``scale``, the ``residual`` join,
``relu``, a LayerNorm over the channels (``norm``; the Co block spans
every channel), and an aligned ``pool`` x ``pool`` max-pool (stride =
pool, VALID: a leading-dim split for the rows, strided loads through
a lane-wide scratch for the columns).  This collapses a
CNN layer's ``conv-write -> read -> bias/relu/pool -> write`` HBM round
trip into the single mandatory output write — with pooling the write
volume itself drops by pool**2.

Tiling contract (``ops.py`` enforces it by padding):
  * B % b_block == 0, Ci % ci_block == 0, Co % co_block == 0;
  * the padded output plane divides the spatial tile:
    Ho % y_block == 0 and Wo % x_block == 0;
  * with pooling: y_block % pool == 0, x_block % pool == 0 (tiles
    start at pool-aligned rows, so pool windows never straddle tiles),
    and the *true* Ho/Wo are divisible by pool;
  * the input is padded so every tile's halo read stays in bounds:
    Hp == (Ho-1)*stride_y + (Hk-1)*dil_y + 1 (same for W);
  * ``bias`` arrives as a (1, Co) row so the (1, co_block) slice rides
    the same Co-block sweep as the weights.

Lhs-dilated planes (``lhs_dilation != (1, 1)``) — the strided-dgrad /
transposed-conv geometry: the *logical* input plane is the forward
stride's zero-dilation of a compact plane (``stride-1`` zeros between
rows/cols), but HBM only ever holds the compact plane.  The BlockSpec
walks the compact plane — each tile fetches the ``ceil``-shrunk halo —
and the kernel re-inserts the zeros in VMEM — one strided store of
the fetch into a zeroed scratch — before the window sweep, so the
dilated tile is
materialized on chip from a compact fetch: traffic scales with the
compact (true dy) plane, not the dilated one.  Phase contract: the
per-tile input offset ``y_block*stride_y`` must divide by the lhs
dilation so every compact fetch starts on a real row (``ops.py`` snaps
tiles accordingly); ``pad=(py, px)`` carries the conv padding of the
*dilated* plane so the kernel can place the first real row at
``ceil(py/ld)*ld - py`` inside the reconstructed tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tpu_adapter import LANE, VMEM_LIMIT_BYTES


def halo_dims(y_block: int, x_block: int, hk: int, wk: int,
              stride: tuple[int, int], dilation: tuple[int, int]
              ) -> tuple[int, int]:
    """Input footprint (yp, xp) of one (y_block, x_block) output tile."""
    yp = (y_block - 1) * stride[0] + (hk - 1) * dilation[0] + 1
    xp = (x_block - 1) * stride[1] + (wk - 1) * dilation[1] + 1
    return yp, xp


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def compact_halo(halo: int, ld: int, pad: int) -> int:
    """Compact rows fetched per tile on one lhs-dilated axis: the
    ``ceil``-shrunk image of a ``halo``-row dilated window, phase-
    shifted by the conv padding (``ceil(pad/ld)`` leading zero-rows)."""
    if ld == 1:
        return halo
    return ceil_div(pad, ld) + max(1, ceil_div(halo - pad, ld))


def compact_axis_dims(block: int, halo: int, stride: int, ld: int,
                      pad: int) -> tuple[int, int, int]:
    """Compact-plane walk geometry for one lhs-dilated axis.

    Returns ``(chalo, step, off)``: the compact rows fetched per tile,
    the compact-row advance between neighbouring tiles, and the local
    offset of logical dilated row 0 inside the reconstructed VMEM tile
    (``ceil(pad/ld)*ld - pad``, the phase shift that aligns the conv
    padding onto the zero-dilation grid).  Requires the dilated-plane
    tile offset ``block*stride`` to divide by ``ld``."""
    if ld == 1:
        return halo, block * stride, 0
    assert (block * stride) % ld == 0, (block, stride, ld)
    off = ceil_div(pad, ld) * ld - pad      # in [0, ld)
    return compact_halo(halo, ld, pad), (block * stride) // ld, off


# LayerNorm's epsilon (ConvNeXt's, for every norm of the network)
NORM_EPS = 1e-6

# erf(x) = x * P(x^2) / Q(x^2) on [-4, 4], float32 erf's rational
# approximation (Eigen's, which XLA's float32 erf also uses); erf is
# +-1 to float32 rounding outside.  Mosaic has no lowering rule for
# lax.erf, so a kernel epilogue builds it from these primitives.
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08,
          -2.10102402082508e-06, -5.69250639462346e-05,
          -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


def erf(x):
    """float32 erf from multiplies, adds and a divide."""
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p = functools.reduce(lambda acc, c: acc * x2 + c, _ERF_P[1:],
                         jnp.full_like(x2, _ERF_P[0]))
    q = functools.reduce(lambda acc, c: acc * x2 + c, _ERF_Q[1:],
                         jnp.full_like(x2, _ERF_Q[0]))
    return x * p / q


def gelu(x):
    """Exact GELU, x * Phi(x), through :func:`erf`."""
    return 0.5 * x * (1.0 + erf(x * 0.7071067811865476))


def layer_norm(x, w, b):
    """LayerNorm over the last (channel) axis, biased variance."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    d = x - mean
    var = jnp.mean(d * d, axis=-1, keepdims=True)
    return d * jax.lax.rsqrt(var + NORM_EPS) * w + b


def epilogue(acc, *, bias=None, gelu_act=False, scale=None,
             residual=None, relu=False, norm=None):
    """The fused epilogue on an f32 value whose last axis is channels:
    bias, GELU, layer scale, residual join, ReLU, LayerNorm (``norm``
    is its ``(w, b)`` rows).  Each absent step adds no operation."""
    if bias is not None:
        acc = acc + bias
    if gelu_act:
        acc = gelu(acc)
    if scale is not None:
        acc = acc * scale
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    if norm is not None:
        acc = layer_norm(acc, *norm)
    return acc


def _conv_kernel(*refs, nci: int, hk: int, wk: int,
                 bb: int, ty: int, tx: int,
                 stride: tuple[int, int], dilation: tuple[int, int],
                 lhs_dilation: tuple[int, int],
                 off: tuple[int, int], chalo: tuple[int, int],
                 has_bias: bool, has_residual: bool, relu: bool,
                 pool: int, gelu_act: bool = False,
                 has_scale: bool = False, has_norm: bool = False):
    refs = list(refs)
    x_ref, w_ref = refs[:2]
    rest = refs[2:]
    b_ref = rest.pop(0) if has_bias else None
    s_ref = rest.pop(0) if has_scale else None
    n_ref = rest.pop(0) if has_norm else None
    r_ref = rest.pop(0) if has_residual else None
    o_ref, acc_ref = rest[:2]

    @pl.when(pl.program_id(4) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sy, sx = stride
    dy, dx = dilation
    ldy, ldx = lhs_dilation
    offy, offx = off
    cib = x_ref.shape[-1]
    cob = acc_ref.shape[-1]
    src = x_ref
    if (ldy, ldx) != (1, 1):
        # compact fetch -> dilated VMEM tile: one strided store places
        # the compact rows/cols on the dilation grid of a zeroed
        # scratch (the stride-1 zero rows/cols, plus a short hi edge so
        # the last window's slice stays in bounds)
        src = rest[2]
        src[...] = jnp.zeros_like(src)
        src[:, pl.ds(0, chalo[0], stride=ldy),
            pl.ds(0, chalo[1], stride=ldx), :] = x_ref[...]
    for ky in range(hk):                      # unrolled window sweep:
        for kx in range(wk):                  # WndR served from VMEM
            xs = src[:, pl.ds(offy + ky * dy, ty, stride=sy),
                     pl.ds(offx + kx * dx, tx, stride=sx), :]
            acc_ref[...] += jnp.dot(
                xs.reshape(bb * ty * tx, cib), w_ref[ky, kx],
                preferred_element_type=jnp.float32
            ).reshape(bb, ty, tx, cob)

    @pl.when(pl.program_id(4) == nci - 1)
    def _flush():
        # fused epilogue: the psum tile is still in VMEM; the residual
        # join lands before the ReLU, the norm spans the whole Co block
        acc = epilogue(
            acc_ref[...], bias=None if b_ref is None else b_ref[0],
            gelu_act=gelu_act, scale=None if s_ref is None else s_ref[0],
            residual=None if r_ref is None else r_ref[...], relu=relu,
            norm=None if n_ref is None else (n_ref[0], n_ref[1]))
        if pool > 1:
            # rows: max over a leading-dim split of the finished tile
            acc = acc.reshape(bb, ty // pool, pool, tx, cob)
            acc = functools.reduce(jnp.maximum,
                                   [acc[:, :, i] for i in range(pool)])
            # cols: strided sublane reads through a scratch, one
            # LANE-wide channel chunk at a time (Mosaic's strided load
            # takes at most one lane tile as its base's last dim)
            pool_ref = rest[-1]
            lanes = pool_ref.shape[-1]
            parts = []
            for c in range(cob // lanes):
                pool_ref[...] = acc[..., c * lanes:(c + 1) * lanes]
                parts.append(functools.reduce(jnp.maximum, [
                    pool_ref[:, :, pl.ds(j, tx // pool, stride=pool), :]
                    for j in range(pool)]))
            acc = (parts[0] if len(parts) == 1
                   else jnp.concatenate(parts, axis=-1))
        o_ref[...] = acc.astype(o_ref.dtype)


def conv_lb_call(x: jax.Array, w: jax.Array, *,
                 bias: jax.Array | None = None,
                 residual: jax.Array | None = None,
                 relu: bool = False, pool: int = 1,
                 gelu_act: bool = False,
                 scale: jax.Array | None = None,
                 norm: jax.Array | None = None,
                 stride: tuple[int, int] = (1, 1),
                 dilation: tuple[int, int] = (1, 1),
                 lhs_dilation: tuple[int, int] = (1, 1),
                 pad: tuple[int, int] = (0, 0),
                 out_plane: tuple[int, int] | None = None,
                 b_block: int = 1,
                 y_block: int, x_block: int,
                 ci_block: int, co_block: int,
                 out_dtype=None, interpret: bool = True,
                 name: str = "conv_fwd") -> jax.Array:
    """x: (B, Hp, Wp, Ci) pre-padded NHWC; w: (Hk, Wk, Ci, Co);
    bias: (1, Co) or None; residual: (B, Ho, Wo, Co) pre-pool tensor
    added on the psum tile before the ReLU (the residual join of a
    BasicBlock, served by one streamed read per output tile instead of
    a separate HBM round trip) or None.  ``gelu_act``, ``scale`` (a
    (1, Co) row) and ``norm`` (the (2, Co) LayerNorm weight and bias
    rows; the Co block must be the whole of Co) extend the epilogue,
    in :func:`epilogue`'s order.

    With ``lhs_dilation != (1, 1)`` x is the *compact* plane (zeros not
    materialized); ``pad`` is the conv padding of the logical dilated
    plane and ``out_plane`` the padded (Ho, Wo) — both required because
    neither is derivable from the compact shape alone.

    ``name`` names the kernel on the device (the HLO instruction a
    profile shows): ``conv_fwd``, or ``conv_dgrad`` for a backward's
    data-gradient conv.

    See the module docstring for the padding/divisibility contract."""
    b, hp, wp, ci = x.shape
    hk, wk, ci2, co = w.shape
    sy, sx = stride
    dy, dx = dilation
    ldy, ldx = lhs_dilation
    lhs_dilated = (ldy, ldx) != (1, 1)
    assert ci == ci2 and ci % ci_block == 0 and co % co_block == 0
    assert b % b_block == 0, (b, b_block)
    if lhs_dilated:
        assert out_plane is not None, "lhs-dilated calls need out_plane"
        ho, wo = out_plane
    else:
        ho = (hp - ((hk - 1) * dy + 1)) // sy + 1
        wo = (wp - ((wk - 1) * dx + 1)) // sx + 1
    assert ho % y_block == 0 and wo % x_block == 0, (
        f"output plane {ho}x{wo} does not divide tile "
        f"{y_block}x{x_block}; ops.py must pad")
    assert y_block % pool == 0 and x_block % pool == 0, (
        f"tile {y_block}x{x_block} not divisible by pool={pool}")
    nb, ny, nx = b // b_block, ho // y_block, wo // x_block
    nci, nco = ci // ci_block, co // co_block
    yp, xp = halo_dims(y_block, x_block, hk, wk, stride, dilation)
    chalo_y, step_y, offy = compact_axis_dims(y_block, yp, sy, ldy,
                                              pad[0])
    chalo_x, step_x, offx = compact_axis_dims(x_block, xp, sx, ldx,
                                              pad[1])
    # rows of the reconstructed dilated tile, extended hi so the
    # deepest window slice (off + halo rows) stays in bounds
    rec_y = max(offy + yp, (chalo_y - 1) * ldy + 1)
    rec_x = max(offx + xp, (chalo_x - 1) * ldx + 1)
    if lhs_dilated:
        assert hp >= (ny - 1) * step_y + chalo_y, (hp, ny, step_y,
                                                   chalo_y)
        assert wp >= (nx - 1) * step_x + chalo_x, (wp, nx, step_x,
                                                   chalo_x)
    out_dtype = out_dtype or x.dtype
    if residual is not None:
        assert residual.shape == (b, ho, wo, co), (residual.shape,
                                                   (b, ho, wo, co))
    kern = functools.partial(_conv_kernel, nci=nci, hk=hk, wk=wk,
                             bb=b_block, ty=y_block, tx=x_block,
                             stride=stride, dilation=dilation,
                             lhs_dilation=lhs_dilation,
                             off=(offy, offx), chalo=(chalo_y, chalo_x),
                             has_bias=bias is not None,
                             has_residual=residual is not None,
                             relu=relu, pool=pool, gelu_act=gelu_act,
                             has_scale=scale is not None,
                             has_norm=norm is not None)
    if norm is not None:
        assert co_block == co, "a fused norm needs every channel"
    in_specs = [
        # overlapping halo tile: element offsets, not block indices
        # (Mosaic takes Element on every dim or none) — an
        # lhs-dilated walk strides the compact plane instead
        pl.BlockSpec(
            (pl.Element(b_block), pl.Element(chalo_y),
             pl.Element(chalo_x), pl.Element(ci_block)),
            lambda bi, yi, xi, coi, cii: (
                bi * b_block, yi * step_y,
                # a sole x tile / Ci block starts at literal 0, so
                # Mosaic can prove the tiled dims' offsets aligned
                0 if nx == 1 else xi * step_x,
                0 if nci == 1 else cii * ci_block)),
        pl.BlockSpec((hk, wk, ci_block, co_block),
                     lambda bi, yi, xi, coi, cii: (0, 0, cii, coi)),
    ]
    operands = [x, w]
    if bias is not None:
        in_specs.append(pl.BlockSpec(
            (1, co_block), lambda bi, yi, xi, coi, cii: (0, coi)))
        operands.append(bias)
    for rows in (scale, norm):
        if rows is not None:
            in_specs.append(pl.BlockSpec(
                (rows.shape[0], co_block),
                lambda bi, yi, xi, coi, cii: (0, coi)))
            operands.append(rows)
    if residual is not None:
        # pre-pool psum-tile geometry: one streamed fetch per
        # (bi, yi, xi, coi) — the Ci sweep never re-reads it
        in_specs.append(pl.BlockSpec(
            (b_block, y_block, x_block, co_block),
            lambda bi, yi, xi, coi, cii: (bi, yi, xi, coi)))
        operands.append(residual)
    scratch = [pltpu.VMEM((b_block, y_block, x_block, co_block),
                          jnp.float32)]
    if lhs_dilated:
        scratch.append(pltpu.VMEM((b_block, rec_y, rec_x, ci_block),
                                  x.dtype))
    if pool > 1:
        lanes = co_block if co_block % LANE else LANE
        scratch.append(pltpu.VMEM((b_block, y_block // pool, x_block,
                                   lanes), jnp.float32))
    return pl.pallas_call(
        kern,
        grid=(nb, ny, nx, nco, nci),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (b_block, y_block // pool, x_block // pool, co_block),
            lambda bi, yi, xi, coi, cii: (bi, yi, xi, coi)),
        out_shape=jax.ShapeDtypeStruct(
            (b, ho // pool, wo // pool, co), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(*operands)
