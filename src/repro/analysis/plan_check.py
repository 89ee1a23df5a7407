"""Static conv/matmul plan verifier: Mosaic legality + traffic audit.

Every traffic ratio this repo publishes rests on two assumptions that
were, until this module, unverified at rest:

  1. the accountant's :meth:`ConvPlan.traffic` matches the HBM words
     the kernel's BlockSpecs actually move (Pallas' refetch rule);
  2. the autotuner's winning plans are *executable* — their blocks
     respect the Mosaic/MXU tiling constraints a compiled
     (``interpret=False``) ``pallas_call`` enforces, fit the VMEM
     budget with double-buffering, and never index out of bounds.

Demmel & Dinh (*Communication-Optimal Convolutional Neural Nets*,
2018) warn precisely about tilings that attain the bound on paper but
violate hardware tiling constraints; the ROADMAP's compiled-mode item
records that the autotuner's favourite ASIC-budget plans (tiny
``ci_block``) are exactly that.  This module makes both assumptions
*checkable without running a kernel*:

  * **Legality pass** — :func:`check_conv_plan` /
    :func:`check_wgrad_plan` / :func:`check_matmul_block` verify a
    plan against structural rules (VMEM fit including double-buffered
    operands and the residual/bias epilogue panels, grid
    divisibility, halo-extended input windows in bounds, psum tile
    shape, pool alignment — always ``error``) and Mosaic alignment
    rules (``SUBLANE``/``LANE`` tiles per dtype, the full-row x tile,
    the compiled working set and tile size, MXU reduction fill —
    ``error`` under the ``mosaic`` target, ``warn`` under
    ``interpret``), returning structured
    :class:`Diagnostic` records with rule ids and repair hints.
    Conv and matmul share one rule implementation
    (:func:`_lane_rule` / :func:`_sublane_rule`), so every kernel
    family inherits the same gate.

  * **Traffic cross-audit** — :func:`symbolic_conv_traffic` /
    :func:`symbolic_wgrad_traffic` / :func:`symbolic_bound_words`
    re-derive the per-operand HBM word counts and the Eq. (15) bound
    from the block geometry through a second, simpler derivation
    (fetch-count × block-volume, ceil divisions of the *true* dims)
    and :func:`audit_handles` asserts exact agreement with the
    accountant for every plan — accountant drift becomes a test
    failure, not a silent benchmark lie.

  * **Graph audit** — :func:`audit_graph` runs both passes over every
    node of a :class:`~repro.models.graph.ConvGraph` (forward, dgrad
    and wgrad plans), producing the ``plans checked / plans legal``
    counts the benchmark gate tracks.

Targets: ``TARGET_INTERPRET`` is the accounting profile (structural
rules are errors; Mosaic alignment demoted to warnings — ASIC-budget
accounting plans are *meant* to be hardware-agnostic), and
``TARGET_MOSAIC`` is the compiled-execution profile where alignment
violations are errors — the gate for flipping ``interpret=False``.
"""

from __future__ import annotations

import dataclasses
import math

from repro.core.dataflow import Traffic
from repro.core.layer import ceil_div
from repro.core.tpu_adapter import (LANE, MOSAIC_TILE_BYTES, MXU_DIM,
                                    VMEM_LIMIT_BYTES, round_up,
                                    sublane_for)

TARGET_INTERPRET = "interpret"
TARGET_MOSAIC = "mosaic"

ERROR = "error"
WARN = "warn"

#: rule id -> one-line meaning (the README's rule table renders this)
RULES = {
    "conv.grid": "padded output/channel dims must divide the blocks "
                 "(Pallas grid = padded // block exactly)",
    "conv.halo": "the halo-extended input window of every tile must "
                 "stay inside the padded input plane",
    "conv.pool": "a fused pool must divide the spatial blocks and the "
                 "true output plane (windows never straddle tiles)",
    "conv.vmem": "psums + double-buffered operand panels (+ residual "
                 "join panel, + pinned-weight single buffer) must fit "
                 "the VMEM budget",
    "conv.lhsdil": "an lhs-dilated plan's compact fetches must start "
                   "on the dilation phase (block*stride divisible by "
                   "lhs_dilation) and fuse no pool/residual epilogue",
    "conv.norm": "a fused LayerNorm needs the Co block to span every "
                 "output channel (no Co padding)",
    "dw.block": "a depthwise channel block must divide the channels "
                "and, under Mosaic, be a LANE multiple or all of them",
    "dw.norm": "a depthwise LayerNorm is fused only where the channel "
               "block spans every channel, and runs as a pass of its "
               "own otherwise",
    "dw.vmem": "a depthwise plane's double-buffered input/output/weight "
               "blocks and its padded scratch must fit the VMEM budget",
    "wgrad.vmem": "resident f32 dW block + double-buffered x/dy "
                  "strips must fit the VMEM budget",
    "wgrad.grid": "dW channel blocks must not exceed the layer's "
                  "channel counts",
    "wgrad.strip": "the lagged carry must cover the strip halo "
                   "(lag * strip*stride >= ekh - stride) so the "
                   "rolling disjoint fetches stay exact",
    "matmul.shape": "block dims must be positive and not exceed the "
                    "padded operand dims",
    "matmul.vmem": "psum block + double-buffered A/B panels must fit "
                   "the VMEM budget",
    "mosaic.lane": "a block's last dim must be a LANE (128) multiple "
                   "or cover the full (padded) array dim",
    "mosaic.sublane": "a block's second-minor dim must be a sublane "
                      "multiple for the dtype (f32 8 / bf16 16 / "
                      "int8 32) or cover the full dim",
    "mosaic.xtile": "the x tile is one sublane-aligned span of the "
                    "whole padded output row (the kernel merges "
                    "(b, y, x) rows, and fetches full-width halos)",
    "mosaic.vmem": "the compiled kernel's (sublane, LANE)-tiled "
                   "buffers and temporaries must fit the scoped VMEM "
                   "limit",
    "mosaic.tile": "no in-kernel value may exceed MOSAIC_TILE_BYTES "
                   "(Mosaic unrolls vector code over it)",
    "mosaic.stride": "a strided window read or lhs-dilation store is "
                     "a Mosaic strided access: its input block's last "
                     "dim must fit one LANE tile",
    "mosaic.mxu": "a reduction slice far below the 128-wide MXU "
                  "leaves the systolic array underfilled (perf, not "
                  "legality)",
    "autotune.vmem": "a search candidate was rejected because its "
                     "working set exceeds the VMEM budget",
    "autotune.mosaic": "a search candidate was snapped to (or "
                       "rejected for lacking) a Mosaic-legal shape "
                       "under the 'mosaic' target",
    "audit.traffic": "the symbolic traffic/bound re-derivation "
                     "disagrees with the accountant (planner or "
                     "accountant drift)",
}


@dataclasses.dataclass(frozen=True)
class Diagnostic:
    """One finding of the static verifier.

    ``rule`` indexes :data:`RULES`; ``severity`` is ``error`` (the
    plan must not execute / be served) or ``warn`` (legal under the
    current target, would block a stricter one); ``hint`` says how to
    repair the shape, not just that it is wrong."""

    rule: str
    severity: str
    message: str
    hint: str = ""
    where: str = ""

    def __str__(self) -> str:
        tail = f"  [{self.hint}]" if self.hint else ""
        head = f"{self.where}: " if self.where else ""
        return f"{self.severity}:{self.rule}: {head}{self.message}{tail}"


def errors(diags) -> list[Diagnostic]:
    return [d for d in diags if d.severity == ERROR]


def format_diagnostics(diags) -> str:
    return "\n".join(str(d) for d in diags) or "clean"


class PlanLegalityError(ValueError):
    """An auto-chosen plan failed the legality pass (a planner bug:
    the search must never emit a structurally illegal plan)."""

    def __init__(self, diags):
        self.diagnostics = list(diags)
        super().__init__("illegal plan:\n" + format_diagnostics(
            errors(self.diagnostics)))


# --------------------------------------------------------------------------
# shared Mosaic alignment rules (conv and matmul ride the same impls)
# --------------------------------------------------------------------------

def _mosaic_sev(target: str) -> str:
    return ERROR if target == TARGET_MOSAIC else WARN


def _lane_rule(block: int, full: int, operand: str, target: str,
               where: str = "") -> Diagnostic | None:
    """Last-dim tile rule: LANE multiple, or the block covers the
    whole (padded) dim so Mosaic pads the array internally."""
    if block % LANE == 0 or block >= full:
        return None
    legal = min(full, -(-block // LANE) * LANE)
    return Diagnostic(
        rule="mosaic.lane", severity=_mosaic_sev(target), where=where,
        message=f"{operand} last dim {block} is neither a multiple of "
                f"{LANE} nor the full dim {full}",
        hint=f"grow to {legal} (or the full {full})")


def _sublane_rule(block: int, full: int, dtype_bytes: int,
                  operand: str, target: str,
                  where: str = "") -> Diagnostic | None:
    """Second-minor tile rule, keyed by the word size."""
    sub = sublane_for(dtype_bytes)
    if block % sub == 0 or block >= full:
        return None
    legal = min(full, -(-block // sub) * sub)
    return Diagnostic(
        rule="mosaic.sublane", severity=_mosaic_sev(target), where=where,
        message=f"{operand} second-minor dim {block} is not a "
                f"{sub}-row tile ({dtype_bytes}-byte words) nor the "
                f"full dim {full}",
        hint=f"grow to {legal} (or the full {full})")


def _err(rule: str, message: str, hint: str = "",
         where: str = "") -> Diagnostic:
    return Diagnostic(rule=rule, severity=ERROR, message=message,
                      hint=hint, where=where)


# --------------------------------------------------------------------------
# legality pass: ConvPlan
# --------------------------------------------------------------------------

def check_conv_plan(plan, *, batch: int = 1, dtype_bytes: int = 4,
                    vmem_budget: int | None = None,
                    target: str = TARGET_INTERPRET,
                    where: str = "") -> list[Diagnostic]:
    """Verify one :class:`~repro.kernels.conv_lb.ops.ConvPlan` against
    the structural contract ``conv_lb_call`` asserts at trace time
    (re-derived independently here, so planner drift is caught
    *before* any kernel is built) plus the Mosaic tiling rules a
    compiled ``pallas_call`` would enforce."""
    budget = VMEM_LIMIT_BYTES if vmem_budget is None else vmem_budget
    blk = plan.blocks
    sy, sx = plan.stride
    ekh = (plan.hk - 1) * plan.dilation[0] + 1
    ekw = (plan.wk - 1) * plan.dilation[1] + 1
    diags: list[Diagnostic] = []

    # -- structural: grid divisibility ------------------------------------
    for name, dim, b in (("ho_pad", plan.ho_pad, blk.y),
                         ("wo_pad", plan.wo_pad, blk.x),
                         ("ci_pad", plan.ci_pad, blk.ci),
                         ("co_pad", plan.co_pad, blk.co)):
        if b < 1 or dim % b:
            diags.append(_err(
                "conv.grid", f"{name}={dim} does not divide its block "
                f"{b}", hint=f"pad {name} to a multiple of {b}",
                where=where))
    for name, dim, true in (("ho", plan.ho_pad, plan.ho),
                            ("wo", plan.wo_pad, plan.wo),
                            ("ci", plan.ci_pad, plan.ci),
                            ("co", plan.co_pad, plan.co)):
        if true and dim < true:
            diags.append(_err(
                "conv.grid", f"padded {name} {dim} is smaller than "
                f"the true dim {true}", where=where))

    # -- structural: halo windows in bounds -------------------------------
    want_hy = (blk.y - 1) * sy + ekh
    want_hx = (blk.x - 1) * sx + ekw
    if (blk.halo_y, blk.halo_x) != (want_hy, want_hx):
        diags.append(_err(
            "conv.halo", f"halo ({blk.halo_y}, {blk.halo_x}) does not "
            f"match the tile's input footprint ({want_hy}, {want_hx})",
            hint="halos belong to the tile: (t-1)*stride + dilated "
                 "kernel extent", where=where))
    if plan.ho_pad // max(1, blk.y):
        last_y = (plan.ho_pad // blk.y - 1) * blk.y * sy + blk.halo_y
        last_x = (plan.wo_pad // blk.x - 1) * blk.x * sx + blk.halo_x
        if last_y > plan.hp_pad or last_x > plan.wp_pad:
            diags.append(_err(
                "conv.halo", f"last tile's halo reads "
                f"({last_y}, {last_x}) past the padded input plane "
                f"({plan.hp_pad}, {plan.wp_pad})",
                hint="pad the input to the last tile's halo end",
                where=where))

    # -- structural: lhs-dilated compact-plane walk -----------------------
    if getattr(plan, "lhs_dilated", False):
        ldy, ldx = plan.lhs_dilation
        for name, bv, s, ld in (("y", blk.y, sy, ldy),
                                ("x", blk.x, sx, ldx)):
            if ld > 1 and (bv * s) % ld:
                diags.append(_err(
                    "conv.lhsdil",
                    f"{name}-block {bv} * stride {s} is not a multiple "
                    f"of lhs_dilation {ld} — compact fetches would "
                    f"start mid-phase",
                    hint="snap the block so block*stride % lhs_dilation"
                         " == 0", where=where))
        if plan.pool > 1 or plan.residual:
            diags.append(_err(
                "conv.lhsdil", "lhs-dilated plans fuse no "
                "pool/residual epilogue", where=where))

    # -- structural: fused pool alignment ---------------------------------
    if plan.pool > 1:
        if blk.y % plan.pool or blk.x % plan.pool:
            diags.append(_err(
                "conv.pool", f"tile {blk.y}x{blk.x} is not divisible "
                f"by the fused pool {plan.pool}",
                hint="snap spatial blocks to pool multiples",
                where=where))
        if plan.ho % plan.pool or plan.wo % plan.pool:
            diags.append(_err(
                "conv.pool", f"output plane {plan.ho}x{plan.wo} is "
                f"not divisible by the fused pool {plan.pool}",
                where=where))

    # -- structural: a fused norm sees every channel ----------------------
    if getattr(plan, "norm", False) and (blk.co < plan.co
                                         or plan.co_pad != plan.co):
        diags.append(_err(
            "conv.norm", f"a fused LayerNorm over {plan.co} channels "
            f"sits on a {blk.co}-channel block", where=where))

    # -- structural: VMEM fit (double-buffered, epilogue-aware) -----------
    pinned = blk.ci >= plan.ci_pad and blk.co >= plan.co_pad
    need = blk.vmem_bytes(plan.hk, plan.wk, dtype_bytes,
                          w_pinned=pinned, residual=plan.residual)
    if need > budget:
        diags.append(_err(
            "conv.vmem", f"working set {need} B exceeds the "
            f"{budget} B budget (psum {blk.psum_bytes} B + "
            f"double-buffered panels{' + residual join panel' if plan.residual else ''})",
            hint="shrink ci/batch blocks first (they only cost "
                 "memory), then the spatial tile", where=where))

    # -- Mosaic alignment (error only under the mosaic target) ------------
    d = _lane_rule(blk.co, plan.co_pad, "psum/output/weight block",
                   target, where)
    if d:
        diags.append(d)
    d = _lane_rule(blk.ci, plan.ci_pad, "input block", target, where)
    if d:
        diags.append(d)
    d = _sublane_rule(blk.x // max(1, plan.pool),
                      plan.wo_pad // max(1, plan.pool), dtype_bytes,
                      "output block", target, where)
    if d:
        diags.append(d)
    d = _sublane_rule(blk.ci, plan.ci_pad, dtype_bytes,
                      "weight block", target, where)
    if d:
        diags.append(d)
    sub = sublane_for(dtype_bytes)
    if plan.wo_pad // blk.x > 1 or blk.x % sub:
        diags.append(Diagnostic(
            rule="mosaic.xtile", severity=_mosaic_sev(target),
            where=where,
            message=f"x tile {blk.x} is not one {sub}-row multiple "
                    f"spanning the padded output row {plan.wo_pad}",
            hint=f"make the x tile round_up(wo, {sub}); the kernel "
                 f"crops the pad"))
    strided = sy * sx * plan.lhs_dilation[0] * plan.lhs_dilation[1] > 1
    if strided and blk.ci > LANE:
        diags.append(Diagnostic(
            rule="mosaic.stride", severity=_mosaic_sev(target),
            where=where, message=f"strided access over a {blk.ci}-wide "
            f"input block", hint=f"cap ci_block at {LANE}"))
    if target == TARGET_MOSAIC:
        vmem, tile = plan.mosaic_working_set(dtype_bytes)
        if vmem > budget:
            diags.append(_err(
                "mosaic.vmem", f"compiled working set {vmem} B exceeds "
                f"the {budget} B scoped VMEM limit",
                hint="shrink the batch/y blocks, then co", where=where))
        if tile > MOSAIC_TILE_BYTES:
            diags.append(_err(
                "mosaic.tile", f"in-kernel tile of {tile} B exceeds "
                f"{MOSAIC_TILE_BYTES} B", hint="shrink the batch/y "
                "blocks", where=where))
    if blk.ci < min(MXU_DIM, plan.ci_pad):
        diags.append(Diagnostic(
            rule="mosaic.mxu", severity=WARN, where=where,
            message=f"reduction slice ci_block={blk.ci} underfills "
                    f"the {MXU_DIM}-wide MXU",
            hint="grow ci_block toward 128 when VMEM allows"))
    return diags


# --------------------------------------------------------------------------
# legality pass: DepthwisePlan (the channel-blocked conv_dw kernel)
# --------------------------------------------------------------------------

def check_dw_plan(plan, *, dtype_bytes: int = 4,
                  vmem_budget: int | None = None,
                  target: str = TARGET_INTERPRET,
                  where: str = "") -> list[Diagnostic]:
    """Verify one :class:`~repro.kernels.conv_lb.depthwise.DepthwisePlan`:
    the channel block divides the channels (and is Mosaic-tiled), the
    norm is fused exactly where the block spans every channel, and the
    working set fits."""
    budget = VMEM_LIMIT_BYTES if vmem_budget is None else vmem_budget
    cb, c = plan.c_block, plan.c
    diags: list[Diagnostic] = []
    if cb < 1 or c % cb:
        diags.append(_err("dw.block", f"channel block {cb} does not "
                          f"divide {c} channels", where=where))
    d = _lane_rule(cb, c, "depthwise channel block", target, where)
    if d:
        diags.append(d)
    if plan.norm and (cb != c or plan.norm_pass):
        diags.append(_err("dw.norm", f"a fused LayerNorm over {c} "
                          f"channels sits on a {cb}-channel block",
                          where=where))
    need = plan.vmem_bytes(dtype_bytes)
    if need > budget:
        diags.append(_err("dw.vmem", f"working set {need} B exceeds the "
                          f"{budget} B budget", where=where))
    if target == TARGET_MOSAIC:
        vmem, tile = plan.mosaic_working_set(dtype_bytes)
        if vmem > budget:
            diags.append(_err(
                "mosaic.vmem", f"compiled working set {vmem} B exceeds "
                f"the {budget} B scoped VMEM limit", where=where))
        if tile > MOSAIC_TILE_BYTES:
            diags.append(_err(
                "mosaic.tile", f"in-kernel row of {tile} B exceeds "
                f"{MOSAIC_TILE_BYTES} B", where=where))
    return diags


# --------------------------------------------------------------------------
# legality pass: WgradPlan (executed by the dW-stationary kernel)
# --------------------------------------------------------------------------

def check_wgrad_plan(wplan, *, batch: int = 1, dtype_bytes: int = 4,
                     vmem_budget: int | None = None,
                     target: str = TARGET_INTERPRET,
                     where: str = "") -> list[Diagnostic]:
    """Verify a dW-stationary :class:`WgradPlan`: the resident dW
    block plus double-buffered x/dy strips must fit the budget, the
    channel blocks must describe a real partition of the layer, and
    the lagged carry must cover the strip halo — the structural
    contract :func:`~repro.kernels.conv_lb.wgrad.wgrad_lb_call`
    executes.  Under the ``mosaic`` target the streamed panels also
    obey the lane tiling rules (the kernel's last dims are the
    channel blocks)."""
    budget = VMEM_LIMIT_BYTES if vmem_budget is None else vmem_budget
    diags: list[Diagnostic] = []
    for name, b, dim in (("ci_b", wplan.ci_b, wplan.ci),
                         ("co_b", wplan.co_b, wplan.co),
                         ("strip", wplan.strip, wplan.ho)):
        if b < 1 or b > dim:
            diags.append(_err(
                "wgrad.grid", f"{name}={b} outside [1, {dim}]",
                where=where))
    if diags:
        return diags
    # the lagged rolling fetch: carry rows must cover the halo strips
    # share, and the warm-up shift must be non-negative (re-derived
    # from the raw geometry, not through WgradPlan.lag)
    r_rows = wplan.strip * wplan.sy
    k_rows = max(0, wplan.ekh - wplan.sy)
    lag = -(-k_rows // r_rows) if k_rows > 0 else 0
    if wplan.lag != lag or lag * r_rows < k_rows:
        diags.append(_err(
            "wgrad.strip",
            f"lag {wplan.lag} x {r_rows}-row fetches cannot carry the "
            f"{k_rows}-row strip halo",
            hint="lag must be ceil((ekh - stride) / (strip*stride))",
            where=where))
    xrows = (wplan.strip - 1) * wplan.sy + wplan.ekh
    need = (4 * wplan.hk * wplan.wk * wplan.ci_b * wplan.co_b
            + 2 * dtype_bytes * xrows * wplan.wp * wplan.ci_b
            + 2 * dtype_bytes * wplan.strip * wplan.wo * wplan.co_b)
    if need > budget:
        diags.append(_err(
            "wgrad.vmem", f"resident dW block + strips need {need} B "
            f"> {budget} B budget",
            hint="shrink the strip first, then the channel blocks",
            where=where))
    if target == TARGET_MOSAIC:
        if wplan.sy * wplan.sx > 1 and wplan.ci_b > LANE:
            diags.append(_err(
                "mosaic.stride", f"strided window reads over a "
                f"{wplan.ci_b}-wide x slab", hint=f"cap ci_b at {LANE}",
                where=where))
        sub = sublane_for(dtype_bytes)
        if wplan.wo_pad % sub:
            diags.append(_err(
                "mosaic.xtile", f"dy strip width {wplan.wo_pad} is not "
                f"a {sub}-row multiple", hint=f"align the dy width to "
                f"{sub}", where=where))
        vmem, tile = wplan.mosaic_working_set(dtype_bytes)
        if vmem > budget:
            diags.append(_err(
                "mosaic.vmem", f"compiled working set {vmem} B exceeds "
                f"the {budget} B scoped VMEM limit",
                hint="shrink the strip, then the channel blocks",
                where=where))
        if tile > MOSAIC_TILE_BYTES:
            diags.append(_err(
                "mosaic.tile", f"in-kernel tile of {tile} B exceeds "
                f"{MOSAIC_TILE_BYTES} B", hint="shrink the strip",
                where=where))
    ci_pad = ceil_div(wplan.ci, wplan.ci_b) * wplan.ci_b
    co_pad = ceil_div(wplan.co, wplan.co_b) * wplan.co_b
    for d in (_lane_rule(wplan.ci_b, ci_pad, "x strip panel", target,
                         where),
              _lane_rule(wplan.co_b, co_pad, "dy strip panel", target,
                         where)):
        if d:
            diags.append(d)
    return diags


# --------------------------------------------------------------------------
# legality pass: matmul BlockShape (shared rules — satellite gate)
# --------------------------------------------------------------------------

def check_matmul_block(blk, m: int, n: int, k: int, *,
                       dtype_bytes: int = 2,
                       vmem_budget: int | None = None,
                       target: str = TARGET_INTERPRET,
                       where: str = "") -> list[Diagnostic]:
    """Verify a matmul :class:`~repro.core.tpu_adapter.BlockShape`
    through the *same* rule implementations the conv pass uses, so the
    matmul/attention kernels inherit the gate rather than growing a
    conv-only checker."""
    budget = VMEM_LIMIT_BYTES if vmem_budget is None else vmem_budget
    diags: list[Diagnostic] = []
    for name, b in (("bm", blk.bm), ("bn", blk.bn), ("bk", blk.bk)):
        if b < 1:
            diags.append(_err("matmul.shape", f"{name}={b} < 1",
                              where=where))
    if diags:
        return diags
    need = blk.vmem_bytes(dtype_bytes)
    if need > budget:
        diags.append(_err(
            "matmul.vmem", f"psum + double-buffered panels need "
            f"{need} B > {budget} B budget",
            hint="shrink bm/bn toward the paper's u ~= R*z balance",
            where=where))
    mp, np_, kp = (ceil_div(m, blk.bm) * blk.bm,
                   ceil_div(n, blk.bn) * blk.bn,
                   ceil_div(k, blk.bk) * blk.bk)
    for d in (_lane_rule(blk.bn, np_, "B-panel/psum block", target,
                         where),
              _lane_rule(blk.bk, kp, "A-panel block", target, where),
              _sublane_rule(blk.bm, mp, dtype_bytes, "A-panel/psum "
                            "block", target, where),
              _sublane_rule(blk.bk, kp, dtype_bytes, "B-panel block",
                            target, where)):
        if d:
            diags.append(d)
    if blk.bk < min(MXU_DIM, kp):
        diags.append(Diagnostic(
            rule="mosaic.mxu", severity=WARN, where=where,
            message=f"reduction slice bk={blk.bk} underfills the "
                    f"{MXU_DIM}-wide MXU"))
    return diags


# --------------------------------------------------------------------------
# traffic cross-audit: the second derivation
# --------------------------------------------------------------------------

def symbolic_conv_traffic(plan, batch: int) -> Traffic:
    """Independent re-derivation of :meth:`ConvPlan.traffic`.

    Counts fetches per operand straight from the BlockSpec index maps
    (an operand is re-fetched when its index-map output changes
    between consecutive grid steps, nci innermost) and multiplies by
    the block volume — ceil divisions of the *true* dims, never
    touching the accountant's padded-plane route.  Exact integer
    agreement with ``_blocks_traffic`` is asserted by the audit."""
    blk = plan.blocks
    tb = max(1, min(blk.b, batch))
    nb = ceil_div(batch, tb)
    ny, nx = ceil_div(plan.ho, blk.y), ceil_div(plan.wo, blk.x)
    nci = ceil_div(plan.ci_pad, blk.ci)
    nco = ceil_div(plan.co_pad, blk.co)
    spatial_blocks = nb * ny * nx
    # input halo tile: index map reads (bi, yi, xi, cii) — constant
    # across the Co sweep only when there is a sole Ci block
    in_fetches = (spatial_blocks if nci == 1
                  else spatial_blocks * nco * nci)
    # an lhs-dilated plan fetches the *compact* plane: of a halo
    # window's rows only those landing on the dilation phase are real
    # — ceil(pad/ld) rows' worth of leading conv padding plus at least
    # one real row per started phase period of the remaining extent
    fetch_y, fetch_x = blk.halo_y, blk.halo_x
    if getattr(plan, "lhs_dilated", False):
        def compact(halo, ld, p):
            if ld == 1:
                return halo
            return ceil_div(p, ld) + max(1, ceil_div(halo - p, ld))
        fetch_y = compact(blk.halo_y, plan.lhs_dilation[0], plan.py)
        fetch_x = compact(blk.halo_x, plan.lhs_dilation[1], plan.px)
    in_words = in_fetches * (tb * fetch_y * fetch_x * blk.ci)
    # weight slice: index map reads (cii, coi) — constant over the
    # whole grid iff both channel dims have a single block
    w_fetches = 1 if nci * nco == 1 else spatial_blocks * nco * nci
    w_words = w_fetches * (plan.hk * plan.wk * blk.ci * blk.co)
    # fused residual join: one (bi, yi, xi, coi) fetch of the pre-pool
    # psum-tile-shaped operand; the Ci sweep never re-reads it
    if plan.residual:
        in_words += spatial_blocks * nco * (tb * blk.y * blk.x * blk.co)
    # outputs: psum-stationary OutR — exactly one (pooled) write per
    # (bi, yi, xi, coi), zero psum re-reads
    out_words = (spatial_blocks * nco
                 * (tb * (blk.y // plan.pool) * (blk.x // plan.pool)
                    * blk.co))
    return Traffic(reads_in=float(in_words), reads_w=float(w_words),
                   reads_out=0.0, writes_out=float(out_words))


def symbolic_wgrad_traffic(wplan, batch: int) -> Traffic:
    """Independent re-derivation of :meth:`WgradPlan.traffic`, walked
    straight off the executing kernel's grid
    ``(nci, nco, batch, strips + lag)``: the disjoint x fetch's index
    map changes every step (one ``strip*stride``-row block per step,
    warm-up fetches included), the dy strip's clamped index map
    ``max(si - lag, 0)`` takes exactly ``strips`` distinct values per
    (ci-block, co-block, image), and the resident dW block flushes
    exactly once."""
    nci = ceil_div(wplan.ci, wplan.ci_b)
    nco = ceil_div(wplan.co, wplan.co_b)
    ns = ceil_div(wplan.ho, wplan.strip)
    r_rows = wplan.strip * wplan.sy
    k_rows = max(0, wplan.ekh - wplan.sy)
    lag = -(-k_rows // r_rows) if k_rows > 0 else 0
    # dy strips are x_align-padded; x fetches widen to the last window
    wo = round_up(wplan.wo, wplan.x_align)
    wx = max(wplan.wp, (wplan.wk - 1) * wplan.dlx + (wo - 1) * wplan.sx
             + 1)
    reads_x = (nci * nco * batch * (ns + lag)
               * r_rows * wx * wplan.ci_b)
    reads_dy = (nci * nco * batch * ns
                * wplan.strip * wo * wplan.co_b)
    writes = (wplan.hk * wplan.wk) * (nci * wplan.ci_b) * (nco
                                                           * wplan.co_b)
    return Traffic(reads_in=float(reads_x), reads_w=float(reads_dy),
                   reads_out=0.0, writes_out=float(writes))


def symbolic_dw_traffic(plan, batch: int) -> Traffic:
    """Independent re-derivation of :meth:`DepthwisePlan.traffic`, off
    the kernel's grid ``(channel-blocks, images)``: the input block's
    index map ``(bi, 0, 0, ci)`` changes every step, the weight block's
    ``(0, ci)`` once per channel block, and each output block is
    written once; an unfused norm pass reads and writes the output
    again."""
    nc = plan.c // plan.c_block
    plane_in = plan.h * plan.w * plan.c_block
    plane_out = ((plan.h + 2 * plan.py - plan.hk + 1)
                 * (plan.w + 2 * plan.px - plan.wk + 1) * plan.c_block)
    again = 1 if plan.norm_pass else 0
    return Traffic(
        reads_in=float(nc * batch * (plane_in + again * plane_out)),
        reads_w=float(nc * plan.hk * plan.wk * plan.c_block),
        reads_out=0.0,
        writes_out=float(nc * batch * plane_out * (1 + again)))


def symbolic_dw_bound(layer) -> float:
    """Independent re-derivation of the depthwise bound: each touched
    input word, each of the k*k*C weights and each output, once."""
    touched = (layer.batch * layer.ci
               * layer.fetched_area(layer.wo, layer.ho))
    return float(touched + layer.hk * layer.wk * layer.co
                 + layer.batch * layer.co * layer.ho * layer.wo)


def symbolic_bound_words(plan, layer) -> float:
    """Independent re-derivation of :meth:`ConvPlan.bound_words`:
    Eq. (15) at the plan's realized footprint, floored at the
    once-per-word ideal, plus the residual join's mandatory read —
    spelled out from first principles rather than through
    ``lower_bound.q_dram_practical``."""
    s = plan.footprint_elems()
    macs = (layer.batch * layer.ho * layer.wo * layer.co
            * layer.hk * layer.wk * layer.ci)
    r = max(1.0, (layer.hk * layer.wk) / float(layer.stride ** 2))
    outputs = layer.batch * layer.co * layer.ho * layer.wo
    touched = (layer.batch * layer.ci
               * layer.fetched_area(layer.wo, layer.ho))
    ideal = float(touched + layer.hk * layer.wk * layer.ci * layer.co
                  + outputs)
    q = max(2.0 * macs / math.sqrt(r * s) + outputs, ideal)
    if plan.residual:
        q += float(outputs)
    return q


# --------------------------------------------------------------------------
# the audit: every plan of a handle list / graph, both passes
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlanAuditEntry:
    """One plan's verdict: legality diagnostics + cross-audit flags."""

    name: str            # "<layer>/<pass>" e.g. "conv3_1/dgrad"
    diagnostics: tuple[Diagnostic, ...]
    traffic_ok: bool     # symbolic re-derivation == accountant
    bound_ok: bool       # symbolic Eq. (15) == ConvPlan.bound_words
    words: float         # accountant words at the audit batch
    bound: float         # bound words (0.0 where not applicable)

    @property
    def legal(self) -> bool:
        return not errors(self.diagnostics)

    @property
    def ok(self) -> bool:
        return self.legal and self.traffic_ok and self.bound_ok


@dataclasses.dataclass(frozen=True)
class PlanAudit:
    """The audit over a set of plan handles."""

    entries: tuple[PlanAuditEntry, ...]
    target: str

    @property
    def n_plans(self) -> int:
        return len(self.entries)

    @property
    def n_legal(self) -> int:
        return sum(e.legal for e in self.entries)

    @property
    def legal_frac(self) -> float:
        return self.n_legal / max(1, self.n_plans)

    @property
    def traffic_mismatches(self) -> int:
        return sum(not e.traffic_ok for e in self.entries)

    @property
    def bound_mismatches(self) -> int:
        return sum(not e.bound_ok for e in self.entries)

    @property
    def ok(self) -> bool:
        return all(e.ok for e in self.entries)

    def errors(self) -> list[Diagnostic]:
        return [d for e in self.entries for d in errors(e.diagnostics)]

    def report(self) -> str:
        """Human-readable audit summary (one line per plan, details
        for anything that failed)."""
        lines = [f"plan audit [{self.target}]: {self.n_legal}/"
                 f"{self.n_plans} legal, "
                 f"{self.traffic_mismatches} traffic mismatch(es), "
                 f"{self.bound_mismatches} bound mismatch(es)"]
        for e in self.entries:
            flag = "ok " if e.ok else "BAD"
            lines.append(f"  {flag} {e.name}: {e.words:.3g} words"
                         + (f" vs bound {e.bound:.3g}" if e.bound
                            else ""))
            for d in e.diagnostics:
                if d.severity == ERROR or not e.legal:
                    lines.append(f"       {d}")
        return "\n".join(lines)


def _traffic_eq(a: Traffic, b: Traffic) -> bool:
    return (a.reads_in == b.reads_in and a.reads_w == b.reads_w
            and a.reads_out == b.reads_out
            and a.writes_out == b.writes_out)


def _audit_conv(name, layer, plan, *, batch, dtype_bytes, vmem_budget,
                target) -> PlanAuditEntry:
    diags = check_conv_plan(plan, batch=batch, dtype_bytes=dtype_bytes,
                            vmem_budget=vmem_budget, target=target,
                            where=name)
    acct = plan.traffic(batch)
    traffic_ok = _traffic_eq(symbolic_conv_traffic(plan, batch), acct)
    bound = plan.bound_words(layer) if layer is not None else 0.0
    bound_ok = (layer is None
                or symbolic_bound_words(plan, layer) == bound)
    return PlanAuditEntry(name=name, diagnostics=tuple(diags),
                          traffic_ok=traffic_ok, bound_ok=bound_ok,
                          words=acct.total, bound=bound)


def _audit_dw(name, layer, plan, *, batch, dtype_bytes, vmem_budget,
              target) -> PlanAuditEntry:
    diags = check_dw_plan(plan, dtype_bytes=dtype_bytes,
                          vmem_budget=vmem_budget, target=target,
                          where=name)
    acct = plan.traffic(batch)
    bound = plan.bound_words(layer)
    return PlanAuditEntry(
        name=name, diagnostics=tuple(diags),
        traffic_ok=_traffic_eq(symbolic_dw_traffic(plan, batch), acct),
        bound_ok=symbolic_dw_bound(layer) == bound,
        words=acct.total, bound=bound)


def _audit_wgrad(name, wplan, *, batch, dtype_bytes, vmem_budget,
                 target) -> PlanAuditEntry:
    diags = check_wgrad_plan(wplan, dtype_bytes=dtype_bytes,
                             vmem_budget=vmem_budget, target=target,
                             where=name)
    acct = wplan.traffic(batch)
    traffic_ok = _traffic_eq(symbolic_wgrad_traffic(wplan, batch), acct)
    return PlanAuditEntry(name=name, diagnostics=tuple(diags),
                          traffic_ok=traffic_ok, bound_ok=True,
                          words=acct.total, bound=0.0)


def audit_handles(handles, *, batch: int, dtype_bytes: int = 4,
                  vmem_budget: int | None = None,
                  target: str = TARGET_INTERPRET) -> PlanAudit:
    """Audit ``[(ConvLayer, ConvPlan | ConvTrainingPlan)]`` handles
    (the :func:`~repro.models.graph.graph_plan_handles` export): the
    legality pass on every constituent plan and the symbolic traffic/
    bound cross-audit against the accountant."""
    entries: list[PlanAuditEntry] = []
    for layer, handle in handles:
        if hasattr(handle, "c_block"):    # DepthwisePlan (fwd only)
            entries.append(_audit_dw(
                f"{layer.name}/fwd", layer, handle, batch=batch,
                dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
                target=target))
        elif hasattr(handle, "fwd"):      # ConvTrainingPlan triple
            entries.append(_audit_conv(
                f"{layer.name}/fwd", layer, handle.fwd, batch=batch,
                dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
                target=target))
            # the dgrad conv is its own layer geometry; legality and
            # the traffic re-derivation apply, the fwd bound does not
            entries.append(_audit_conv(
                f"{layer.name}/dgrad", None, handle.dgrad, batch=batch,
                dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
                target=target))
            entries.append(_audit_wgrad(
                f"{layer.name}/wgrad", handle.wgrad, batch=batch,
                dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
                target=target))
        else:
            entries.append(_audit_conv(
                f"{layer.name}/fwd", layer, handle, batch=batch,
                dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
                target=target))
    return PlanAudit(entries=tuple(entries), target=target)


def audit_graph(graph, h: int, w: int, *, batch: int, in_ch: int = 3,
                dtype_bytes: int = 4, vmem_budget: int | None = None,
                training: bool = True,
                target: str = TARGET_INTERPRET) -> PlanAudit:
    """Run the full static audit over every node of a conv graph:
    forward plans, and with ``training=True`` the planned dgrad/wgrad
    convs too — the ``plans checked / plans legal`` gate."""
    from repro.models.graph import graph_plan_handles

    handles = graph_plan_handles(graph, h, w, batch=batch, in_ch=in_ch,
                                 dtype_bytes=dtype_bytes,
                                 vmem_budget=vmem_budget,
                                 training=training, target=target)
    return audit_handles(handles, batch=batch, dtype_bytes=dtype_bytes,
                         vmem_budget=vmem_budget, target=target)
