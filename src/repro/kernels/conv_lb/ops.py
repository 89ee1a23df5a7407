"""jit'd wrapper + HBM-traffic accountant for the paper-dataflow conv.

Block-size selection is a two-stage plan search, memoized per layer
geometry (:func:`plan_conv` is LRU-cached, so jit retraces never
re-plan):

  1. the paper's closed form (Sec. IV-C's two key conditions,
     :func:`repro.core.lower_bound.optimal_block`) seeds a candidate
     via :func:`repro.core.tpu_adapter.conv_lb_block_shape` — the
     single block chooser shared with the matmul kernel, now on the
     *batch-folded* matmul view (M = B*Ho*Wo);
  2. a traffic-guided autotuner (:func:`autotune_conv_blocks`)
     enumerates candidate ``(b_block, y, x, ci, co)`` shapes under the
     VMEM budget and keeps whichever :func:`conv_lb_traffic` scores
     cheapest.  The closed form is always in the candidate set, so the
     tuned plan can never score worse than it.

The wrapper owns the tiling contract (padding so tiles divide the
output plane, batch divides into b_block images, and every halo read
is in bounds) and supports strided, dilated and grouped convolutions
plus a *fused epilogue* (``bias``/``residual`` join/``relu``/aligned
max-``pool``) applied while the psum tile is still in VMEM — a
residual shortcut is added before the ReLU for one streamed read
instead of a separate HBM round trip; ``fallback=True`` routes
the same surface through ``lax.conv_general_dilated`` (XLA's schedule,
identical math).  Input (lhs) dilation rides the compact-plane walk
(:func:`ConvPlan.compact_geometry`): zeros are re-inserted on the
VMEM-resident fetch, never streamed.  Asymmetric before/after padding
stays out of scope for both paths — express it directly via
``jax.lax``.

``conv_lb_traffic`` is the analytic per-BlockSpec accountant: it
counts exactly the HBM words the ``pallas_call`` moves (a block is
re-fetched whenever its index-map output changes between consecutive
grid steps — Pallas' pipelining rule), giving the *measured* side of
the paper's Eq. (14)/(15) validation in tests and benchmarks.

The backward pass is planned *and executed* through the same
machinery (the paper's bound holds for dgrad/wgrad — they are convs
too): dgrad executes through the kernel itself via
:func:`plan_conv_dgrad` — strided layers included, by handing the
kernel the compact dy plane with ``lhs_dilation = stride`` — wgrad
executes through the dW-stationary
:func:`~repro.kernels.conv_lb.wgrad.wgrad_lb_call` realizing
:class:`WgradPlan`'s BlockSpecs, and :func:`plan_conv_training` /
:meth:`ConvPlan.training_traffic` bundle the per-training-step triple
scored against ``lower_bound.q_dram_training``.

The batch-reuse term of Eq. (14)/(15): the bound is over output
elements u = B*Ho*Wo, so per u x z block the z-kernel weight slice is
read once *regardless of how many images the block folds* — weight
traffic for a layer is ``(B/b_block) * Nyx * Wk*Hk*Ci*Co`` and stops
scaling with batch once ``b_block -> B``.  A per-image schedule
(b_block = 1) re-fetches the weights ``nco*nci`` times per image,
which is exactly the gap Eq. (15) charges it for: at serving-scale
batch the sqrt(R*S) denominator is only attainable with u folded
across images.  The fused epilogue attacks the second term of
Eq. (15), |outputs|: bias/relu happen before the single mandatory
write, and a fused pool divides that write volume by pool**2 while
eliminating the separate read-modify-write pass a layer-by-layer
schedule would issue.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from math import gcd as _gcd
from math import lcm as _lcm

import jax
import jax.numpy as jnp

from repro.core.dataflow import Traffic
from repro.core.exec_target import resolve_target
from repro.core.layer import ceil_div
from repro.core.tpu_adapter import (MOSAIC_TILE_BYTES, VMEM_LIMIT_BYTES,
                                    ConvBlockShape, balanced_tile,
                                    conv_block_candidates,
                                    conv_lb_block_shape, round_up)
from repro.kernels.conv_lb.wgrad import wgrad_lb_call
from repro.obs.tracer import active_tracer


def _pair(v) -> tuple[int, int]:
    return tuple(v) if isinstance(v, (tuple, list)) else (int(v), int(v))


def mosaic_working_set(blk: ConvBlockShape, hk: int, wk: int,
                       dtype_bytes: int, *, stride, dilation,
                       lhs_dilation=(1, 1), pad=(0, 0), pool: int = 1,
                       residual: bool = False,
                       norm: bool = False) -> tuple[int, int]:
    """``(vmem_bytes, tile_bytes)`` of the compiled kernel for ``blk``:
    :meth:`ConvBlockShape.mosaic_vmem_bytes` at the fetch the BlockSpec
    really makes (the compact halo of an lhs-dilated walk, plus its
    reconstructed dilated scratch) and the largest in-kernel value; a
    fused norm holds two more psum-tile temporaries."""
    from repro.kernels.conv_lb.kernel import compact_halo

    fetch, rec = [], []
    for halo, ld, p in ((blk.halo_y, lhs_dilation[0], pad[0]),
                        (blk.halo_x, lhs_dilation[1], pad[1])):
        chalo = compact_halo(halo, ld, p)
        off = ceil_div(p, ld) * ld - p if ld > 1 else 0
        fetch.append(chalo)
        rec.append(max(off + halo, (chalo - 1) * ld + 1))
    dilated = tuple(rec) if tuple(lhs_dilation) != (1, 1) else None
    vmem = blk.mosaic_vmem_bytes(hk, wk, dtype_bytes, fetch=tuple(fetch),
                                 pool=pool, residual=residual,
                                 dilated=dilated)
    if norm:
        vmem += 2 * blk.psum_bytes
    return vmem, blk.mosaic_tile_bytes(dtype_bytes)


@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """Concrete grid/padding geometry for one conv_lb_call.

    Shared between the wrapper and the traffic accountant so the bytes
    we account are the bytes the kernel moves — by construction.
    :meth:`traffic` surfaces the per-plan HBM volume directly to
    callers (the serve-path ledger charges requests off plan handles
    built with this machinery, normalized to its accounting budget)."""

    blocks: ConvBlockShape
    ho: int            # true output dims
    wo: int
    ho_pad: int        # tile-aligned output dims
    wo_pad: int
    hp_pad: int        # input dims after conv + halo padding
    wp_pad: int
    ci_pad: int
    co_pad: int
    stride: tuple[int, int]
    dilation: tuple[int, int]
    hk: int            # kernel extent (accounting needs the w panel)
    wk: int
    pool: int = 1      # fused epilogue max-pool window (1 = none)
    # lhs (input) dilation: the strided-dgrad / transposed-conv
    # geometry.  The *logical* plane the conv runs over is the
    # zero-dilated expansion of a compact plane, but HBM only holds the
    # compact plane: BlockSpecs walk it with ceil-shrunk halos and the
    # kernel re-inserts the zeros in VMEM (see kernel.py).  With
    # lhs_dilation != (1, 1), ``h``/``hp_pad`` stay in *dilated*
    # coordinates while traffic/padding account the compact fetches.
    lhs_dilation: tuple[int, int] = (1, 1)
    # true (pre-padding) layer geometry — what the plan was planned
    # *for*; lets the backward planners derive the dgrad/wgrad conv
    # geometry from a forward handle alone
    h: int = 0         # input plane entering the conv
    w: int = 0
    ci: int = 0        # per-group channel counts
    co: int = 0
    py: int = 0        # conv padding
    px: int = 0
    # a residual join lands on this conv's output: the fused epilogue
    # streams one pre-pool output-shaped read per psum tile (accounted
    # in traffic()), and the bound gains the join's mandatory read
    residual: bool = False
    # the plan_check legality profile this plan was planned (and, when
    # auto-chosen, verified) for — "interpret" or "mosaic"; an
    # ExecTarget.COMPILED execution requires a mosaic-target plan
    target: str = "interpret"
    # a LayerNorm over the output channels is fused in the epilogue:
    # the Co block spans every channel (no extra HBM words)
    norm: bool = False

    @property
    def grid(self) -> tuple[int, int, int, int]:
        """(ny, nx, nco, nci) — spatial/channel grid extents (the
        batch extent is ceil(B / blocks.b), B is not plan state)."""
        return (self.ho_pad // self.blocks.y,
                self.wo_pad // self.blocks.x,
                self.co_pad // self.blocks.co,
                self.ci_pad // self.blocks.ci)

    @property
    def lhs_dilated(self) -> bool:
        return self.lhs_dilation != (1, 1)

    def compact_geometry(self) -> tuple[tuple[int, int, int, int],
                                        tuple[int, int, int, int]]:
        """Per-axis ``(chalo, step, pad_lo, total)`` of the compact
        plane the BlockSpecs walk when ``lhs_dilated``: rows fetched
        per tile, compact rows advanced between tiles, leading
        zero-rows of conv padding (``ceil(p/ld)``), and the padded
        compact plane extent the last tile's fetch reaches.  For a
        plain plan this degenerates to the dilated-coordinate walk
        ``(halo, block*stride, p, hp_pad)``."""
        from repro.kernels.conv_lb.kernel import compact_axis_dims

        out = []
        for blk, s, halo, ld, p, n, full in (
                (self.blocks.y, self.stride[0], self.blocks.halo_y,
                 self.lhs_dilation[0], self.py,
                 self.ho_pad // self.blocks.y, self.hp_pad),
                (self.blocks.x, self.stride[1], self.blocks.halo_x,
                 self.lhs_dilation[1], self.px,
                 self.wo_pad // self.blocks.x, self.wp_pad)):
            chalo, step, _off = compact_axis_dims(blk, halo, s, ld, p)
            pc = ceil_div(p, ld)
            total = ((n - 1) * step + chalo) if ld > 1 else full
            out.append((chalo, step, pc if ld > 1 else p, total))
        return tuple(out)

    def traffic(self, batch: int) -> Traffic:
        """HBM words this plan moves for one group at ``batch`` images
        (the batch extent is not plan state: the same memoized plan
        serves every arrival batch that shares a ``b_block`` bucket)."""
        return _blocks_traffic(batch, self.blocks, self.hk, self.wk,
                               self.ho, self.wo, self.ci_pad,
                               self.co_pad, self.pool,
                               residual=self.residual,
                               lhs_dilation=self.lhs_dilation,
                               pad=(self.py, self.px))

    def traffic_bytes(self, batch: int, dtype_bytes: int = 4) -> float:
        return self.traffic(batch).total * dtype_bytes

    def footprint_elems(self) -> int:
        """Realized on-chip words S (the paper-model footprint the
        Eq. (15) comparisons are evaluated at — a fused residual
        join's streamed operand tile is part of it)."""
        return self.blocks.footprint_elems(self.hk, self.wk,
                                           residual=self.residual)

    def mosaic_working_set(self, dtype_bytes: int = 4) -> tuple[int, int]:
        """``(vmem_bytes, tile_bytes)`` of the compiled kernel for this
        plan (see :func:`mosaic_working_set`)."""
        return mosaic_working_set(
            self.blocks, self.hk, self.wk, dtype_bytes,
            stride=self.stride, dilation=self.dilation,
            lhs_dilation=self.lhs_dilation, pad=(self.py, self.px),
            pool=self.pool, residual=self.residual, norm=self.norm)

    def bound_words(self, layer) -> float:
        """This layer's Eq. (15) bound at the realized plan footprint,
        plus the residual join's mandatory once-per-word read when the
        plan fuses one (the join operand must enter the chip exactly
        like any input — the bound side of the fused epilogue's
        streamed read)."""
        from repro.core.lower_bound import q_dram_practical

        q = q_dram_practical(layer, self.footprint_elems())
        if self.residual:
            q += float(layer.n_outputs)
        return q

    def training_traffic(self, batch: int, *, dtype_bytes: int = 4,
                         vmem_budget: int | None = None,
                         autotune: bool = True) -> "TrainingTraffic":
        """HBM words one *training step* moves through this layer:
        forward + dgrad + wgrad, each accounted off its own planned
        dataflow (the bwd plans are derived from this forward handle
        via :func:`plan_conv_training` and memoized like any plan)."""
        return plan_conv_training(
            self, batch=batch, dtype_bytes=dtype_bytes,
            vmem_budget=vmem_budget, autotune=autotune).traffic(batch)

    def explain(self, *, batch: int = 1, dtype_bytes: int = 4,
                vmem_budget: int | None = None,
                target: str | None = None) -> str:
        """Human-readable account of this plan: block geometry, grid,
        VMEM working set, per-operand traffic split, and every
        :class:`~repro.analysis.plan_check.Diagnostic` the static
        verifier raises against it — the audit report's per-plan
        detail, and the first thing to read when a candidate was
        rejected or a ratio looks wrong."""
        from repro.analysis.plan_check import (check_conv_plan,
                                               format_diagnostics)
        target = self.target if target is None else target
        budget = VMEM_LIMIT_BYTES if vmem_budget is None else vmem_budget
        blk = self.blocks
        pinned = blk.ci >= self.ci_pad and blk.co >= self.co_pad
        need = (self.mosaic_working_set(dtype_bytes)[0]
                if target == "mosaic"
                else blk.vmem_bytes(self.hk, self.wk, dtype_bytes,
                                    w_pinned=pinned,
                                    residual=self.residual))
        t = self.traffic(batch)
        ny, nx, nco, nci = self.grid
        diags = check_conv_plan(self, batch=batch,
                                dtype_bytes=dtype_bytes,
                                vmem_budget=vmem_budget, target=target)
        return "\n".join([
            f"conv plan {self.ci}->{self.co} k{self.hk}x{self.wk} "
            f"s{self.stride} d{self.dilation} on {self.h}x{self.w} "
            f"(out {self.ho}x{self.wo}, pool {self.pool}"
            f"{', residual join' if self.residual else ''})",
            f"  blocks: b={blk.b} y={blk.y} x={blk.x} ci={blk.ci} "
            f"co={blk.co} halo={blk.halo_y}x{blk.halo_x}"
            f"{' [weights pinned]' if pinned else ''}",
            f"  grid:   ny={ny} nx={nx} nco={nco} nci={nci} "
            f"(x ceil(B/{blk.b}) batch blocks)",
            f"  vmem:   {need} B of {budget} B "
            f"({100.0 * need / max(1, budget):.0f}%)",
            f"  traffic @B={batch}: in={t.reads_in:.4g} "
            f"w={t.reads_w:.4g} out={t.writes_out:.4g} "
            f"(total {t.total:.4g} words)",
            f"  verifier [{target}]: {format_diagnostics(diags)}",
        ])


def _blocks_traffic(batch: int, blk: ConvBlockShape, hk: int, wk: int,
                    ho: int, wo: int, ci: int, co: int,
                    pool: int = 1, residual: bool = False,
                    lhs_dilation: tuple[int, int] = (1, 1),
                    pad: tuple[int, int] = (0, 0)) -> Traffic:
    """HBM words moved by the kernel's BlockSpecs for one group.

    Pallas re-fetches an operand block whenever its index-map output
    changes between consecutive steps of the grid
    (nb, ny, nx, nco, nci) — nci innermost.  Hence per grid step the
    halo'd input tile (b*halo_y*halo_x*ci_b) and the weight slice
    (hk*wk*ci_b*co_b) are each fetched once — except that a sole
    Ci-block lets the input tile persist across the whole Co sweep, and
    a sole (Ci, Co) block pins the weights for the entire run.  The
    weight slice is fetched once per u x z block *regardless of blk.b*:
    reads_w scales with B/b_block, not B — the batch-reuse term.
    Outputs flush exactly once per (bi, yi, xi, coi): the
    psum-stationary OutR guarantee (reads_out = 0, writes = padded
    |outputs| / pool**2 when the epilogue pool is fused).

    An lhs-dilated plan (``lhs_dilation != (1, 1)``) fetches the
    *compact* plane — the ceil-shrunk halo of
    :func:`repro.kernels.conv_lb.kernel.compact_axis_dims` — so its
    input traffic scales with the true dy plane, not the zero-dilated
    one the conv logically runs over (``pad`` carries the dilated
    plane's conv padding the compact halo depends on).

    Not counted: the fused bias row's (1, co_b) fetches — O(nb*ny*nx*co)
    words, vanishing next to any conv operand panel (the smallest of
    which carries an hk*wk*ci_b factor per fetch).
    """
    ho_pad, wo_pad = round_up(ho, blk.y), round_up(wo, blk.x)
    ci_pad, co_pad = round_up(ci, blk.ci), round_up(co, blk.co)
    tb = max(1, min(blk.b, batch))
    nb = ceil_div(batch, tb)
    ny, nx = ho_pad // blk.y, wo_pad // blk.x
    nco, nci = co_pad // blk.co, ci_pad // blk.ci
    steps = nb * ny * nx * nco * nci
    in_fetches = steps if nci > 1 else nb * ny * nx
    w_fetches = steps if nco * nci > 1 else 1
    fetch_y, fetch_x = blk.halo_y, blk.halo_x
    if lhs_dilation != (1, 1):
        from repro.kernels.conv_lb.kernel import compact_halo

        fetch_y = compact_halo(blk.halo_y, lhs_dilation[0], pad[0])
        fetch_x = compact_halo(blk.halo_x, lhs_dilation[1], pad[1])
    reads_in = in_fetches * tb * fetch_y * fetch_x * blk.ci
    reads_w = w_fetches * hk * wk * blk.ci * blk.co
    if residual:
        # fused residual join: the pre-pool output-shaped operand is
        # streamed once per (bi, yi, xi, coi) psum tile — its index map
        # ignores the Ci sweep, so it is never re-fetched within one
        reads_in += nb * tb * ho_pad * wo_pad * co_pad
    writes = nb * tb * (ho_pad // pool) * (wo_pad // pool) * co_pad
    return Traffic(reads_in=float(reads_in), reads_w=float(reads_w),
                   reads_out=0.0, writes_out=float(writes))


def _snap_pool(t: int, dim: int, pool: int) -> int:
    """Round a tile up to a pool multiple (tiles stay pool-aligned so
    fused pool windows never straddle tile boundaries)."""
    return min(dim, round_up(t, pool)) if pool > 1 else t


# Extra score charge per weight word moved, on top of its 1x share of
# the total.  At serving scale the weights are the *recurring* HBM
# term — re-streamed from DRAM for every inference batch, forever —
# while each activation word flows through once per request, so the
# planner buys weight reuse with activation traffic whenever the
# exchange is better than 1:2 (the Hong-Kung balance point treats all
# words equally; serving does not).
W_READ_BIAS = 2.0


def conv_plan_score(t: Traffic) -> float:
    """The autotuner's serving-oriented traffic score (lower=better)."""
    return t.total + W_READ_BIAS * t.reads_w


def autotune_conv_blocks(batch: int, ho: int, wo: int, ci: int, co: int,
                         hk: int, wk: int, *,
                         stride: tuple[int, int],
                         dilation: tuple[int, int],
                         lhs_dilation: tuple[int, int] = (1, 1),
                         pad: tuple[int, int] = (0, 0),
                         pool: int = 1, residual: bool = False,
                         norm: bool = False,
                         dtype_bytes: int = 4,
                         vmem_budget: int,
                         seed: ConvBlockShape,
                         target: str = "interpret",
                         diagnostics: list | None = None
                         ) -> ConvBlockShape:
    """Traffic-guided plan autotuner (the 'exhaustive search' of the
    paper's methodology, collapsed): enumerate balanced candidate
    ``(b, y, x, ci_b)`` shapes, solve the best ``co_b`` analytically
    (largest fitting the budget — weight traffic is ~co_b-independent
    while input traffic strictly falls with co_b, cf.
    ``OursDataflow._z_max``), plus the fully weight-pinned candidate
    (sole Ci & Co block — single-buffered, fetched once for the whole
    grid) when it fits, and keep whichever :func:`conv_plan_score`
    rates cheapest.  ``seed`` (the closed form) is always a candidate,
    so the result never scores worse than the closed form —
    ``residual=True`` (a fused join streams an extra double-buffered
    u x co_b operand tile) first shrinks the seed's co_b until the
    join's buffer fits too, so every candidate honors the budget.
    ``norm=True`` (a fused LayerNorm over the output channels) admits
    only Co blocks that span every channel.

    ``target`` selects the legality profile of
    :mod:`repro.analysis.plan_check`: under ``"interpret"`` (the
    accounting default) candidates only need to fit the budget; under
    ``"mosaic"`` every candidate is *snapped to the nearest
    Mosaic-legal shape before scoring* (channel blocks to LANE
    multiples or the full dim, spatial blocks to sublane-aligned
    offsets for the dtype) and misalignable ones are rejected, so the
    winner is executable with ``interpret=False`` by construction.
    ``diagnostics`` (a list) collects a
    :class:`~repro.analysis.plan_check.Diagnostic` per rejected or
    snapped candidate — the ``plan.explain()``-grade debug trail of
    *why* the search landed where it did."""
    from repro.analysis.plan_check import (LANE, TARGET_MOSAIC,
                                           Diagnostic, PlanLegalityError)
    from repro.core.tpu_adapter import sublane_for

    sy, sx = stride
    dy, dx = dilation
    ldy, ldx = lhs_dilation
    db = dtype_bytes
    kk = hk * wk
    mosaic = target == TARGET_MOSAIC
    sub = sublane_for(db)
    p = max(1, pool)

    def note(rule: str, message: str, hint: str = "") -> None:
        if diagnostics is not None:
            diagnostics.append(Diagnostic(rule=rule, severity="warn",
                                          message=message, hint=hint))

    def snap_lhs(v: int, dim: int, s: int, ld: int) -> int:
        """Round a tile up so its input offset (v*stride) lands on the
        lhs-dilation phase — every compact fetch starts on a real row."""
        if ld == 1 or (v * s) % ld == 0:
            return v
        step = ld // _gcd(ld, s)
        return min(round_up(v, step), round_up(dim, step))

    def traffic(blk: ConvBlockShape) -> Traffic:
        return _blocks_traffic(batch, blk, hk, wk, ho, wo, ci, co, pool,
                               residual=residual,
                               lhs_dilation=lhs_dilation, pad=pad)

    # the mosaic x tile: the whole output row, padded to a sublane
    # (and pool / lhs-phase) multiple — the kernel's (b*y*x, ci) merge
    # needs x % sublane == 0, and a full-width halo fetch is the only
    # element-indexed input block Mosaic tiles
    x_row = round_up(wo, _lcm(sub, p, ldx // _gcd(ldx, sx)))
    # a strided window read (or lhs-dilation store) is a Mosaic strided
    # access, whose base block may span at most one lane tile
    ci_cap = LANE if sy * sx * ldy * ldx > 1 else ci

    def fits(blk: ConvBlockShape) -> bool:
        if mosaic:
            vmem, tile = mosaic_working_set(
                blk, hk, wk, db, stride=stride, dilation=dilation,
                lhs_dilation=lhs_dilation, pad=pad, pool=pool,
                residual=residual, norm=norm)
            return vmem <= vmem_budget and tile <= MOSAIC_TILE_BYTES
        pinned = blk.ci >= ci and blk.co >= co
        return blk.vmem_bytes(hk, wk, db, w_pinned=pinned,
                              residual=residual) <= vmem_budget

    def mosaic_ok(blk: ConvBlockShape) -> bool:
        ci_pad, co_pad = round_up(ci, blk.ci), round_up(co, blk.co)
        return ((blk.ci % LANE == 0 or blk.ci >= ci_pad)
                and (blk.co % LANE == 0 or blk.co >= co_pad)
                and blk.x == x_row and blk.ci <= ci_cap)

    def snap_ch(v: int, dim: int) -> int:
        """Nearest legal channel block: a LANE multiple, or full."""
        return dim if v >= dim or round_up(v, LANE) >= dim \
            else round_up(v, LANE)

    def lane_blocks(dim: int) -> list[int]:
        """Legal channel blocks, largest first: full, then LANE
        multiples below it."""
        return [dim] + list(range((dim - 1) // LANE * LANE, 0, -LANE))

    def snap_mosaic(blk: ConvBlockShape) -> ConvBlockShape:
        cib = min(snap_ch(blk.ci, ci), ci_cap)
        cob = snap_ch(blk.co, co)
        x, y = x_row, snap_lhs(blk.y, ho, sy, ldy)
        if (cib, cob, x) != (blk.ci, blk.co, blk.x):
            note("autotune.mosaic",
                 f"snapped candidate ci={blk.ci} co={blk.co} "
                 f"x={blk.x} to Mosaic-legal ci={cib} co={cob} x={x}")
        return ConvBlockShape(y=y, x=x, co=cob, ci=cib,
                              halo_y=(y - 1) * sy + (hk - 1) * dy + 1,
                              halo_x=(x - 1) * sx + (wk - 1) * dx + 1,
                              b=blk.b)

    if mosaic:
        seed = snap_mosaic(seed)
    if norm:
        seed = dataclasses.replace(seed, co=co)
    while (residual or mosaic) and not norm and not fits(seed) \
            and seed.co > 1:
        shrunk = (balanced_tile(co, seed.co // 2) if not mosaic
                  else max(LANE, (seed.co // 2 // LANE) * LANE)
                  if seed.co > LANE else 0)
        if not shrunk:
            break
        seed = dataclasses.replace(seed, co=shrunk)

    cands = []
    if fits(seed) and (not mosaic or mosaic_ok(seed)):
        cands.append((traffic(seed), seed))
    elif mosaic:
        note("autotune.mosaic", "closed-form seed has no Mosaic-legal "
             "shape under the budget; enumerated candidates only")
    seen = set()
    for b, y, x, cib in conv_block_candidates(batch, ho, wo, ci):
        y, x = _snap_pool(y, ho, pool), _snap_pool(x, wo, pool)
        if mosaic:
            cib, x = min(snap_ch(cib, ci), ci_cap), x_row
        y = snap_lhs(y, ho, sy, ldy)
        x = snap_lhs(x, wo, sx, ldx)
        yp = (y - 1) * sy + (hk - 1) * dy + 1
        xp = (x - 1) * sx + (wk - 1) * dx + 1
        if norm:
            cobs = [co]
        elif mosaic:
            cobs = lane_blocks(co)  # largest first: the first fit wins
        else:
            # largest co_b under the budget: psums 4*b*y*x*co_b plus
            # double-buffered input (b*yp*xp*cib), weight
            # (kk*cib*co_b) and, for a fused join, residual
            # (b*y*x*co_b) panels
            free = vmem_budget - 2 * db * b * yp * xp * cib
            denom = (4 * b * y * x + 2 * db * kk * cib
                     + (2 * db * b * y * x if residual else 0))
            cobs = []
            if free // denom >= 1:
                cobs.append(balanced_tile(co, min(co, int(free // denom))))
            if cib >= ci:
                cobs.append(co)     # weight-pinned: one fetch, 1x buffer
        for cob in cobs:
            blk = ConvBlockShape(y=y, x=x, co=cob, ci=cib,
                                 halo_y=yp, halo_x=xp, b=b)
            if blk in seen:
                continue
            seen.add(blk)
            if not fits(blk):
                note("autotune.vmem",
                     f"rejected b={b} y={y} x={x} ci={cib} co={cob}: "
                     f"working set exceeds {vmem_budget} B")
                continue
            if mosaic and not mosaic_ok(blk):
                note("autotune.mosaic",
                     f"rejected b={b} y={y} x={x} ci={cib} co={cob}: "
                     f"no Mosaic-legal snap under the budget")
                continue
            cands.append((traffic(blk), blk))
            if mosaic:
                break
    if not cands:
        raise PlanLegalityError([Diagnostic(
            rule="autotune.mosaic", severity="error",
            message=f"no {target}-legal block shape fits the "
                    f"{vmem_budget} B budget for "
                    f"{ci}->{co} k{hk}x{wk} on {ho}x{wo}",
            hint="raise the VMEM budget or relax the target")])
    # ties go to the larger psum tile under mosaic (fewer grid steps)
    best = min(cands,
               key=lambda tb: (conv_plan_score(tb[0]), tb[0].reads_w,
                               -tb[1].u if mosaic else 0))[1]
    # nests under the plan.search span when a tracer is ambient
    active_tracer().event(
        "plan.autotune", candidates=len(cands),
        enumerated=len(seen), target=target,
        layer=f"{ci}->{co}k{hk}x{wk}",
        best=f"b={best.b},y={best.y},x={best.x},"
             f"ci={best.ci},co={best.co}")
    return best


@lru_cache(maxsize=1024)
def plan_conv(h: int, w: int, ci: int, co: int, hk: int, wk: int, *,
              batch: int = 1, stride=(1, 1), padding=(0, 0),
              dilation=(1, 1), lhs_dilation=(1, 1), pool: int = 1,
              residual: bool = False, norm: bool = False,
              blocks: ConvBlockShape | None = None,
              dtype_bytes: int = 4,
              vmem_budget: int | None = None,
              autotune: bool = True,
              target: str = "interpret") -> ConvPlan:
    """Resolve blocks + padding for a (B, H, W, Ci) -> Co conv.

    LRU-cached on the full layer geometry: the same geometry inside a
    jit retrace (or across layers of a model) pays no re-planning.
    ``residual=True`` marks a fused residual join on the output: its
    streamed read is accounted in :meth:`ConvPlan.traffic`, its
    double-buffered operand tile in the autotuner's VMEM fit, and its
    resident tile in :meth:`ConvPlan.footprint_elems` (the S the
    Eq. (15) comparisons are evaluated at).  ``norm=True`` marks a
    fused LayerNorm over the output channels: the plan's Co block then
    spans every channel.

    ``target`` names the :mod:`repro.analysis.plan_check` legality
    profile the plan must satisfy, and the returned plan *remembers
    it* (``ConvPlan.target``) — an ``ExecTarget.COMPILED`` execution
    only trusts a mosaic-target plan.  Auto-chosen plans
    (``blocks=None``) are verified before being returned — a failing
    plan raises :class:`~repro.analysis.plan_check.PlanLegalityError`
    instead of silently entering the LRU cache.  Explicit ``blocks``
    overrides are the caller's contract and bypass the gate (tests
    deliberately probe odd shapes).

    ``lhs_dilation != (1, 1)`` plans the conv over the *logical*
    zero-dilated plane (``h``/``w`` are the dilated extents; callers
    hold the compact plane — dy of a strided forward, or a
    transposed-conv input) with compact-plane BlockSpec traffic and
    phase-snapped tiles; see :class:`ConvPlan`."""
    sy, sx = _pair(stride)
    py, px = _pair(padding)
    dy, dx = _pair(dilation)
    ldy, ldx = _pair(lhs_dilation)
    hp, wp = h + 2 * py, w + 2 * px
    ekh, ekw = (hk - 1) * dy + 1, (wk - 1) * dx + 1   # dilated extent
    ho = (hp - ekh) // sy + 1
    wo = (wp - ekw) // sx + 1
    if pool > 1 and (ho % pool or wo % pool):
        raise ValueError(f"fused pool={pool} needs pool-divisible "
                         f"output plane, got {ho}x{wo}")
    if (ldy, ldx) != (1, 1) and (pool > 1 or residual):
        raise ValueError("lhs-dilated plans fuse no pool/residual "
                         "epilogue (dgrad/transposed convs have none)")
    budget = VMEM_LIMIT_BYTES if vmem_budget is None else vmem_budget
    auto = blocks is None
    if blocks is None:
        # fires only on LRU miss — a span per *distinct* geometry, via
        # the ambient tracer (the lru_cache wrapper can't take tracer=)
        with active_tracer().span(
                "plan.search", layer=f"{ci}->{co}k{hk}x{wk}",
                h=h, w=w, batch=batch, target=target,
                autotune=autotune) as _sp:
            blocks = conv_lb_block_shape(ho, wo, ci, co, hk, wk,
                                         batch=batch, stride=(sy, sx),
                                         dilation=(dy, dx),
                                         dtype_bytes=dtype_bytes,
                                         vmem_budget=budget)
            if autotune:
                blocks = autotune_conv_blocks(
                    batch, ho, wo, ci, co, hk, wk, stride=(sy, sx),
                    dilation=(dy, dx), lhs_dilation=(ldy, ldx),
                    pad=(py, px), pool=pool, residual=residual,
                    norm=norm, dtype_bytes=dtype_bytes,
                    vmem_budget=budget, seed=blocks, target=target)
            _sp.set(blocks=f"b={blocks.b},y={blocks.y},x={blocks.x},"
                           f"ci={blocks.ci},co={blocks.co}")
    ty = _snap_pool(min(blocks.y, ho), ho, pool)
    # a mosaic x tile is the whole row padded to a sublane multiple,
    # so it may exceed wo (the kernel computes, then crops, the pad)
    tx = (blocks.x if target == "mosaic" and blocks.x >= wo
          else _snap_pool(min(blocks.x, wo), wo, pool))
    if ldy > 1 and (ty * sy) % ldy:
        # phase-snap: every compact fetch must start on a real row
        step = ldy // _gcd(ldy, sy)
        ty = min(round_up(ty, step), round_up(ho, step))
    if ldx > 1 and (tx * sx) % ldx:
        step = ldx // _gcd(ldx, sx)
        tx = min(round_up(tx, step), round_up(wo, step))
    cib, cob = min(blocks.ci, ci), min(blocks.co, co)
    tb = max(1, min(blocks.b, batch))
    blocks = ConvBlockShape(y=ty, x=tx, co=cob, ci=cib,
                            halo_y=(ty - 1) * sy + ekh,
                            halo_x=(tx - 1) * sx + ekw, b=tb)
    ho_pad, wo_pad = round_up(ho, ty), round_up(wo, tx)
    # max(): a strided conv can have unused trailing input rows/cols —
    # keep them (blocks never index past the last tile's halo)
    plan = ConvPlan(blocks=blocks, ho=ho, wo=wo,
                    ho_pad=ho_pad, wo_pad=wo_pad,
                    hp_pad=max(hp, (ho_pad - 1) * sy + ekh),
                    wp_pad=max(wp, (wo_pad - 1) * sx + ekw),
                    ci_pad=round_up(ci, cib), co_pad=round_up(co, cob),
                    stride=(sy, sx), dilation=(dy, dx),
                    lhs_dilation=(ldy, ldx), pool=pool,
                    hk=hk, wk=wk,
                    h=h, w=w, ci=ci, co=co, py=py, px=px,
                    residual=residual, target=target, norm=norm)
    if auto:
        from repro.analysis.plan_check import (PlanLegalityError,
                                               check_conv_plan, errors)
        diags = check_conv_plan(plan, batch=batch,
                                dtype_bytes=dtype_bytes,
                                vmem_budget=budget, target=target)
        if errors(diags):
            raise PlanLegalityError(diags)
    return plan


# --------------------------------------------------------------------------
# backward pass: dgrad / wgrad as planned convs
# --------------------------------------------------------------------------

def _flip_w(w: jax.Array) -> jax.Array:
    """(Hk, Wk, Ci, Co) -> spatially flipped (Hk, Wk, Co, Ci): the
    dgrad conv's kernel."""
    return w[::-1, ::-1].transpose(0, 1, 3, 2)


def dgrad_rides_kernel(plan: ConvPlan) -> bool:
    """True when the layer's dgrad can execute through the planned
    conv_lb kernel itself: a forward padding the full-padding
    transform can absorb.  Unit-stride layers run the plain conv over
    the flipped weights; strided layers run the *same* kernel over the
    compact dy plane with ``lhs_dilation = stride`` (the BlockSpec
    walks dy, the kernel re-inserts the stride-1 zeros in VMEM)."""
    ekh = (plan.hk - 1) * plan.dilation[0] + 1
    ekw = (plan.wk - 1) * plan.dilation[1] + 1
    return plan.py <= ekh - 1 and plan.px <= ekw - 1


def plan_conv_dgrad(plan: ConvPlan, *, batch: int = 1,
                    dtype_bytes: int = 4,
                    vmem_budget: int | None = None,
                    autotune: bool = True) -> ConvPlan:
    """Plan the layer's *dgrad* conv (dx from dy) off a forward handle.

    dx is the conv of dy with the spatially-flipped ``(Hk, Wk, Co, Ci)``
    weights at unit stride and full padding — for unit forward stride
    it is exactly the conv the batch-folded kernel runs; a strided
    forward lhs-dilates the dy plane first (``stride-1`` zeros between
    dy rows/cols), which the kernel executes off the *compact* plane
    (``lhs_dilation = stride``): the plan is over the dilated extents
    but its BlockSpecs fetch — and its traffic charges — dy words only.
    """
    sy, sx = plan.stride
    hd = plan.ho if sy == 1 else (plan.ho - 1) * sy + 1
    wd = plan.wo if sx == 1 else (plan.wo - 1) * sx + 1
    ekh = (plan.hk - 1) * plan.dilation[0] + 1
    ekw = (plan.wk - 1) * plan.dilation[1] + 1
    return plan_conv(hd, wd, plan.co, plan.ci, plan.hk, plan.wk,
                     batch=batch, stride=(1, 1),
                     padding=(max(0, ekh - 1 - plan.py),
                              max(0, ekw - 1 - plan.px)),
                     dilation=plan.dilation,
                     lhs_dilation=(sy, sx), dtype_bytes=dtype_bytes,
                     vmem_budget=vmem_budget, autotune=autotune,
                     target=plan.target)


@dataclasses.dataclass(frozen=True)
class WgradPlan:
    """dW-stationary tiled schedule for the layer's *wgrad* conv.

    dW is the conv of the padded input with the incoming gradient as
    the kernel plane:

      dW[ky, kx, ci, co] = sum_{b, oy, ox}
          x_pad[b, ky*dil + oy*stride, kx*dil + ox*stride, ci]
          * dy[b, oy, ox, co]

    **Batch folds into the reduction** (every image accumulates into
    the same dW), so the natural bound-attaining dataflow is the
    mirror image of the forward's psum-stationary u x z block: a
    ``(Hk, Wk, ci_b, co_b)`` block of *dW* stays resident (OutR on the
    weight gradient — written exactly once), while matching spatial
    strips of x and dy stream through on-chip memory, image after
    image.  Forcing wgrad through the forward's u x z machinery
    instead would re-stream whole activation planes per (Ci, Co) block
    (the dW output plane is only Hk x Wk — u cannot grow), landing
    10-60x off Eq. (15); this schedule attains the once-per-word floor
    outright whenever the full dW fits on chip.

    Per (ci-block, co-block) sweep the strips roll: each grid step
    fetches a *disjoint* ``strip*stride``-row x block (every touched
    row enters the chip once per plane pass) while the ``ekh - stride``
    shared halo rows stay resident in a carry scratch the dW psums
    never evict — the compute *lags* the fetch by
    ``lag = ceil((ekh - stride)/(strip*stride))`` steps so strip ``j``
    reduces over carry + fetch rows ``[j*R, j*R + R + K)``.  x is
    re-fetched once per Co-block sweep, dy once per Ci-block sweep;
    ``strip`` is the footprint knob (rows in flight), and the only
    re-read overhead is the ``lag`` warm-up fetch per plane pass.
    Execution rides :func:`repro.kernels.conv_lb.wgrad.wgrad_lb_call`
    — the kernel realizes exactly these BlockSpecs, so the charged
    volume is the moved volume, cf. the paper's WtR-B stationarity
    analysis.
    """

    hk: int            # dW spatial extent (= fwd kernel)
    wk: int
    ci: int
    co: int
    ho: int            # dy plane (the wgrad reduction's spatial extent)
    wo: int
    wp: int            # padded input plane cols
    ekh: int           # dilated kernel extent (x strip halo rows)
    sy: int            # fwd stride (x rows advanced per dy row)
    ci_b: int          # resident dW block channels
    co_b: int
    strip: int         # dy rows streamed per strip
    # executing-kernel geometry (defaults keep prior handles valid)
    sx: int = 1        # fwd stride cols
    ekw: int = 1       # dilated kernel extent cols
    dly: int = 1       # rhs (kernel) dilation
    dlx: int = 1
    py: int = 0        # fwd conv padding
    px: int = 0
    h: int = 0         # true input plane rows (0: unknown/legacy)
    # dy strip width padded to this multiple (the mosaic profile's
    # sublane: the kernel merges each (strip, wo) panel into rows)
    x_align: int = 1

    @property
    def n_strips(self) -> int:
        return ceil_div(self.ho, self.strip)

    @property
    def wo_pad(self) -> int:
        """dy cols after sublane alignment (zero-padded tail)."""
        return round_up(self.wo, self.x_align)

    @property
    def wx(self) -> int:
        """x plane cols fetched per strip: the padded input, widened so
        the deepest window of the padded dy width stays in bounds."""
        return max(self.wp, self.ekw + (self.wo_pad - 1) * self.sx)

    @property
    def lag(self) -> int:
        """Fetch steps the compute trails behind: the resident carry
        holds ``K = ekh - stride`` halo rows spanning the previous
        ``lag`` disjoint fetches (0 when ``ekh <= stride`` — strips
        don't overlap at all)."""
        k = self.ekh - self.sy
        return ceil_div(k, self.strip * self.sy) if k > 0 else 0

    @property
    def ho_pad(self) -> int:
        """dy rows after strip alignment (zero-padded tail)."""
        return self.n_strips * self.strip

    @property
    def grid(self) -> tuple[int, int, int]:
        """(n_ci_blocks, n_co_blocks, n_strips)."""
        return (ceil_div(self.ci, self.ci_b),
                ceil_div(self.co, self.co_b),
                self.n_strips)

    def _x_rows(self) -> int:
        """x rows *fetched* per image-channel plane pass, measured off
        the executing kernel's disjoint-strip BlockSpec: ``n_strips +
        lag`` fetches of ``strip*stride`` rows each (the warm-up
        fetches fill the carry before the first compute step)."""
        return (self.n_strips + self.lag) * self.strip * self.sy

    def traffic(self, batch: int) -> Traffic:
        """HBM words one wgrad pass moves at ``batch`` images: x is
        re-read once per Co-block sweep, dy once per Ci-block sweep,
        the dW block accumulates on chip and is written once."""
        nci, nco, _ = self.grid
        ci_pad = nci * self.ci_b
        co_pad = nco * self.co_b
        reads_x = nco * batch * ci_pad * self._x_rows() * self.wx
        reads_dy = nci * batch * co_pad * self.ho_pad * self.wo_pad
        writes = self.hk * self.wk * ci_pad * co_pad
        return Traffic(reads_in=float(reads_x), reads_w=float(reads_dy),
                       reads_out=0.0, writes_out=float(writes))

    def traffic_bytes(self, batch: int, dtype_bytes: int = 4) -> float:
        return self.traffic(batch).total * dtype_bytes

    def footprint_elems(self) -> int:
        """On-chip words S of the paper's model: resident dW block +
        one x strip + one dy strip (no double buffering)."""
        xrows = (self.strip - 1) * self.sy + self.ekh
        return (self.hk * self.wk * self.ci_b * self.co_b
                + xrows * self.wx * self.ci_b
                + self.strip * self.wo_pad * self.co_b)

    def mosaic_working_set(self, dtype_bytes: int = 4) -> tuple[int, int]:
        """``(vmem_bytes, tile_bytes)`` of the compiled wgrad kernel on
        (sublane, LANE) tiles: the resident f32 dW block and its
        double-buffered output, the (K + R)-row slab scratch, the
        double-buffered x fetch and dy strip, and the window sweep's
        slice (plus its transpose) and dy panel temporaries."""
        from repro.core.tpu_adapter import LANE, sublane_for

        db = dtype_bytes
        sub = sublane_for(db)
        ci, co = round_up(self.ci_b, LANE), round_up(self.co_b, LANE)
        r_rows = self.strip * self.sy
        k_rows = max(0, self.ekh - self.sy)
        wx = round_up(self.wx, sub)
        rows = self.strip * self.wo_pad
        dw = 3 * self.hk * self.wk * round_up(self.ci_b, sub) * co * 4
        vmem = (dw + (k_rows + 3 * r_rows) * wx * ci * db
                + 2 * rows * co * db
                + 2 * rows * ci * db + rows * co * db)
        tile = max(rows * ci * db, rows * co * db, r_rows * wx * ci * db,
                   round_up(self.ci_b, sub) * co * 4)   # one dW tap
        return vmem, tile


@lru_cache(maxsize=1024)
def plan_conv_wgrad(plan: ConvPlan, *, dtype_bytes: int = 4,
                    vmem_budget: int | None = None,
                    autotune: bool = True) -> WgradPlan:
    """Choose the dW-stationary blocks for a layer's wgrad conv off a
    forward handle: minimize the re-read volume
    ``n_co_blocks*|x| + n_ci_blocks*|dy|`` under the VMEM budget
    (resident f32 dW block + double-buffered x/dy strips).  The plan
    carries no batch extent — like :class:`ConvPlan`, the same handle
    accounts any training batch via ``traffic(batch)``.  LRU-cached on
    the (hashable) forward handle, like ``plan_conv``.

    A mosaic-target forward handle yields a mosaic plan: channel blocks
    on LANE multiples (or the full dim), the dy width padded to the
    sublane, and the fit judged by
    :meth:`WgradPlan.mosaic_working_set` against the budget and
    :data:`~repro.core.tpu_adapter.MOSAIC_TILE_BYTES`."""
    from repro.core.layer import balanced_candidates
    from repro.core.tpu_adapter import LANE, sublane_for

    budget = VMEM_LIMIT_BYTES if vmem_budget is None else vmem_budget
    db = dtype_bytes
    mosaic = plan.target == "mosaic"
    sy, sx = plan.stride
    ekh = (plan.hk - 1) * plan.dilation[0] + 1
    ekw = (plan.wk - 1) * plan.dilation[1] + 1
    wp = plan.w + 2 * plan.px

    def mk(cib, cob, s):
        return WgradPlan(hk=plan.hk, wk=plan.wk, ci=plan.ci, co=plan.co,
                         ho=plan.ho, wo=plan.wo, wp=wp, ekh=ekh, sy=sy,
                         ci_b=cib, co_b=cob, strip=s,
                         sx=sx, ekw=ekw,
                         dly=plan.dilation[0], dlx=plan.dilation[1],
                         py=plan.py, px=plan.px, h=plan.h,
                         x_align=sublane_for(db) if mosaic else 1)

    def fits(cand):
        if mosaic:
            vmem, tile = cand.mosaic_working_set(db)
            return vmem <= budget and tile <= MOSAIC_TILE_BYTES
        xrows = (cand.strip - 1) * sy + ekh
        return (4 * plan.hk * plan.wk * cand.ci_b * cand.co_b
                + 2 * db * xrows * wp * cand.ci_b         # double-
                + 2 * db * cand.strip * plan.wo * cand.co_b  # buffered
                ) <= budget

    def chans(dim, cap):
        cands = balanced_candidates(dim)
        if mosaic:
            cands = [c for c in cands
                     if (c == dim or c % LANE == 0) and c <= cap]
        return cands

    # strided window reads are Mosaic strided loads, whose base (the
    # slab scratch) may span at most one lane tile
    ci_cands = chans(plan.ci, LANE if sy * sx > 1 else plan.ci)
    co_cands = chans(plan.co, plan.co)
    s_cands = balanced_candidates(plan.ho) if autotune else [1]
    # minimal block: always the fallback
    best = mk(min(ci_cands), min(co_cands), 1)
    best_cost = None
    for cib in ci_cands:
        for cob in co_cands:
            for s in s_cands:
                cand = mk(cib, cob, s)
                if not fits(cand):
                    continue
                # reads scale uniformly with batch and writes are
                # batch-free, so ranking at batch=1 is batch-robust; a
                # compiled kernel breaks ties toward fewer grid steps
                cost = (cand.traffic(1).total,
                        -s if mosaic else 0)
                if best_cost is None or cost < best_cost:
                    best, best_cost = cand, cost
    return best


@dataclasses.dataclass(frozen=True)
class TrainingTraffic:
    """Per-training-step HBM words, split by pass."""

    fwd: Traffic
    dgrad: Traffic
    wgrad: Traffic

    @property
    def total(self) -> float:
        return self.fwd.total + self.dgrad.total + self.wgrad.total

    @property
    def bwd_share(self) -> float:
        """Fraction of the step's words moved by the backward convs."""
        return (self.dgrad.total + self.wgrad.total) / max(self.total,
                                                           1e-30)

    def total_bytes(self, dtype_bytes: int = 4) -> float:
        return self.total * dtype_bytes


@dataclasses.dataclass(frozen=True)
class ConvTrainingPlan:
    """The three planned convs of one layer's training step.

    ``dgrad_kernel`` records whether dx executes through the planned
    conv_lb kernel — unit-stride layers as a plain conv, strided
    layers via the lhs-dilated compact-plane walk — or falls back to
    lax while remaining planned and accounted (grouped layers, or a
    forward padding past the full-padding transform)."""

    fwd: ConvPlan
    dgrad: ConvPlan
    wgrad: WgradPlan
    dgrad_kernel: bool

    def traffic(self, batch: int) -> TrainingTraffic:
        """Words per training step at ``batch`` images."""
        return TrainingTraffic(fwd=self.fwd.traffic(batch),
                               dgrad=self.dgrad.traffic(batch),
                               wgrad=self.wgrad.traffic(batch))

    def traffic_bytes(self, batch: int, dtype_bytes: int = 4) -> float:
        return self.traffic(batch).total_bytes(dtype_bytes)

    def bound_words(self, layer) -> float:
        """q_dram_training with each pass's Eq. (15) term evaluated at
        that pass's *realized* plan footprint (the same convention the
        forward tests score distance-to-bound with).  The forward term
        rides :meth:`ConvPlan.bound_words`, so a fused residual join's
        mandatory read is on the bound side too."""
        from repro.core.lower_bound import q_dram_dgrad, q_dram_wgrad

        return (self.fwd.bound_words(layer)
                + q_dram_dgrad(layer, self.dgrad.footprint_elems())
                + q_dram_wgrad(layer, self.wgrad.footprint_elems()))


def plan_conv_training(fwd: ConvPlan, *, batch: int, groups: int = 1,
                       dtype_bytes: int = 4,
                       vmem_budget: int | None = None,
                       autotune: bool = True) -> ConvTrainingPlan:
    """Derive the full training-step plan triple from a forward handle
    (every constituent ``plan_conv`` call is memoized, so this is as
    cheap as the forward planning after first touch).  ``groups`` is
    the executed conv's group count — plans carry per-*group*
    geometry, and grouped backwards take the lax fallback in
    ``conv2d_lb`` even at unit stride, so it gates ``dgrad_kernel``."""
    if not (fwd.ci and fwd.co):
        raise ValueError("forward plan carries no layer geometry; "
                         "build it via plan_conv")
    kw = dict(dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
              autotune=autotune)
    return ConvTrainingPlan(
        fwd=fwd,
        dgrad=plan_conv_dgrad(fwd, batch=batch, **kw),
        wgrad=plan_conv_wgrad(fwd, **kw),
        dgrad_kernel=dgrad_rides_kernel(fwd) and groups == 1)


def _pad_axis(a, axis, target):
    pad = target - a.shape[axis]
    if pad > 0:
        cfg = [(0, 0)] * a.ndim
        cfg[axis] = (0, pad)
        a = jnp.pad(a, cfg)
    return a


def _conv_one_group(x, w, bias, residual, plan: ConvPlan, py: int,
                    px: int, relu: bool, out_dtype,
                    interpret: bool, name: str, gelu: bool = False,
                    scale=None, norm=None) -> jax.Array:
    from repro.kernels.conv_lb.kernel import conv_lb_call

    b = x.shape[0]
    co = w.shape[3]
    blk = plan.blocks
    if plan.lhs_dilated:
        # x is the compact plane: pad with ceil(p/ld) leading zero-rows
        # and a tail up to the last tile's compact fetch
        (_, _, pc_y, rows_y), (_, _, pc_x, rows_x) = \
            plan.compact_geometry()
        x = jnp.pad(x, ((0, 0), (pc_y, rows_y - x.shape[1] - pc_y),
                        (pc_x, rows_x - x.shape[2] - pc_x), (0, 0)))
    else:
        x = jnp.pad(x, ((0, 0), (py, plan.hp_pad - x.shape[1] - py),
                        (px, plan.wp_pad - x.shape[2] - px), (0, 0)))
        # drop trailing rows/cols no window reads (a strided conv's
        # remainder): the last tile's halo then ends the plane, so a
        # sole x tile's fetch spans the full width, as Mosaic requires
        ny, nx = plan.ho_pad // blk.y, plan.wo_pad // blk.x
        x = x[:, :(ny - 1) * blk.y * plan.stride[0] + blk.halo_y,
              :(nx - 1) * blk.x * plan.stride[1] + blk.halo_x]
    x = _pad_axis(_pad_axis(x, 3, plan.ci_pad), 0, round_up(b, blk.b))
    w = _pad_axis(_pad_axis(w, 2, plan.ci_pad), 3, plan.co_pad)
    bias2d, scale2d = (
        None if v is None else _pad_axis(
            v.reshape(1, -1).astype(jnp.float32), 1, plan.co_pad)
        for v in (bias, scale))
    if residual is not None:
        # pad the join operand to the pre-pool psum-tile geometry
        residual = jnp.pad(residual,
                           ((0, 0), (0, plan.ho_pad - plan.ho),
                            (0, plan.wo_pad - plan.wo), (0, 0)))
        residual = _pad_axis(_pad_axis(residual, 3, plan.co_pad),
                             0, round_up(b, blk.b))
    out = conv_lb_call(x, w, bias=bias2d, residual=residual, relu=relu,
                       pool=plan.pool, gelu_act=gelu, scale=scale2d,
                       norm=None if norm is None
                       else norm.astype(jnp.float32),
                       stride=plan.stride, dilation=plan.dilation,
                       lhs_dilation=plan.lhs_dilation,
                       pad=(plan.py, plan.px),
                       out_plane=((plan.ho_pad, plan.wo_pad)
                                  if plan.lhs_dilated else None),
                       b_block=blk.b, y_block=blk.y, x_block=blk.x,
                       ci_block=blk.ci, co_block=blk.co,
                       out_dtype=out_dtype, interpret=interpret,
                       name=name)
    return out[:b, :plan.ho // plan.pool, :plan.wo // plan.pool, :co]


def _lax_conv(x, w, sy, sx, py, px, dy, dx, groups, ldy=1, ldx=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(sy, sx),
        padding=[(py, py), (px, px)], rhs_dilation=(dy, dx),
        lhs_dilation=(ldy, ldx),
        feature_group_count=groups,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32).astype(x.dtype)


# process-wide exec.fallback tally, keyed by pass ("fwd", "dgrad",
# "wgrad", "bwd" — the last is the wholesale backward fallback).
# Incremented at trace time alongside each loud ``exec.fallback``
# event (once per distinct traced geometry, like the events), so
# ledgers and benches can surface fallback counts instead of letting
# a silently-degraded path regress unnoticed.
FALLBACK_COUNTS: dict[str, int] = {}


def record_fallback(conv_pass: str, reason: str, *, target: str,
                    layer: str) -> None:
    """One loud fallback: traced ``exec.fallback`` event + tally."""
    FALLBACK_COUNTS[conv_pass] = FALLBACK_COUNTS.get(conv_pass, 0) + 1
    active_tracer().event("exec.fallback", target=target, to="lax",
                          layer=layer, reason=reason,
                          **{"pass": conv_pass})


def exec_fallback_counts() -> dict[str, int]:
    """Snapshot of the per-pass fallback tally (ledger summaries)."""
    return dict(FALLBACK_COUNTS)


def reset_fallback_counts() -> None:
    FALLBACK_COUNTS.clear()


def _lax_epilogue(y, bias, relu, pool, residual=None, *,
                  gelu: bool = False, scale=None, norm=None):
    """The unfused reference epilogue (bias -> exact GELU -> layer
    scale -> residual join -> relu -> LayerNorm over the channels ->
    maxpool) — the exact math the kernel fuses on the psum tile."""
    def f32(v):
        return v.astype(jnp.float32)

    if bias is not None:
        y = (f32(y) + f32(bias)).astype(y.dtype)
    if gelu:
        y = jax.nn.gelu(f32(y), approximate=False).astype(y.dtype)
    if scale is not None:
        y = (f32(y) * f32(scale)).astype(y.dtype)
    if residual is not None:
        y = (f32(y) + f32(residual)).astype(y.dtype)
    if relu:
        y = jnp.maximum(y, 0).astype(y.dtype)
    if norm is not None:
        from repro.kernels.conv_lb.kernel import layer_norm
        y = layer_norm(f32(y), f32(norm[0]), f32(norm[1])).astype(y.dtype)
    if pool > 1:
        y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                  (1, pool, pool, 1), (1, pool, pool, 1),
                                  "VALID")
    return y


@partial(jax.jit, static_argnames=("stride", "padding", "dilation",
                                   "lhs_dilation",
                                   "groups", "relu", "pool", "gelu",
                                   "interpret", "fallback", "autotune",
                                   "target",
                                   "b_block", "y_block", "x_block",
                                   "ci_block", "co_block",
                                   "kernel_name"))
def conv2d_lb(x: jax.Array, w: jax.Array, bias: jax.Array | None = None,
              residual: jax.Array | None = None,
              *, stride=1, padding=0, dilation=1, lhs_dilation=1,
              groups: int = 1,
              relu: bool = False, pool: int = 1,
              gelu: bool = False, scale: jax.Array | None = None,
              norm: jax.Array | None = None,
              b_block: int | None = None,
              y_block: int | None = None, x_block: int | None = None,
              ci_block: int | None = None, co_block: int | None = None,
              interpret: bool = True, autotune: bool = True,
              fallback: bool = False, target=None,
              kernel_name: str = "conv_fwd") -> jax.Array:
    """NHWC conv through the paper-dataflow batch-folded tiled kernel.

    x: (B, H, W, Ci); w: (Hk, Wk, Ci/groups, Co)
    -> (B, Ho/pool, Wo/pool, Co).
    ``stride``/``padding``/``dilation`` take an int or an (h, w) pair;
    ``dilation`` is kernel (rhs) dilation.  ``lhs_dilation`` inserts
    ``ld - 1`` zeros between input rows/cols *logically*: x stays the
    compact plane in HBM and the kernel re-dilates VMEM-resident
    fetches in-register, so the dilated-plane walk (a strided layer's
    dgrad, a transposed conv) never materializes or streams the zeros
    — the compact-fetch accounting :class:`ConvPlan` charges.  ``bias`` (shape (Co,)),
    ``residual`` (a (B, Ho, Wo, Co) pre-pool tensor — the shortcut
    join of a residual block, added after bias and before the ReLU),
    ``relu`` and ``pool`` (an aligned pool x pool max-pool, stride =
    pool) form the fused epilogue: applied in-kernel on the VMEM psum
    tile, so the layer issues a single output write and the shortcut
    join costs one streamed read instead of a separate
    write -> read -> add -> write HBM round trip.  ``fallback=True``
    routes through ``lax.conv_general_dilated`` + the unfused epilogue
    (same math, XLA's schedule).  ``gelu`` (exact, erf), ``scale`` (a
    (Co,) per-channel layer scale) and ``norm`` (the (2, Co) weight and
    bias rows of a LayerNorm over the output channels) extend the
    epilogue: bias -> GELU -> scale -> residual join -> ReLU -> norm ->
    pool; left unset they add nothing to what the layer traces.

    A depthwise layer (``groups == Ci == Co``, unit stride and
    dilation, no pool or residual) runs the channel-blocked ``conv_dw``
    kernel (:mod:`repro.kernels.conv_lb.depthwise`): one
    ``pallas_call`` for the layer; other grouped layers run one
    forward kernel per group.

    ``target`` (an :class:`~repro.core.exec_target.ExecTarget` or its
    name) is the first-class way to choose the backend and overrides
    the legacy ``interpret``/``fallback`` booleans: ``COMPILED`` plans
    at the mosaic legality profile and runs
    ``pallas_call(interpret=False)``; a geometry with no mosaic-legal
    plan degrades *loudly* to the lax path — a traced
    ``exec.fallback`` event, never a silent interpreter run.  The backward pass inherits the target;
    its dgrad conv re-negotiates per-layer (the dgrad geometry may be
    mosaic-legal when the forward is not, and vice versa).

    Differentiable, with a *kernel* backward: for ungrouped layers
    (strided included) dx is computed by the batch-folded Pallas
    kernel itself — the dgrad conv of dy against the spatially-flipped
    ``(Hk, Wk, Co, Ci)`` weights at full padding, with
    ``lhs_dilation=stride`` re-dilating the compact dy plane in-VMEM
    (:func:`plan_conv_dgrad`) — and dW executes through the
    dW-stationary Pallas kernel (:func:`plan_conv_wgrad` /
    :func:`~repro.kernels.conv_lb.wgrad.wgrad_lb_call`); db comes from
    the epilogue pullback.  Grouped or lhs-dilated layers fall back to
    the ``lax`` VJP wholesale, loudly (``exec.fallback`` events +
    :func:`exec_fallback_counts`), but remain planned and accounted
    through the same handles.

    ``kernel_name`` names the Pallas call on the device (``conv_fwd``;
    the backward's own dgrad conv passes ``conv_dgrad``, and the wgrad
    kernel is ``conv_wgrad``), so a profile tells the passes apart.
    """
    tgt = None if target is None else resolve_target(target)
    if tgt is not None:
        if not tgt.compute:
            raise ValueError("account-only target cannot execute a "
                             "conv; plan/account via conv_lb_traffic "
                             "or serve through an account-only server")
        fallback = not tgt.kernel
        interpret = tgt.interpret
    sy, sx = _pair(stride)
    py, px = _pair(padding)
    dy, dx = _pair(dilation)
    ldy, ldx = _pair(lhs_dilation)
    b, h, wd, ci = x.shape
    hk, wk, ci_g, co = w.shape
    if ci_g * groups != ci or co % groups:
        raise ValueError(f"groups={groups} incompatible with "
                         f"Ci={ci}, w Ci={ci_g}, Co={co}")
    # the plan sees the logically dilated plane; x stays compact
    h_d = (h - 1) * ldy + 1
    wd_d = (wd - 1) * ldx + 1

    epi = (scale, norm)
    extras = gelu or scale is not None or norm is not None

    def _lax_full(x, w, bias=None, residual=None, epi=(None, None)):
        return _lax_epilogue(_lax_conv(x, w, sy, sx, py, px, dy, dx,
                                       groups, ldy=ldy, ldx=ldx),
                             bias, relu, pool, residual=residual,
                             gelu=gelu, scale=epi[0], norm=epi[1])

    if fallback:
        return _lax_full(x, w, bias, residual, epi)

    plan_target = tgt.plan_target if tgt is not None else "interpret"

    def _loud_fallback(reason: str, conv_pass: str = "fwd") -> jax.Array:
        # a request this geometry can't honor degrades to lax with a
        # traced event + counter — never a silent interpreter run
        record_fallback(conv_pass, reason,
                        target=tgt.name if tgt is not None else "legacy",
                        layer=f"{ci}->{co}k{hk}x{wk}")
        return _lax_full(x, w, bias, residual, epi)

    depthwise = (groups > 1 and groups == ci == co and pool == 1
                 and residual is None
                 and (sy, sx, dy, dx, ldy, ldx) == (1, 1, 1, 1, 1, 1))
    if norm is not None and groups > 1 and not depthwise:
        # a per-group kernel sees a slice of the channels it normalizes
        return _loud_fallback("a LayerNorm over a grouped, not "
                              "depthwise, conv")
    if depthwise:
        from repro.analysis.plan_check import PlanLegalityError
        from repro.kernels.conv_lb.depthwise import (conv_dw_call,
                                                     plan_conv_dw)
        try:
            dplan = plan_conv_dw(h, wd, ci, hk, wk, padding=(py, px),
                                 norm=norm is not None,
                                 dtype_bytes=x.dtype.itemsize,
                                 target=plan_target)
        except PlanLegalityError:
            if plan_target == "interpret":
                raise
            return _loud_fallback("no mosaic-legal depthwise plan under "
                                  "the budget", "dw")

        def _run(x, w, bias, residual, epi):
            return conv_dw_call(x, w, dplan, bias=bias, scale=epi[0],
                                norm=epi[1], relu=relu, gelu_act=gelu,
                                interpret=interpret)
        return _with_lax_vjp(_run, _lax_full, x, w, bias, residual, epi,
                             target=tgt, layer=f"{ci}->{co}k{hk}x{wk}",
                             reason="depthwise forward")

    try:
        plan = plan_conv(h_d, wd_d, ci_g, co // groups, hk, wk, batch=b,
                         stride=(sy, sx), padding=(py, px),
                         dilation=(dy, dx), lhs_dilation=(ldy, ldx),
                         pool=pool,
                         residual=residual is not None,
                         norm=norm is not None,
                         dtype_bytes=x.dtype.itemsize,
                         autotune=autotune, target=plan_target)
    except Exception as e:
        from repro.analysis.plan_check import PlanLegalityError
        if plan_target == "interpret" or not isinstance(
                e, PlanLegalityError):
            raise
        return _loud_fallback("no mosaic-legal plan under the budget")
    if any(v is not None for v in (b_block, y_block, x_block,
                                   ci_block, co_block)):
        bk = plan.blocks
        # halo placeholders only: plan_conv recomputes the overlapping
        # BlockSpec halos from the override's (y, x) and the layer's
        # stride/dilation (an override must never keep the tuned plan's
        # halos — they belong to the tuned tile sizes)
        override = ConvBlockShape(
            y=bk.y if y_block is None else y_block,
            x=bk.x if x_block is None else x_block,
            co=bk.co if co_block is None else co_block,
            ci=bk.ci if ci_block is None else ci_block,
            halo_y=0, halo_x=0,
            b=bk.b if b_block is None else b_block)
        plan = plan_conv(h_d, wd_d, ci_g, co // groups, hk, wk, batch=b,
                         stride=(sy, sx), padding=(py, px),
                         dilation=(dy, dx), lhs_dilation=(ldy, ldx),
                         pool=pool,
                         residual=residual is not None, blocks=override,
                         norm=norm is not None, target=plan_target)
        if plan_target != "interpret":
            # explicit overrides bypass plan_conv's gate; a compiled
            # execution still refuses (loudly) to run an illegal shape
            from repro.analysis.plan_check import (check_conv_plan,
                                                   errors)
            diags = check_conv_plan(plan, batch=b,
                                    dtype_bytes=x.dtype.itemsize,
                                    target=plan_target)
            if errors(diags):
                return _loud_fallback(
                    "explicit blocks are not mosaic-legal")
    co_g = co // groups

    def _run(x, w, bias, residual, epi=(None, None)):
        def cut(v, g):
            return None if v is None else v[..., g * co_g:(g + 1) * co_g]
        outs = [_conv_one_group(
            x[..., g * ci_g:(g + 1) * ci_g], cut(w, g), cut(bias, g),
            cut(residual, g), plan, py, px, relu, x.dtype, interpret,
            kernel_name, gelu=gelu, scale=cut(epi[0], g), norm=epi[1])
            for g in range(groups)]
        return outs[0] if groups == 1 else jnp.concatenate(outs, axis=-1)

    _tgt_name = tgt.name if tgt is not None else "legacy"
    _layer_tag = f"{ci}->{co}k{hk}x{wk}"
    if groups != 1 or ldy > 1 or ldx > 1 or extras:
        # the kernel backward peels only the bias/residual/relu/pool
        # epilogue of an ungrouped conv
        return _with_lax_vjp(_run, _lax_full, x, w, bias, residual, epi,
                             target=tgt, layer=_layer_tag,
                             reason="grouped, lhs-dilated or "
                                    "GELU/scale/norm forward")

    @jax.custom_vjp
    def kernel_conv(x, w, bias, residual):
        return _run(x, w, bias, residual)

    def _fwd(x, w, bias, residual):
        return kernel_conv(x, w, bias, residual), (x, w, bias, residual)

    def _dgrad_lax_fallback(x, w, gy, reason):
        record_fallback("dgrad", reason, target=_tgt_name,
                        layer=_layer_tag)
        _, vjp = jax.vjp(
            lambda xx: _lax_conv(xx, w, sy, sx, py, px, dy, dx, 1), x)
        (gx,) = vjp(gy)
        return gx

    def _wgrad_lax_fallback(x, w, gy, reason):
        record_fallback("wgrad", reason, target=_tgt_name,
                        layer=_layer_tag)
        _, vjp = jax.vjp(
            lambda ww: _lax_conv(x, ww, sy, sx, py, px, dy, dx, 1), w)
        (gw,) = vjp(gy)
        return gw

    def _bwd(res, g):
        x, w, bias, residual = res
        # 1) peel the epilogue: recompute the pre-epilogue conv output
        #    (cheaper than spilling it from the fused kernel, whose
        #    whole point is the single post-epilogue write) and pull g
        #    back through bias/residual/relu/pool; db and the residual
        #    cotangent (the join's pass-through) fall out here
        y = _lax_conv(x, w, sy, sx, py, px, dy, dx, 1)
        _, epi_vjp = jax.vjp(
            lambda yy, bb, rr: _lax_epilogue(yy, bb, relu, pool,
                                             residual=rr),
            y, bias, residual)
        gy, db, dres = epi_vjp(g)
        # 2) dgrad through the planned kernel: dy * flipped weights at
        #    full padding rides the same batch-folded u x z dataflow;
        #    a strided forward hands the *compact* dy plane to the
        #    kernel with lhs_dilation = stride.  The dgrad conv
        #    re-negotiates the target per-layer: its geometry may be
        #    mosaic-legal when the forward is not
        if dgrad_rides_kernel(plan):
            # a strided forward's dilated dy plane ends (h + 2p - ekh)
            # % s rows short of covering the last real input rows; one
            # appended compact zero row/col (s dilated positions, all
            # zero) covers any such remainder, and the crop below
            # drops the surplus
            gyp = (jnp.pad(gy, ((0, 0), (0, int(sy > 1)),
                                (0, int(sx > 1)), (0, 0)))
                   if sy > 1 or sx > 1 else gy)
            gx = conv2d_lb(gyp, _flip_w(w), None, stride=1,
                           padding=((hk - 1) * dy - py,
                                    (wk - 1) * dx - px),
                           dilation=(dy, dx), lhs_dilation=(sy, sx),
                           interpret=interpret,
                           autotune=autotune, target=tgt,
                           kernel_name="conv_dgrad")
            gx = gx[:, :h, :wd]
        else:
            gx = _dgrad_lax_fallback(
                x, w, gy, "padding past the full-padding transform")
        # 3) wgrad through the dW-stationary Pallas kernel executing
        #    the planned blocks (legality-gated, like the forward)
        wplan = plan_conv_wgrad(plan, dtype_bytes=x.dtype.itemsize)
        from repro.analysis.plan_check import check_wgrad_plan, errors
        werrs = errors(check_wgrad_plan(wplan, batch=b,
                                        dtype_bytes=x.dtype.itemsize,
                                        target=plan_target))
        if werrs:
            gw = _wgrad_lax_fallback(x, w, gy, "; ".join(werrs))
        else:
            gw = wgrad_lb_call(x, gy, wplan,
                               interpret=interpret)[..., :ci, :co]
            gw = gw.astype(w.dtype)
        return gx, gw, db, dres

    kernel_conv.defvjp(_fwd, _bwd)
    return kernel_conv(x, w, bias, residual)


def _with_lax_vjp(run, lax_full, x, w, bias, residual, epi, *, target,
                  layer: str, reason: str) -> jax.Array:
    """``run(x, w, bias, residual, epi)`` forward through a kernel whose
    backward is the lax VJP of ``lax_full`` (same arguments), taken
    wholesale and loudly: an ``exec.fallback`` event and a "bwd" count
    when a gradient is asked for.  bias/residual/epi entries of None
    are leafless pytree primals, so one scaffold covers every arity."""
    @jax.custom_vjp
    def conv(x, w, bias, residual, epi):
        return run(x, w, bias, residual, epi)

    def fwd(*res):
        return conv(*res), res

    def bwd(res, g):
        record_fallback("bwd", reason, layer=layer,
                        target=target.name if target is not None
                        else "legacy")
        _, vjp = jax.vjp(lax_full, *res)
        return vjp(g)

    conv.defvjp(fwd, bwd)
    return conv(x, w, bias, residual, epi)


def conv2d_lb_timed(x: jax.Array, w: jax.Array,
                    bias: jax.Array | None = None,
                    residual: jax.Array | None = None,
                    *, stride=1, padding=0, dilation=1,
                    groups: int = 1, relu: bool = False, pool: int = 1,
                    gelu: bool = False, scale=None, norm=None,
                    interpret: bool = True, autotune: bool = True,
                    fallback: bool = False, target=None,
                    tracer=None, clock=None,
                    name: str = "kernel.conv2d_lb") -> jax.Array:
    """:func:`conv2d_lb` with a synced, *accounted* span around the
    call: blocks on the result, then records one span carrying both
    the measured seconds and the plan's analytic ``traffic_bytes`` —
    i.e. the achieved-GB/s sample the roofline needs, per layer.

    ``tracer`` defaults to the ambient tracer; ``clock`` (injectable,
    lint L005/L006 idiom) defaults to the tracer's own clock, so under
    a ``VirtualClock`` the trace stays deterministic while real runs
    get ``time.perf_counter`` semantics.  The span fires for the
    kernel path *and* the lax fallback (``mode`` attr tells them
    apart); accounting is identical — the plan charges the dataflow,
    not the executor.  ``target`` (an
    :class:`~repro.core.exec_target.ExecTarget` or name) supersedes
    the ``interpret``/``fallback`` booleans and names the span's
    ``mode``; the accounted bytes come from the plan at the target's
    legality profile (the dataflow actually executed)."""
    from repro.analysis.plan_check import PlanLegalityError

    tgt = None if target is None else resolve_target(target)
    tr = active_tracer() if tracer is None else tracer
    clk = tr.now if clock is None else clock
    sy, sx = _pair(stride)
    py, px = _pair(padding)
    dy, dx = _pair(dilation)
    b, h, wd, ci = x.shape
    hk, wk, ci_g, co = w.shape
    plan_target = tgt.plan_target if tgt is not None else "interpret"
    if groups > 1 and groups == ci == co and (sy, sx, dy, dx) == (
            1, 1, 1, 1) and pool == 1 and residual is None:
        from repro.kernels.conv_lb.depthwise import plan_conv_dw

        def planned(target):
            return plan_conv_dw(h, wd, ci, hk, wk, padding=(py, px),
                                norm=norm is not None,
                                dtype_bytes=x.dtype.itemsize,
                                target=target)
        n_plans = 1
    else:
        def planned(target):
            return plan_conv(h, wd, ci_g, co // groups, hk, wk,
                             batch=b, stride=(sy, sx), padding=(py, px),
                             dilation=(dy, dx), pool=pool,
                             residual=residual is not None,
                             norm=norm is not None and groups == 1,
                             dtype_bytes=x.dtype.itemsize,
                             autotune=autotune, target=target)
        n_plans = groups
    try:
        plan = planned(plan_target)
    except PlanLegalityError:
        # execution will degrade to lax; account the interpret-profile
        # dataflow (the words any planned schedule at least moves)
        plan = planned("interpret")
    if tgt is not None:
        mode = tgt.name
    else:
        mode = "lax" if fallback else "kernel"
    n_bytes = n_plans * plan.traffic_bytes(b, dtype_bytes=x.dtype.itemsize)
    with tr.span(name, layer=f"{ci}->{co}k{hk}x{wk}",
                 mode=mode,
                 batch=b, traffic_bytes=n_bytes) as sp:
        t0 = clk()
        out = conv2d_lb(x, w, bias, residual, stride=stride,
                        padding=padding, dilation=dilation,
                        groups=groups, relu=relu, pool=pool,
                        gelu=gelu, scale=scale, norm=norm,
                        interpret=interpret, autotune=autotune,
                        fallback=fallback, target=tgt)
        out = jax.block_until_ready(out)
        dt = clk() - t0
        sp.set(us=dt * 1e6,
               achieved_gbps=(n_bytes / dt / 1e9) if dt > 0 else None)
    return out


# --------------------------------------------------------------------------
# analytic HBM-traffic accountant
# --------------------------------------------------------------------------

def conv_lb_traffic(batch: int, h: int, w: int, ci: int, co: int,
                    hk: int, wk: int, *, stride=1, padding=0,
                    dilation=1, groups: int = 1, pool: int = 1,
                    plan: ConvPlan | None = None,
                    vmem_budget: int | None = None,
                    dtype_bytes: int = 4,
                    autotune: bool = True) -> tuple[Traffic, ConvPlan]:
    """Exact HBM words moved by ``conv2d_lb`` for this layer (per group
    geometry x ``groups``), derived from the kernel's BlockSpecs — see
    :func:`_blocks_traffic` for the fetch rule.  ``autotune=False``
    scores the closed-form (non-tuned) plan instead.  With an explicit
    ``plan``, an explicit ``pool`` (> 1) overrides the plan's (the
    blocks must be pool-aligned); ``pool=1`` defers to ``plan.pool``."""
    ci_g, co_g = ci // groups, co // groups
    if plan is None:
        plan = plan_conv(h, w, ci_g, co_g, hk, wk, batch=batch,
                         stride=_pair(stride), padding=_pair(padding),
                         dilation=_pair(dilation), pool=pool,
                         dtype_bytes=dtype_bytes,
                         vmem_budget=vmem_budget, autotune=autotune)
    elif pool > 1 and plan.pool != pool:
        if plan.blocks.y % pool or plan.blocks.x % pool:
            raise ValueError(f"plan tiles {plan.blocks.y}x{plan.blocks.x}"
                             f" are not pool={pool} aligned")
        plan = dataclasses.replace(plan, pool=pool)
    t = plan.traffic(batch)
    t = Traffic(reads_in=t.reads_in * groups,
                reads_w=t.reads_w * groups,
                reads_out=0.0,
                writes_out=t.writes_out * groups)
    return t, plan


def conv_lb_traffic_bytes(*args, dtype=None, dtype_bytes: int | None = None,
                          **kw) -> float:
    """Total HBM bytes moved (all tensors at one word size).

    The word size comes from ``dtype`` (anything ``jnp.dtype`` accepts,
    e.g. ``jnp.bfloat16`` for bf16 serving) when given; an explicit
    ``dtype_bytes`` overrides it; with neither, f32 words."""
    if dtype_bytes is None:
        dtype_bytes = jnp.dtype(dtype).itemsize if dtype is not None else 4
    t, _ = conv_lb_traffic(*args, dtype_bytes=dtype_bytes, **kw)
    return t.total * dtype_bytes
