"""TPU adaptation of the paper's optimality conditions (DESIGN.md §2).

Maps {S, u, z, k} of the ASIC formulation onto Pallas BlockSpec block
shapes for the MXU/VMEM hierarchy:

  * S            -> VMEM budget per core (bytes);
  * u x z psums  -> bm x bn f32 accumulator block, with the paper's two
                    conditions  bm ~= R*bn  and  bm*bn ~= S_eff;
  * k = 1        -> bk = smallest MXU-aligned reduction slice (128/256/512):
                    on TPU the reduction slice must still fill the
                    128-wide systolic array, so k=1 becomes bk>=128
                    (assumption change recorded in DESIGN.md §7);
  * WndR         -> halo-extended input blocks chosen for the conv kernel.

Also provides the per-chip communication-balance rule used by the
mesh-level sharding (the beyond-paper extension)."""

from __future__ import annotations

import dataclasses
import itertools

# --- TPU v5e hardware constants (per chip) ----------------------------------
PEAK_BF16_FLOPS = 197e12          # MXU bf16
HBM_BYTES_PER_S = 819e9
ICI_BYTES_PER_S = 50e9            # per link
VMEM_BYTES = 128 * 1024 * 1024    # v5e VMEM per core (physical)
# scoped VMEM one kernel asks Mosaic for (``vmem_limit_bytes``) and the
# execution planner's default budget — one number, so a plan that fits
# the budget is a kernel Mosaic grants.  Mosaic's own default scope on
# v5e is 16 MiB; the physical 128 MiB also holds Mosaic's internal
# scratch and the in-kernel temporaries the mosaic profile charges
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
# cap on one in-kernel value under the mosaic profile: Mosaic unrolls
# vector code over the tile, so a 1 MiB tile keeps each kernel's
# compile near a second while still feeding the MXU 1-2k-row matmuls
MOSAIC_TILE_BYTES = 1024 * 1024
HBM_BYTES = 16 * 1024 * 1024 * 1024
MXU_DIM = 128                     # systolic array edge
LANE = 128                        # last-dim tile
# dtype bytes -> second-minor (sublane) tile: Mosaic packs narrower
# words deeper, so the minimum tile *grows* as the word shrinks —
# f32 (8, 128), bf16 (16, 128), int8/fp8 (32, 128)
SUBLANE = {1: 32, 2: 16, 4: 8}


def sublane_for(dtype_bytes: int) -> int:
    """Mosaic second-minor tile for a word size; unknown sizes take
    the 1-byte (deepest-packing) tile — the safe over-alignment."""
    return SUBLANE.get(dtype_bytes, SUBLANE[1])


def round_to(v: int, mult: int) -> int:
    return max(mult, (v // mult) * mult)


def round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


@dataclasses.dataclass(frozen=True)
class BlockShape:
    """Pallas matmul/conv block geometry."""

    bm: int   # output rows per block   (paper: u)
    bn: int   # output cols per block   (paper: z)
    bk: int   # reduction slice         (paper: k, MXU-adapted)

    @property
    def psum_bytes(self) -> int:
        return self.bm * self.bn * 4          # f32 accumulator

    def operand_bytes(self, dtype_bytes: int = 2) -> int:
        return (self.bm * self.bk + self.bk * self.bn) * dtype_bytes

    def vmem_bytes(self, dtype_bytes: int = 2) -> int:
        # double-buffered operands (Pallas pipelining) + resident psums
        return self.psum_bytes + 2 * self.operand_bytes(dtype_bytes)


def lb_block_shape(m: int, n: int, k: int, *,
                   r: float = 1.0,
                   dtype_bytes: int = 2,
                   vmem_budget: int = VMEM_LIMIT_BYTES,
                   bk: int | None = None,
                   align: int = MXU_DIM) -> BlockShape:
    """Choose {bm, bn, bk} from the paper's lower-bound conditions.

    The geometry is *seeded by the paper's closed form*
    (:func:`repro.core.lower_bound.optimal_block`: u = R*z, u*z = S on
    the f32 psum budget), then MXU/lane-aligned and shrunk until psums
    plus double-buffered operand panels fit ``vmem_budget``.  With r==1
    the block is square (sqrt(S) x sqrt(S)) — the communication-optimal
    matmul of Sec. III.  This is the single block chooser: the conv
    kernel's spatial tiling (:func:`conv_lb_block_shape`) routes
    through it too.
    """
    from repro.core.lower_bound import optimal_block

    if bk is None:
        # smallest aligned slice that keeps the MXU pipeline full; the
        # paper's k=1 principle (stream the reduction minimally) under
        # the 128-alignment constraint.
        bk = min(round_up(min(k, 512), align), round_up(k, align))
    # paper Sec. IV-C closed form on the f32 psum element budget
    tiles = optimal_block(max(align * align, vmem_budget // 4), r)
    bm = min(round_up(tiles.u, align), round_up(m, align))
    bn = min(round_up(tiles.z, align), round_up(n, align))
    # shrink toward bm ~= r*bn until the VMEM working set fits
    while BlockShape(bm, bn, bk).vmem_bytes(dtype_bytes) > vmem_budget \
            and (bm > align or bn > align):
        if bm > max(align, round_to(int(r * bn), align)):
            bm -= align
        elif bn > align and round_to(int(r * (bn - align)), align) \
                >= bm - align:
            bn -= align
            bm = max(align, min(bm, round_to(int(r * bn), align)))
        else:
            bm = max(align, bm - align)
            bn = max(align, bn - align)
    return BlockShape(bm=max(align, bm), bn=max(align, bn), bk=bk)


@dataclasses.dataclass(frozen=True)
class ConvBlockShape:
    """Pallas conv block geometry: the paper's {u, z, k} in conv space.

    u = b*y*x batch-folded psum tile (the paper's u is over *output
    elements* B*Ho*Wo, so a block of b images folds straight into it),
    z = co channels resident, k = ci slice streamed per pass;
    (halo_y, halo_x) is the halo-extended input footprint of one (y, x)
    output tile — batch rows add u without adding halo."""

    y: int
    x: int
    co: int
    ci: int
    halo_y: int
    halo_x: int
    b: int = 1

    @property
    def u(self) -> int:
        return self.b * self.y * self.x

    @property
    def psum_bytes(self) -> int:
        return self.u * self.co * 4               # f32 accumulator

    def operand_bytes(self, hk: int, wk: int, dtype_bytes: int = 4) -> int:
        return (self.b * self.halo_y * self.halo_x * self.ci
                + hk * wk * self.ci * self.co) * dtype_bytes

    def vmem_bytes(self, hk: int, wk: int, dtype_bytes: int = 4,
                   w_pinned: bool = False, residual: bool = False) -> int:
        # double-buffered streamed panels + resident psums; a weight
        # block whose index map is constant over the whole grid (sole
        # Ci and Co block) is never re-fetched, so it needs no second
        # pipelining buffer — pass w_pinned=True to count it once.
        # A fused residual join streams one more double-buffered
        # psum-tile-shaped operand (u x co at the serving dtype)
        in_buf = 2 * self.b * self.halo_y * self.halo_x * self.ci
        w_buf = (1 if w_pinned else 2) * hk * wk * self.ci * self.co
        r_buf = 2 * self.u * self.co if residual else 0
        return self.psum_bytes + (in_buf + w_buf + r_buf) * dtype_bytes

    def footprint_elems(self, hk: int, wk: int,
                        residual: bool = False) -> int:
        """On-chip words S of the paper's model (no double buffering).
        A fused residual join holds one more u x co operand tile."""
        return (self.u * self.co * (2 if residual else 1)
                + self.b * self.halo_y * self.halo_x * self.ci
                + hk * wk * self.ci * self.co)

    def mosaic_vmem_bytes(self, hk: int, wk: int, dtype_bytes: int = 4,
                          *, fetch: tuple[int, int], pool: int = 1,
                          residual: bool = False,
                          dilated: tuple[int, int] | None = None) -> int:
        """VMEM the compiled kernel really holds: every block Mosaic
        double-buffers (input fetch of ``fetch`` rows x cols, weights,
        residual, output) plus the f32 psum scratch, the lhs-dilation
        scratch of ``dilated`` rows x cols, and the window-sweep
        temporaries (one input slice, the dot result and the psum
        reload), each laid out on (sublane, LANE) tiles."""
        db = dtype_bytes
        s = sublane_for(db)
        ci, co = round_up(self.ci, LANE), round_up(self.co, LANE)
        row = self.b * self.y * round_up(self.x, s)      # psum rows
        out = self.b * (self.y // pool) * round_up(self.x // pool, s)
        n = (2 * self.b * fetch[0] * round_up(fetch[1], s) * ci * db
             + 2 * hk * wk * round_up(self.ci, s) * co * db
             + 2 * out * co * db
             + 3 * row * co * 4 + row * ci * db)
        if residual:
            n += 2 * row * co * db
        if pool > 1:                                  # pool scratch
            n += self.b * (self.y // pool) * round_up(self.x, s) * LANE * 4
        if dilated is not None:
            n += self.b * dilated[0] * round_up(dilated[1], s) * ci * db
        return n

    def mosaic_tile_bytes(self, dtype_bytes: int = 4) -> int:
        """Largest single in-kernel value (the f32 psum tile or one
        window slice) on (sublane, LANE) tiles — what Mosaic unrolls
        over vector registers, so its compile time scales with it."""
        rows = self.b * self.y * round_up(self.x, sublane_for(dtype_bytes))
        return rows * max(round_up(self.co, LANE) * 4,
                          round_up(self.ci, LANE) * dtype_bytes)


def balanced_tile(dim: int, t: int) -> int:
    """Largest tile <= t splitting dim into equal ceil pieces —
    minimal padding waste (cf. layer.balanced_candidates)."""
    return -(-dim // -(-dim // max(1, t)))


def conv_lb_block_shape(ho: int, wo: int, ci: int, co: int,
                        hk: int, wk: int, *,
                        batch: int = 1,
                        stride: tuple[int, int] = (1, 1),
                        dilation: tuple[int, int] = (1, 1),
                        dtype_bytes: int = 4,
                        vmem_budget: int = VMEM_LIMIT_BYTES
                        ) -> ConvBlockShape:
    """Spatially-tiled conv blocks from the paper's two key conditions.

    Routes :func:`repro.core.lower_bound.optimal_block` through
    :func:`lb_block_shape` on the layer's converted-matmul view
    (Fig. 3: M = B*Ho*Wo, N = Co, K = Ci) with the conv reuse factor
    R = Hk*Wk/(sy*sx), then unfolds bm back into a batch-folded
    (b, y, x) tile (:func:`repro.core.lower_bound.fold_u`: square-ish
    spatial tile first, leftover u into batch) and shrinks until the
    halo-extended working set fits.
    """
    from repro.core.lower_bound import fold_u

    sy, sx = stride
    r = max(1.0, (hk * wk) / float(sy * sx))
    # lane-width alignment only makes sense once the budget affords
    # 128-wide blocks; at paper-scale (ASIC GBuf-sized) budgets it
    # would pin z to 128 and destroy the u ~= R*z balance, so fall
    # back to the *dtype's* sublane there — bf16 needs 16 rows where
    # f32 needs 8, int8 needs 32 (an 8-row bf16 block is not a legal
    # Mosaic tile, it only looked aligned under the old f32 constant).
    align = (MXU_DIM if vmem_budget >= 8 * 1024 * 1024
             else sublane_for(dtype_bytes))
    blk = lb_block_shape(batch * ho * wo, co, ci, r=r,
                         dtype_bytes=dtype_bytes,
                         vmem_budget=vmem_budget, align=align,
                         bk=min(round_up(ci, align), align))
    co_b = max(1, min(co, blk.bn))
    ci_b = max(1, min(ci, blk.bk))
    u = max(1, min(blk.bm, batch * ho * wo))
    tb, ty, tx = fold_u(u, batch, ho, wo)
    # snap to balanced tile sizes: ceil(dim/n) splits cover the plane
    # with minimal padding waste (cf. layer.balanced_candidates)
    ty = balanced_tile(ho, ty)
    tx = balanced_tile(wo, tx)
    tb = balanced_tile(batch, tb)

    def mk(tb, ty, tx, co_b, ci_b):
        yp = (ty - 1) * sy + (hk - 1) * dilation[0] + 1
        xp = (tx - 1) * sx + (wk - 1) * dilation[1] + 1
        return ConvBlockShape(y=ty, x=tx, co=co_b, ci=ci_b,
                              halo_y=yp, halo_x=xp, b=tb)

    cand = mk(tb, ty, tx, co_b, ci_b)
    # halos are ignored by the matmul view: shrink (largest-first) the
    # dims that only cost memory until the real working set fits
    while cand.vmem_bytes(hk, wk, dtype_bytes) > vmem_budget:
        if ci_b > 8:
            ci_b = max(8, ci_b // 2)
        elif tb > 1:
            tb = tb // 2              # batch rows are pure psum+halo
        elif ty * tx > 64 and ty >= tx:
            ty = max(1, ty // 2)
        elif ty * tx > 64:
            tx = max(1, tx // 2)
        elif co_b > 8:
            co_b = max(8, co_b // 2)
        elif ty * tx > 1:
            ty, tx = max(1, ty // 2), max(1, tx // 2)
        elif ci_b > 1 or co_b > 1:
            ci_b, co_b = max(1, ci_b // 2), max(1, co_b // 2)
        else:
            break                     # nothing left to shrink
        cand = mk(tb, ty, tx, co_b, ci_b)
    # snapping never grows a dim, so the budget check above still holds
    return mk(balanced_tile(batch, tb), balanced_tile(ho, ty), balanced_tile(wo, tx),
              balanced_tile(co, co_b), balanced_tile(ci, ci_b))


def conv_block_candidates(batch: int, ho: int, wo: int, ci: int
                          ) -> "itertools.product":
    """Candidate (b, y, x, ci_b) tuples for the plan autotuner.

    Geometric subsample of the balanced-split sets (every optimum of a
    ceil-based traffic formula lies on the balanced set; the geometric
    thinning keeps it within a (1+eps) factor — cf. layer.py).  The
    best co_b is solved analytically by the scorer (largest fitting the
    budget: weight traffic is ~co_b-independent, input traffic strictly
    falls with co_b), so it is not enumerated here.
    """
    from repro.core.layer import balanced_candidates, geometric_candidates

    def cands(dim: int, base: float) -> list[int]:
        bal = balanced_candidates(dim)
        geo = set(geometric_candidates(dim, base=base, include=(dim,)))
        return [c for c in bal if c in geo] or bal

    return itertools.product(cands(batch, 1.6), cands(ho, 2.0),
                             cands(wo, 2.0), cands(ci, 2.0))


def hbm_traffic_model(m: int, n: int, k: int, blk: BlockShape,
                      dtype_bytes: int = 2) -> float:
    """Eq. (14) instantiated for the kernel: HBM bytes moved.

    Per bm x bn output block: A-panel bm*k + B-panel k*bn read once,
    C written once."""
    nblocks_m = -(-m // blk.bm)
    nblocks_n = -(-n // blk.bn)
    reads = nblocks_n * (m * k) + nblocks_m * (k * n)
    writes = m * n
    return float((reads + writes) * dtype_bytes)


def arithmetic_intensity(m: int, n: int, k: int, blk: BlockShape,
                         dtype_bytes: int = 2) -> float:
    flops = 2.0 * m * n * k
    return flops / hbm_traffic_model(m, n, k, blk, dtype_bytes)


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Mesh-level communication balance (beyond-paper, DESIGN.md §5)."""

    m_shards: int
    n_shards: int

    def per_chip_tile(self, m: int, n: int) -> tuple[int, int]:
        return -(-m // self.m_shards), -(-n // self.n_shards)


def balanced_shard_plan(m: int, n: int, chips: int,
                        r: float = 1.0) -> ShardPlan:
    """Apply u ~= R*z at the mesh level: per-chip output tile as square
    as R allows, which minimizes the all-gather volume of the two
    operand panels (the ICI analogue of Eq. (14))."""
    best, best_cost = None, None
    for mshard in range(1, chips + 1):
        if chips % mshard:
            continue
        nshard = chips // mshard
        pm, pn = -(-m // mshard), -(-n // nshard)
        # per-chip panel traffic ~ pm*K + K*pn ;  minimized when pm ~= r*pn
        cost = pm / r + pn
        if best_cost is None or cost < best_cost:
            best, best_cost = ShardPlan(mshard, nshard), cost
    return best
