"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tpu_adapter import BlockShape, lb_block_shape
from repro.kernels.conv_lb.ops import conv2d_lb
from repro.kernels.conv_lb.ref import conv2d_ref
from repro.kernels.matmul_lb.ops import matmul_lb
from repro.kernels.matmul_lb.ref import matmul_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 8e-2}


def _allclose(out, ref, dtype):
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 10)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [
    (64, 64, 64), (128, 256, 128), (300, 200, 150), (1000, 333, 77),
    (8, 8, 8), (257, 129, 511),
])
def test_matmul_lb_sweep(m, k, n, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k),
                          jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n),
                          jnp.float32).astype(dtype)
    _allclose(matmul_lb(x, w), matmul_ref(x, w), dtype)


def test_matmul_lb_block_shape_invariance():
    """The lower-bound tiling must not change results (psum exactness)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 192))
    w = jax.random.normal(jax.random.PRNGKey(1), (192, 160))
    ref = matmul_ref(x, w)
    for blk in [BlockShape(64, 64, 64), BlockShape(128, 128, 64),
                BlockShape(256, 160, 192), BlockShape(64, 32, 32)]:
        _allclose(matmul_lb(x, w, blk=blk), ref, jnp.float32)


def test_lb_block_shape_conditions():
    """Chooser: MXU-aligned, psum-dominant, square-ish (R=1)."""
    blk = lb_block_shape(4096, 4096, 4096)
    assert blk.bm % 128 == 0 and blk.bn % 128 == 0 and blk.bk % 128 == 0
    assert blk.bm == blk.bn                     # u ~= z balance
    assert blk.vmem_bytes(2) <= 64 * 1024 * 1024
    assert blk.psum_bytes >= blk.operand_bytes(2)   # psums get most


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,w,ci,co,k,s,p", [
    (2, 16, 16, 8, 16, 3, 1, 1),
    (1, 14, 14, 24, 40, 3, 1, 1),
    (2, 12, 12, 6, 10, 3, 2, 1),
    (1, 9, 9, 5, 7, 1, 1, 0),
    (1, 20, 20, 16, 32, 5, 1, 2),
    (1, 8, 8, 3, 4, 3, 2, 0),
])
def test_conv_lb_sweep(b, h, w, ci, co, k, s, p, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (b, h, w, ci),
                          jnp.float32).astype(dtype)
    wt = (jax.random.normal(jax.random.PRNGKey(1), (k, k, ci, co),
                            jnp.float32) * 0.2).astype(dtype)
    out = conv2d_lb(x, wt, stride=s, padding=p)
    ref = conv2d_ref(x, wt, stride=s, padding=p)
    assert out.shape == ref.shape
    _allclose(out, ref, dtype)


def test_conv_lb_block_split_invariance():
    """Ci/Co block sizes are a pure dataflow choice (no numerics)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 10, 12))
    wt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 12, 20)) * 0.2
    ref = conv2d_ref(x, wt, padding=1)
    for cib, cob in [(4, 4), (12, 20), (6, 10), (12, 8)]:
        out = conv2d_lb(x, wt, padding=1, ci_block=cib, co_block=cob)
        _allclose(out, ref, jnp.float32)


@pytest.mark.parametrize("b,h,w,ci,co,k,s,p,d,g", [
    (1, 17, 13, 5, 6, 3, 1, 1, 2, 1),      # dilated, odd plane
    (1, 16, 16, 8, 8, 3, 1, 1, 3, 1),      # heavy dilation
    (2, 16, 16, 8, 12, 3, 1, 1, 1, 4),     # grouped
    (1, 12, 12, 6, 6, 3, 2, 1, 1, 3),      # grouped + strided
    (2, 15, 11, 7, 9, 3, 2, 1, 1, 1),      # odd strided
    (1, 21, 21, 6, 8, 5, 2, 2, 1, 1),      # 5x5 strided
    (1, 14, 10, 4, 6, 3, (2, 1), (1, 0), (1, 2), 1),  # asymmetric
])
def test_conv_lb_general_sweep(b, h, w, ci, co, k, s, p, d, g):
    """Stride/dilation/groups/odd-shape parity vs lax.conv (Fig. 3)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (b, h, w, ci),
                          jnp.float32)
    wt = jax.random.normal(jax.random.PRNGKey(1),
                           (k, k, ci // g, co), jnp.float32) * 0.2
    out = conv2d_lb(x, wt, stride=s, padding=p, dilation=d, groups=g)
    ref = conv2d_ref(x, wt, stride=s, padding=p, dilation=d, groups=g)
    assert out.shape == ref.shape
    _allclose(out, ref, jnp.float32)


@pytest.mark.parametrize("s,p,d,g", [(1, 1, 1, 1), (2, 1, 1, 1),
                                     (1, 1, 2, 1), (1, 1, 1, 2)])
def test_conv_lb_grad_matches_reference(s, p, d, g):
    """custom-VJP parity: d/dx and d/dw equal the lax conv's grads, so
    CNN training can run through the Pallas dataflow."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 10, 4))
    wt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4 // g, 6)) * 0.2

    def f_kernel(x, w):
        return jnp.sum(conv2d_lb(x, w, stride=s, padding=p,
                                 dilation=d, groups=g) ** 2)

    def f_ref(x, w):
        return jnp.sum(conv2d_ref(x, w, stride=s, padding=p,
                                  dilation=d, groups=g) ** 2)

    gx, gw = jax.grad(f_kernel, argnums=(0, 1))(x, wt)
    rx, rw = jax.grad(f_ref, argnums=(0, 1))(x, wt)
    _allclose(gx, rx, jnp.float32)
    _allclose(gw, rw, jnp.float32)


@pytest.mark.parametrize("relu,pool,use_bias", [
    (False, 1, True),
    (True, 1, True),
    (True, 2, True),
    (True, 2, False),
    (False, 2, False),
])
def test_conv_lb_fused_epilogue_matches_unfused(relu, pool, use_bias):
    """Fused bias/relu/maxpool epilogue == the unfused lax composition
    to <= 1e-5, forward and both/all grads (acceptance criterion)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 12, 6))
    wt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 6, 8)) * 0.2
    b = (jax.random.normal(jax.random.PRNGKey(2), (8,)) * 0.1
         if use_bias else None)

    out = conv2d_lb(x, wt, b, padding=1, relu=relu, pool=pool)
    ref = conv2d_ref(x, wt, b, padding=1, relu=relu, pool=pool)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    args = (x, wt) if b is None else (x, wt, b)
    nums = tuple(range(len(args)))

    def f_kernel(*a):
        return jnp.mean(conv2d_lb(*a, padding=1, relu=relu,
                                  pool=pool) ** 2)

    def f_ref(*a):
        return jnp.mean(conv2d_ref(*a, padding=1, relu=relu,
                                   pool=pool) ** 2)

    gk = jax.grad(f_kernel, argnums=nums)(*args)
    gr = jax.grad(f_ref, argnums=nums)(*args)
    for a, c in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-5)


def test_conv_lb_fused_epilogue_grouped():
    """Per-group bias slicing composes with the fused epilogue."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 8))
    wt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 12)) * 0.2
    b = jax.random.normal(jax.random.PRNGKey(2), (12,)) * 0.1
    out = conv2d_lb(x, wt, b, padding=1, groups=2, relu=True, pool=2)
    ref = conv2d_ref(x, wt, b, padding=1, groups=2, relu=True, pool=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_conv_lb_batch_fold_invariance():
    """b_block is a pure dataflow choice: folding 1, 2 or all 4 images
    into a psum tile (and the odd-batch padded case) is bit-equivalent
    work."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 10, 10, 6))
    wt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 6, 8)) * 0.2
    ref = conv2d_ref(x, wt, padding=1)
    for bb in (1, 2, 4):
        out = conv2d_lb(x, wt, padding=1, b_block=bb, y_block=5,
                        x_block=10, ci_block=6, co_block=8)
        _allclose(out, ref, jnp.float32)
    # batch 3 with b_block 2: the wrapper pads the batch axis
    x3 = x[:3]
    out = conv2d_lb(x3, wt, padding=1, b_block=2, y_block=5,
                    x_block=10, ci_block=6, co_block=8)
    _allclose(out, conv2d_ref(x3, wt, padding=1), jnp.float32)


def test_conv_lb_fallback_matches_kernel():
    """The lax fallback path computes the same convolution."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 12, 6))
    wt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 6, 8)) * 0.2
    a = conv2d_lb(x, wt, stride=2, padding=1)
    b = conv2d_lb(x, wt, stride=2, padding=1, fallback=True)
    _allclose(a, b, jnp.float32)


def test_conv_lb_true_spatial_tiling():
    """A psum plane far larger than one spatial tile: the grid must
    sweep y/x tiles (the old kernel kept all of Ho x Wo in scratch —
    this shape exercises a 6x6-tile sweep of a 48x48 plane)."""
    from repro.kernels.conv_lb.ops import plan_conv

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 48, 48, 8))
    wt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16)) * 0.2
    out = conv2d_lb(x, wt, padding=1, y_block=8, x_block=8,
                    ci_block=8, co_block=8)
    ref = conv2d_ref(x, wt, padding=1)
    _allclose(out, ref, jnp.float32)
    plan = plan_conv(48, 48, 8, 16, 3, 3, padding=(1, 1),
                     blocks=None, vmem_budget=64 * 1024)
    ny, nx, _, _ = plan.grid
    assert ny * nx > 1                      # genuinely tiled
    blk = plan.blocks
    assert blk.y * blk.x * blk.co < 48 * 48 * 16   # psum tile << plane


def test_hbm_traffic_model_matches_eq14():
    """Kernel wrapper's traffic model == Eq. (14) with R=1."""
    from repro.core.tpu_adapter import hbm_traffic_model
    m = n = k = 1024
    blk = BlockShape(256, 256, 256)
    got = hbm_traffic_model(m, n, k, blk, dtype_bytes=2)
    nm, nn = m // blk.bm, n // blk.bn
    expected = (nn * m * k + nm * k * n + m * n) * 2
    assert got == expected
