"""End-to-end behaviour tests of the VGG conv stack: training, the
kernel path against XLA, and the fused epilogue."""

import jax
import jax.numpy as jnp
import pytest

from repro.models.cnn import init_vgg, vgg_forward, vgg_loss


def test_vgg_cnn_trains(tmp_path):
    """The paper's own workload family: a reduced-width VGG learns a
    separable synthetic image task."""
    key = jax.random.PRNGKey(0)
    params = init_vgg(key, n_classes=4, width_mult=0.1)
    imgs = jax.random.normal(key, (16, 32, 32, 3))
    labels = jnp.arange(16) % 4
    # class-dependent mean shift makes the task learnable
    imgs = imgs + labels[:, None, None, None] * 0.5
    batch = {"images": imgs, "labels": labels}
    loss0 = float(vgg_loss(params, batch))

    @jax.jit
    def step(p):
        l, g = jax.value_and_grad(vgg_loss)(p, batch)
        return l, jax.tree_util.tree_map(lambda a, b: a - 0.08 * b, p, g)

    best = loss0
    for _ in range(100):
        loss, params = step(params)
        best = min(best, float(loss))
    assert best < loss0 - 0.25


def test_vgg_kernel_path_matches_xla():
    """vgg_forward(target="interpret") routes through the Pallas conv
    (bias/relu/pool fused into the kernel epilogue) and must agree
    with the unfused lax.conv path."""
    key = jax.random.PRNGKey(0)
    params = init_vgg(key, n_classes=4, width_mult=0.05)
    imgs = jax.random.normal(key, (2, 16, 16, 3))
    a = vgg_forward(params, imgs, target="lax")
    b = vgg_forward(params, imgs, target="interpret")
    assert float(jnp.max(jnp.abs(a - b))) < 1e-3


def test_vgg_kernel_path_fuses_epilogue():
    """The fused layers issue no separate bias/relu/pool HBM round
    trip: the kernel-path jaxpr contains no reduce_window (pool) and no
    conv-shaped max (relu) outside the pallas_call, while the lax path
    contains both."""
    key = jax.random.PRNGKey(0)
    params = init_vgg(key, n_classes=4, width_mult=0.05)
    imgs = jax.random.normal(key, (2, 16, 16, 3))

    def prims(target):
        jaxpr = jax.make_jaxpr(
            lambda p, x: vgg_forward(p, x, target)
        )(params, imgs)
        return str(jaxpr)

    lax_path, kernel_path = prims("lax"), prims("interpret")
    assert "reduce_window_max" in lax_path
    assert "reduce_window_max" not in kernel_path
    assert "conv_general_dilated" not in kernel_path


@pytest.mark.slow
def test_vgg_kernel_trains(tmp_path):
    """Interpret-mode VGG training straight through the fused Pallas
    path: gradients flow through the batch-folded kernel + epilogue
    and the loss actually drops.  Slow (interpret-mode grids) — run
    with `pytest -m slow`."""
    key = jax.random.PRNGKey(0)
    params = init_vgg(key, n_classes=4, width_mult=0.1)
    imgs = jax.random.normal(key, (8, 16, 16, 3))
    labels = jnp.arange(8) % 4
    imgs = imgs + labels[:, None, None, None] * 0.5
    batch = {"images": imgs, "labels": labels}
    loss0 = float(vgg_loss(params, batch, target="interpret"))

    @jax.jit
    def step(p):
        l, g = jax.value_and_grad(
            lambda q: vgg_loss(q, batch, target="interpret"))(p)
        return l, jax.tree_util.tree_map(lambda a, b: a - 0.08 * b, p, g)

    best = loss0
    for _ in range(100):
        loss, params = step(params)
        best = min(best, float(loss))
    assert best < loss0 - 0.2

