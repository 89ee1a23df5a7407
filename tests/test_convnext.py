"""ConvNeXt through the conv graph: the depthwise ``conv_dw`` kernel,
the LayerNorm / GELU / layer-scale epilogue, the patch stem, the head
norm, and their plans, against the plain reference
``tests/convnext_reference.py`` on seeded weights.

Tolerances: the program and the reference both compute in float32
(the CPU's float32 products are exact, and the reference asks for
HIGHEST precision), so they differ by summation order and the float32
erf approximation alone, ~1e-6 of the largest logit; 1e-4 leaves that
two orders of room while one block's LayerNorm left out moves the
logits by far more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import convnext_reference
from repro.analysis.plan_check import audit_graph
from repro.core.layer import ConvLayer
from repro.core.lower_bound import q_dram_depthwise, q_dram_ideal
from repro.kernels.conv_lb.depthwise import plan_conv_dw
from repro.kernels.conv_lb.ops import (conv2d_lb, exec_fallback_counts,
                                       reset_fallback_counts)
from repro.models.cnn import convnext_graph, init_convnext
from repro.models.graph import (ConvGraph, ConvNode, graph_forward,
                                graph_logits, graph_plan_handles)

TINY = dict(depths=(1, 1, 2, 1), widths=(8, 16, 16, 32))
TOL = 1e-4


def drawn_params(key, graph, n_classes):
    """Seeded weights under which every branch carries weight: layer
    scales, norm affines and biases drawn away from their published
    initial values (a layer scale of 1e-6 would hide each block)."""
    params = init_convnext(key, graph, n_classes=n_classes)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
    out = []
    for k, (path, leaf) in zip(keys, leaves):
        name = jax.tree_util.keystr(path[-1:])
        noise = jax.random.normal(k, leaf.shape)
        if "scale" in name:
            leaf = 0.5 + 0.5 * jax.random.uniform(k, leaf.shape)
        elif "norm_w" in name:
            leaf = 1.0 + 0.2 * noise
        elif "norm_b" in name or name.endswith("['b']") \
                or "head_b" in name:
            leaf = 0.1 * noise
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module")
def tiny():
    graph = convnext_graph(**TINY, name="convnext_tiny")
    params = drawn_params(jax.random.PRNGKey(17), graph, 10)
    images = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32, 3))
    ref = convnext_reference.logits(params, images, TINY["depths"])
    return graph, params, images, ref


def gap(a, ref):
    return float(jnp.max(jnp.abs(a - ref)) / jnp.max(jnp.abs(ref)))


@pytest.mark.parametrize("target", ["interpret", "lax"])
def test_tiny_convnext_matches_the_reference(tiny, target):
    graph, params, images, ref = tiny
    reset_fallback_counts()
    got = graph_logits(graph, params, images, target=target)
    assert got.shape == (2, 10)
    assert gap(got, ref) < TOL
    assert exec_fallback_counts() == {}


def test_the_reference_without_a_block_norm_departs(tiny):
    """One LayerNorm fewer in the reference must fail the comparison
    the program passes, or the tolerance would not see a missing
    norm."""
    graph, params, images, ref = tiny
    got = graph_logits(graph, params, images, target="interpret")
    for block in ("s1b0", "s3b1"):
        wrong = convnext_reference.logits(params, images, TINY["depths"],
                                          skip_norm=block)
        assert gap(got, wrong) > 100 * TOL, block


def test_the_published_layer_scale_hides_every_block():
    """Why the weights are drawn: at gamma = 1e-6 each block's branch
    vanishes, and the logits barely see a depthwise conv."""
    graph = convnext_graph(**TINY)
    params = init_convnext(jax.random.PRNGKey(5), graph, n_classes=10)
    images = jax.random.normal(jax.random.PRNGKey(6), (2, 32, 32, 3))
    ref = convnext_reference.logits(params, images, TINY["depths"])
    wrong = convnext_reference.logits(params, images, TINY["depths"],
                                      skip_norm="s1b0")
    assert gap(wrong, ref) < 1e-4


def test_convnext_t_has_the_published_structure():
    g = convnext_graph()
    assert len(g.nodes) == 58
    assert sum(n.depthwise for n in g.nodes) == 18
    assert sum(n.gelu for n in g.nodes) == 18
    assert sum(n.scale for n in g.nodes) == 18
    # stem, every depthwise conv, and the three pw2s that feed a
    # downsampling conv carry a LayerNorm
    assert [n.name for n in g.nodes if n.norm and not n.depthwise] == [
        "stem", "s1b2_pw2", "s2b2_pw2", "s3b8_pw2"]
    params = jax.eval_shape(lambda: init_convnext(jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(params))
    assert n == 28_589_128          # timm's convnext_tiny, 28.59 M


@pytest.mark.parametrize("c,h,w,k", [(8, 9, 11, 7), (128, 7, 7, 7),
                                     (136, 5, 9, 3), (16, 14, 14, 3)])
@pytest.mark.parametrize("epilogue", ["bias", "norm", "gelu_scale"])
def test_conv_dw_matches_lax(c, h, w, k, epilogue):
    """conv_dw against lax.conv_general_dilated(feature_group_count=C):
    widths that are and are not multiples of 128, odd planes, k = 7 and
    k = 3, each epilogue."""
    keys = jax.random.split(jax.random.PRNGKey(c * h + k), 6)
    x = jax.random.normal(keys[0], (2, h, w, c))
    wt = jax.random.normal(keys[1], (k, k, 1, c)) / k
    b = jax.random.normal(keys[2], (c,))
    kw = {"bias": {}, "gelu_scale": dict(gelu=True, scale=b * 0.5),
          "norm": dict(norm=jnp.stack([1 + 0.1 * b, 0.1 * b[::-1]]))}[
        epilogue]
    reset_fallback_counts()
    got = conv2d_lb(x, wt, b, groups=c, padding=k // 2,
                    target="interpret", **kw)
    assert exec_fallback_counts() == {}
    want = jax.lax.conv_general_dilated(
        x, wt, (1, 1), [(k // 2, k // 2)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c, precision=jax.lax.Precision.HIGHEST) + b
    if epilogue == "gelu_scale":
        want = jax.nn.gelu(want, approximate=False) * kw["scale"]
    if epilogue == "norm":
        want = convnext_reference.layer_norm(want, *kw["norm"])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def _pallas_calls(jaxpr) -> list:
    """Every pallas_call equation of a jaxpr, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _pallas_calls(inner)
    return found


def test_convnext_t_forward_is_one_kernel_per_conv_node():
    """The full-width ConvNeXt-T forward at bucket 8 on the compiled
    path: one pallas_call per conv node (18 of them conv_dw), no
    per-channel loop, no fallback."""
    g = convnext_graph()
    params = jax.eval_shape(lambda: init_convnext(jax.random.PRNGKey(0)))
    images = jax.ShapeDtypeStruct((8, 224, 224, 3), jnp.float32)
    reset_fallback_counts()
    jaxpr = jax.jit(lambda p, x: graph_logits(g, p, x, target="compiled")
                    ).trace(params, images).jaxpr
    calls = _pallas_calls(jaxpr.jaxpr)
    assert exec_fallback_counts() == {}
    assert len(calls) == 58
    names = [e.params["name"] for e in calls]
    assert names.count("conv_dw") == 18
    assert names.count("conv_fwd") == 40


@pytest.mark.parametrize("batch", [1, 2, 4, 8])
def test_convnext_t_plans_one_depthwise_handle_within_the_bound(batch):
    """graph_plan_handles gives each depthwise layer one handle, the
    audit passes at the server's accounting budget and at the compiled
    kernels' own, and a depthwise plan's words are within 1.25x of the
    depthwise bound (it attains it)."""
    g = convnext_graph()
    for kw in (dict(vmem_budget=1 << 20), dict(target="mosaic")):
        handles = graph_plan_handles(g, 224, 224, batch=batch, **kw)
        assert len(handles) == 58
        dws = [(layer, p) for layer, p in handles
               if hasattr(p, "c_block")]
        assert [layer.name for layer, _ in dws] == [
            n.name for n in g.nodes if n.depthwise]
        # serving plans (the compiled forward); training's plans are
        # audited at the accounting budget
        assert audit_graph(g, 224, 224, batch=batch,
                           training="target" not in kw, **kw).ok
        if "target" in kw:
            for layer, p in dws:
                assert p.norm and not p.norm_pass
                assert p.traffic(batch).total <= 1.25 * p.bound_words(
                    layer)


def test_depthwise_bound_is_the_once_per_word_floor():
    layer = ConvLayer(name="dw", batch=8, ci=96, co=96, hi=56, wi=56,
                      hk=7, wk=7, pad=3, groups=96)
    assert layer.n_weights == 7 * 7 * 96
    assert layer.macs == 8 * 56 * 56 * 96 * 49
    assert q_dram_depthwise(layer) == q_dram_ideal(layer) == (
        8 * 56 * 56 * 96 * 2 + 49 * 96)


def test_an_unfused_norm_is_charged_as_a_pass():
    """A channel block that cannot hold every channel runs the norm as
    a pass of its own, and the plan charges its read and write."""
    fused = plan_conv_dw(56, 56, 96, 7, 7, padding=(3, 3), norm=True)
    small = plan_conv_dw(56, 56, 96, 7, 7, padding=(3, 3), norm=True,
                         vmem_budget=1 << 20)
    assert fused.norm and not fused.norm_pass
    assert small.norm_pass and small.c_block < 96
    out = 56 * 56 * 96
    assert small.traffic(1).total == fused.traffic(1).total + 2 * out


def test_an_unfused_norm_pass_matches_the_fused_one():
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    c = 16
    x = jax.random.normal(keys[0], (1, 6, 6, c))
    wt = jax.random.normal(keys[1], (3, 3, 1, c))
    norm = jnp.stack([1 + 0.1 * jax.random.normal(keys[2], (c,)),
                      jnp.zeros(c)])
    from repro.kernels.conv_lb.depthwise import conv_dw_call
    split = plan_conv_dw(6, 6, c, 3, 3, padding=(1, 1), norm=True,
                         vmem_budget=12 * 1024)
    assert split.norm_pass
    got = conv_dw_call(x, wt, split, norm=norm)
    want = conv2d_lb(x, wt, groups=c, padding=1, norm=norm,
                     target="lax")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_a_dense_node_traces_no_new_epilogue():
    """A node with gelu, scale and norm unset hands the kernel the
    operands it always did, and traces no erf, rsqrt or extra rows."""
    node = ConvNode(name="c", ci=8, co=16, residual=None)
    g = ConvGraph(name="g", nodes=(node,))
    p = [{"w": jnp.ones((3, 3, 8, 16)), "b": jnp.ones(16)}]
    x = jnp.ones((1, 8, 8, 8))
    jaxpr = jax.make_jaxpr(lambda p, x: graph_forward(
        g, p, x, target="interpret"))(p, x)
    calls = _pallas_calls(jaxpr.jaxpr)
    assert len(calls) == 1 and len(calls[0].invars) == 3
    text = str(jaxpr)
    assert "erf" not in text and "rsqrt" not in text


def test_a_norm_on_a_grouped_conv_needs_the_depthwise_geometry():
    with pytest.raises(ValueError, match="depthwise"):
        ConvGraph(name="g", nodes=(
            ConvNode(name="c", ci=8, co=8, groups=2, norm=True),))
    ConvGraph(name="g", nodes=(
        ConvNode(name="c", ci=8, co=8, groups=8, norm=True),))


def test_depthwise_fallbacks_count_under_their_own_pass(monkeypatch):
    """A depthwise layer with no Mosaic-legal plan degrades loudly to
    lax, counted under "dw"."""
    from repro.analysis.plan_check import PlanLegalityError
    from repro.kernels.conv_lb import depthwise

    def refuse(*a, **k):
        raise PlanLegalityError([])
    monkeypatch.setattr(depthwise, "plan_conv_dw", refuse)
    reset_fallback_counts()
    x = jnp.ones((1, 6, 6, 8))
    y = conv2d_lb(x, jnp.ones((3, 3, 1, 8)), groups=8, padding=1,
                  target="compiled")
    assert y.shape == (1, 6, 6, 8)
    assert exec_fallback_counts() == {"dw": 1}


def test_eager_layer_spans_cover_every_convnext_node(tiny):
    """Executed eagerly under a tracer, every node (the depthwise ones
    with their norm, the GELU and scaled 1x1 convs) gets its
    graph.layer span and an accounted kernel span, and the result is
    the traced-free forward's."""
    from repro.obs import Tracer

    graph, params, images, _ = tiny
    tr = Tracer()
    got = graph_forward(graph, params["convs"], images,
                        target="interpret", tracer=tr)
    layers = tr.find(name="graph.layer")
    assert [s.attrs["layer"] for s in layers] == [n.name
                                                  for n in graph.nodes]
    kernels = tr.find(name="kernel.conv2d_lb")
    assert len(kernels) == len(graph.nodes)
    assert all(s.attrs["traffic_bytes"] > 0 for s in kernels)
    want = graph_forward(graph, params["convs"], images,
                         target="interpret")
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
