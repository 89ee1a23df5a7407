"""Conv-graph IR: the model-agnostic walk feeding forward, training
and serving.

Covers the graph walk's geometry/validation contract (strict channel
checking with opt-in truncation), ResNet BasicBlock stacks end to end
through the kernel path (stride-2 downsampling, 1x1 projection
shortcuts, residual joins fused into the psum-resident epilogue),
grouped/strided layers through the graph-level planner, and the
per-graph Eq. (15) bound sums the acceptance criteria are scored
against (<= 1.25x at the paper's 1 MiB budget).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lower_bound import (q_dram_graph, q_dram_graph_serving,
                                    q_dram_serving, q_dram_training)
from repro.models.cnn import (init_resnet, init_vgg, resnet_graph,
                              vgg_conv_geometry, vgg_forward, vgg_graph)
from repro.models.graph import (GRAPH_INPUT, ConvGraph, ConvNode,
                                GraphStage, graph_forward, graph_logits,
                                graph_plan_handles, graph_stages,
                                graph_training_step_report, init_graph)

KEY = jax.random.PRNGKey(0)
S_1M = 1 << 20


# --------------------------------------------------------------------------
# the walk: geometry + validation
# --------------------------------------------------------------------------

def test_vgg_graph_matches_legacy_geometry():
    """The generic walk reproduces the legacy VGG geometry exactly —
    same stages, planes, pool cadence and fusion decisions."""
    params = init_vgg(KEY, n_classes=10, width_mult=0.1)
    legacy = vgg_conv_geometry(params, 32, 32)
    stages = graph_stages(vgg_graph(params), 32, 32, 3)
    assert len(stages) == len(legacy) == 13
    for st, g in zip(stages, legacy):
        assert (st.node.name, st.node.ci, st.node.co) == (g.name, g.ci,
                                                          g.co)
        assert (st.h, st.w) == (g.h, g.w)
        assert (st.pool > 1) == g.pool
        assert st.fused_pool == g.fused_pool


def test_strict_walk_raises_on_channel_mismatch():
    """Truncation is an explicit opt-in now: the graph walk errors on
    a channel mismatch unless strict=False."""
    params = init_vgg(KEY, n_classes=4, width_mult=0.05)
    g = vgg_graph(params)
    with pytest.raises(ValueError, match="strict=False"):
        graph_stages(g, 8, 8, in_ch=1)
    assert graph_stages(g, 8, 8, in_ch=1, strict=False) == []
    # the vgg_* wrappers keep the historical truncating default
    assert vgg_conv_geometry(params, 8, 8, in_ch=1) == []
    with pytest.raises(ValueError):
        vgg_conv_geometry(params, 8, 8, in_ch=1, strict=True)


def test_reduced_width_smoke_path_still_works():
    """The reduced-width stack (the tier-1 smoke config) flows through
    the strict walk untruncated and the forward still runs."""
    params = init_vgg(KEY, n_classes=4, width_mult=0.05)
    assert len(graph_stages(vgg_graph(params), 8, 8, 3)) == 13
    logits = vgg_forward(params, jnp.zeros((2, 8, 8, 3)))
    assert logits.shape == (2, 4)


def test_graph_validation_rejects_malformed():
    n = ConvNode(name="a", ci=3, co=4)
    with pytest.raises(ValueError, match="duplicate"):
        ConvGraph(name="bad", nodes=(n, n))
    with pytest.raises(ValueError, match="before"):
        ConvGraph(name="bad", nodes=(
            ConvNode(name="a", ci=3, co=4, residual="b"),
            ConvNode(name="b", ci=4, co=4)))
    with pytest.raises(ValueError, match="groups"):
        ConvGraph(name="bad", nodes=(
            ConvNode(name="a", ci=3, co=4, groups=2),))
    # residual join with mismatched planes: caught at walk time
    g = ConvGraph(name="bad_join", nodes=(
        ConvNode(name="a", ci=3, co=4),
        ConvNode(name="b", ci=4, co=4, stride=2, residual="a")))
    with pytest.raises(ValueError, match="residual"):
        graph_stages(g, 8, 8, 3)


def test_resnet_graph_topology():
    """ResNet-20: 21 conv nodes (stem + 9 blocks x 2 + 2 projections),
    stride-2 stage transitions halve the plane, projection shortcuts
    land shape-exact on the join."""
    g = resnet_graph()
    assert g.name == "resnet20" and len(g.nodes) == 21
    stages = graph_stages(g, 32, 32, 3)
    planes = {st.node.name: (st.ho, st.wo) for st in stages}
    assert planes["s1b2_b"] == (32, 32)
    assert planes["s2b0_a"] == (16, 16)      # stride-2 downsample
    assert planes["s2b0_proj"] == (16, 16)   # 1x1 projection matches
    assert planes["s3b2_b"] == (8, 8)
    joins = [st for st in stages if st.residual]
    assert len(joins) == 9                   # one join per BasicBlock
    strided = [st for st in stages if st.node.stride == 2]
    assert len(strided) == 4                 # 2 stages x (conv_a+proj)


# --------------------------------------------------------------------------
# executable forward: kernel path vs lax, grads included
# --------------------------------------------------------------------------

def _tiny_resnet():
    g = resnet_graph(blocks=(1, 1), widths=(4, 8), name="resnet-tiny")
    params = init_resnet(jax.random.PRNGKey(1), g, n_classes=3)
    return g, params


def test_resnet_forward_kernel_matches_lax():
    """BasicBlock stack (stride-2 downsample + 1x1 projection + fused
    residual joins) through graph_forward(target="interpret") matches the
    lax path, and grads of the kernel path match lax to 1e-4."""
    g, params = _tiny_resnet()
    imgs = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 8, 3))
    lk = graph_logits(g, params, imgs, target="interpret")
    ll = graph_logits(g, params, imgs, target="lax")
    assert lk.shape == (2, 3)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(ll),
                               rtol=1e-4, atol=1e-4)

    def loss(p, target):
        return (graph_logits(g, p, imgs, target=target) ** 2).sum()

    gk = jax.grad(lambda p: loss(p, "interpret"))(params)
    gl = jax.grad(lambda p: loss(p, "lax"))(params)
    flat_k, _ = jax.tree_util.tree_flatten(gk)
    flat_l, _ = jax.tree_util.tree_flatten(gl)
    for a, b in zip(flat_k, flat_l):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_residual_join_fused_into_kernel_epilogue():
    """The kernel path keeps residual joins inside the conv kernel:
    one pallas_call per conv node (no extra kernel or HBM round trip
    for the add), and the join layers' plans carry the fused-residual
    flag whose traffic accounts the streamed read."""
    g, params = _tiny_resnet()
    imgs = jnp.zeros((2, 8, 8, 3))
    jaxpr = str(jax.make_jaxpr(
        lambda x: graph_forward(g, params["convs"], x,
                                target="interpret"))(imgs))
    assert jaxpr.count("pallas_call") == len(g.nodes)
    handles = graph_plan_handles(g, 8, 8, batch=2, vmem_budget=S_1M)
    by_name = {l.name: p for l, p in handles}
    assert by_name["s1b0_b"].residual and by_name["s2b0_b"].residual
    assert not by_name["stem"].residual
    # the fused join's streamed read is accounted: per-batch traffic
    # of a residual plan exceeds its residual-free twin by >= |plane|
    import dataclasses as dc
    p = by_name["s1b0_b"]
    bare = dc.replace(p, residual=False)
    extra = p.traffic(2).total - bare.traffic(2).total
    assert extra >= 2 * p.ho * p.wo * p.co


def test_grouped_conv_through_graph():
    """Grouped nodes ride the same walk: kernel matches lax, and the
    planner exports one per-group handle per group so traffic and
    bound both scale with the group count."""
    g = ConvGraph(name="grouped", nodes=(
        ConvNode(name="in", ci=3, co=8),
        ConvNode(name="gc", ci=8, co=8, groups=2),
    ))
    params = init_graph(jax.random.PRNGKey(3), g, n_classes=3)
    imgs = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 8, 3))
    lk = graph_logits(g, params, imgs, target="interpret")
    ll = graph_logits(g, params, imgs, target="lax")
    np.testing.assert_allclose(np.asarray(lk), np.asarray(ll),
                               rtol=1e-4, atol=1e-4)
    handles = graph_plan_handles(g, 8, 8, batch=2, vmem_budget=S_1M)
    assert len(handles) == 3                 # 1 + 2 group handles
    grouped = [(l, p) for l, p in handles if l.name == "gc"]
    assert len(grouped) == 2
    assert grouped[0][0].ci == grouped[0][0].co == 4   # per-group geometry
    assert grouped[0][1] is grouped[1][1]    # same memoized plan


# --------------------------------------------------------------------------
# acceptance: graph-level traffic vs the per-graph Eq. (15) sums
# --------------------------------------------------------------------------

def test_resnet_serve_traffic_within_bound():
    """Acceptance: ResNet-20 (strided + 1x1 + residual layers) planned
    at batch 8 / 1 MiB stays <= 1.25x the per-graph Eq. (15) sum."""
    handles = graph_plan_handles(resnet_graph(), 32, 32, batch=8,
                                 vmem_budget=S_1M)
    assert len(handles) == 21
    measured = sum(p.traffic(8).total for _, p in handles)
    bound = sum(p.bound_words(l) for l, p in handles)
    assert measured <= 1.25 * bound, measured / bound
    # the pure per-layer conv sum (no residual reads) is a true floor
    conv_sum = q_dram_graph([(l, p.footprint_elems())
                             for l, p in handles])
    assert bound >= conv_sum


def test_resnet_training_step_within_bound():
    """Acceptance: the ResNet-20 training step (fwd + dgrad + wgrad,
    the stride-2 downsample convs riding the lhs-dilated kernel dgrad
    alongside the stride-1 majority) stays <= 1.25x the per-graph
    q_dram_training sum at 1 MiB."""
    rep = graph_training_step_report(resnet_graph(), 32, 32, batch=8,
                                     vmem_budget=S_1M)
    assert rep["model"] == "resnet20"
    assert rep["layers"] == 21
    assert rep["train_vs_bound_x"] <= 1.25, rep
    # every layer — strided downsamples included — rides the kernel
    assert rep["dgrad_kernel_layers"] == 21
    assert rep["dgrad_kernel_frac"] == 1.0
    assert 0.4 < rep["bwd_share"] < 0.85


def test_q_dram_graph_sums():
    """The per-graph bound helpers are plain sums over heterogeneous
    layers, with the serving form amortizing weights per layer."""
    handles = graph_plan_handles(resnet_graph(blocks=(1, 1),
                                              widths=(8, 16),
                                              name="rn-sum"),
                                 16, 16, batch=2, vmem_budget=S_1M)
    stages = [(l, p.footprint_elems()) for l, p in handles]
    assert q_dram_graph(stages) == pytest.approx(
        sum(q_dram_training(l, s, bwd=False) for l, s in stages))
    assert q_dram_graph(stages, bwd=True) > q_dram_graph(stages)
    per_img = [q_dram_graph_serving(stages, requests=n)
               for n in (1, 8, 512)]
    assert per_img == sorted(per_img, reverse=True)   # amortizes down
    assert per_img[0] == pytest.approx(
        sum(q_dram_serving(l, s, requests=1) for l, s in stages))


def test_graph_stage_walk_is_single_source_of_truth():
    """Plan handles enumerate exactly the stages graph_forward runs —
    including effective-pool and projection branches."""
    g = resnet_graph(blocks=(1, 1), widths=(4, 8), name="rn-truth")
    stages = graph_stages(g, 8, 8, 3)
    handles = graph_plan_handles(g, 8, 8, batch=2, vmem_budget=S_1M)
    assert [l.name for l, _ in handles] == [st.node.name
                                            for st in stages]
    for (layer, plan), st in zip(handles, stages):
        assert (layer.hi, layer.wi) == (st.h, st.w)
        assert layer.stride == st.node.stride
        assert plan.residual == st.residual
        assert plan.pool == (st.pool if st.fused_pool else 1)


def test_each_node_names_its_device_ops():
    """graph_forward runs every conv under its node's name scope, so
    the lowered ops of the forward and of its gradient carry it."""
    params = init_vgg(jax.random.PRNGKey(0), n_classes=4, width_mult=0.05)
    g = vgg_graph(params)
    x = jnp.zeros((1, 8, 8, 3))

    def loss(convs, x):
        return graph_forward(g, convs, x, target="lax").sum()

    text = jax.jit(jax.grad(loss)).lower(params["convs"], x).as_text(
        debug_info=True)
    for node in g.nodes:
        assert f"jvp({node.name})" in text, node.name
        assert f"transpose(jvp({node.name}))" in text, node.name
