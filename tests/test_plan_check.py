"""Static conv-plan verifier: legality pass, symbolic cross-audit, and
the planner gates that ride it (``repro.analysis.plan_check``).

The acceptance contract: every node of the benchmark's VGG16,
ResNet-20 and ConvNeXt-T graphs (forward, dgrad, wgrad) audits clean at
the paper's 1 MiB accounting budget — zero legality errors, exact
symbolic-vs-accountant traffic and bound agreement — and the planners
provably never return an illegal plan (``plan_conv`` raises instead).
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.analysis import plan_check as pc
from repro.core.layer import ConvLayer
from repro.core.lower_bound import q_dram_theorem2
from repro.core.tpu_adapter import (BlockShape, ConvBlockShape,
                                    conv_lb_block_shape, sublane_for)
from repro.kernels.conv_lb.ops import (autotune_conv_blocks, plan_conv,
                                       plan_conv_training,
                                       plan_conv_wgrad)
from repro.models.cnn import (convnext_graph, init_vgg, resnet_graph,
                              vgg_graph, vgg_layer_dims)
from repro.models.graph import graph_plan_handles

MB = 1024 * 1024


def _layer(h, w, ci, co, hk, stride=1, pad=0, batch=4):
    return ConvLayer(name="t", batch=batch, ci=ci, co=co, hi=h, wi=w,
                     hk=hk, wk=hk, stride=stride, pad=pad)


# --------------------------------------------------------------------------
# the acceptance audit: every committed graph, every pass
# --------------------------------------------------------------------------

#: the benchmark configurations: (graph builder, image side, whether
#: the mosaic audit plans training) — ConvNeXt-T's compiled path is
#: serving only (its strided s3_down has no Mosaic wgrad plan)
_CONFIGS = {
    "vgg16_224": (lambda: vgg_graph(jax.eval_shape(init_vgg,
                                                   jax.random.PRNGKey(0))),
                  224, True),
    "resnet20_32": (resnet_graph, 32, True),
    "convnext_t_224": (convnext_graph, 224, False),
}

#: plans per audit at batch 8: (1 MiB training, mosaic) — fwd + dgrad +
#: wgrad per dense node, one plan per depthwise node
_N_PLANS = {"vgg16_224": (3 * 13, 3 * 13),
            "resnet20_32": (3 * 21, 3 * 21),
            "convnext_t_224": (3 * 40 + 18, 58)}

_NODES = ([("vgg16_224", name) for name, *_ in vgg_layer_dims()]
          + [("resnet20_32", n.name) for n in resnet_graph().nodes]
          + [("convnext_t_224", n.name) for n in convnext_graph().nodes])


@pytest.fixture(scope="module")
def node_audits():
    """``{config: {node: [(entry, layer, handle, depthwise)]}}`` from two
    audits per benchmark configuration at batch 8: training plans at
    the paper's 1 MiB budget, and the plans a ``target="compiled"`` run
    executes, planned under the mosaic profile at the scoped VMEM
    limit."""
    out = {}
    for config, (build, hw, mosaic_training) in _CONFIGS.items():
        graph = build()
        depthwise = {n.name: n.depthwise for n in graph.nodes}
        per_node = {n.name: [] for n in graph.nodes}
        n_plans = []
        for kw in (dict(vmem_budget=MB, training=True),
                   dict(target=pc.TARGET_MOSAIC,
                        training=mosaic_training)):
            handles = graph_plan_handles(graph, hw, hw, batch=8, **kw)
            audit = pc.audit_handles(
                handles, batch=8, vmem_budget=kw.get("vmem_budget"),
                target=kw.get("target", pc.TARGET_INTERPRET))
            n_plans.append(audit.n_plans)
            by_layer = {layer.name: (layer, h) for layer, h in handles}
            for e in audit.entries:
                node = e.name.rpartition("/")[0]
                per_node[node].append((e, *by_layer[node],
                                       depthwise[node]))
        out[config] = per_node
        assert tuple(n_plans) == _N_PLANS[config], (config, n_plans)
    return out


@pytest.mark.parametrize("config,node", _NODES)
def test_every_node_audits_clean(node_audits, config, node):
    """Every pass of every node of the benchmark configurations is
    legal, accounts exactly (symbolic re-derivation == accountant),
    carries the symbolic bound, and moves no fewer words than a lower
    bound.  A depthwise plan's bound is the once-per-word floor, so its
    words may not beat it.  A dense forward's ``bound`` is Eq. (15)'s
    attainable form, which a plan beats where the whole reduction fits
    on chip, so its floor is Theorem 2 at the plan's footprint."""
    entries = node_audits[config][node]
    assert entries
    for e, layer, handle, depthwise in entries:
        assert e.legal, [str(d) for d in e.diagnostics]
        assert e.traffic_ok, e.name
        assert e.bound_ok, e.name
        if depthwise:
            assert e.words >= e.bound > 0, (e.name, e.words, e.bound)
        elif e.name.endswith("/fwd"):
            fwd = getattr(handle, "fwd", handle)
            floor = q_dram_theorem2(layer, fwd.footprint_elems())
            assert e.words >= floor, (e.name, e.words, floor)


def test_audit_forward_only_handles():
    audit = pc.audit_graph(resnet_graph(), 32, 32, batch=8,
                           vmem_budget=MB, training=False)
    assert audit.n_plans == 21 and audit.ok, audit.report()


# --------------------------------------------------------------------------
# legality pass: the rules actually fire on broken plans
# --------------------------------------------------------------------------

def test_detects_halo_mismatch_and_grid_break():
    plan = plan_conv(16, 16, 8, 8, 3, 3, padding=(1, 1))
    bad = dataclasses.replace(
        plan, blocks=dataclasses.replace(plan.blocks, halo_y=3))
    rules = {d.rule for d in pc.errors(pc.check_conv_plan(bad))}
    assert "conv.halo" in rules
    bad = dataclasses.replace(plan, ho_pad=plan.ho_pad + 1)
    rules = {d.rule for d in pc.errors(pc.check_conv_plan(bad))}
    assert "conv.grid" in rules


def test_detects_vmem_overflow_with_repair_hint():
    plan = plan_conv(32, 32, 64, 64, 3, 3, padding=(1, 1), batch=8)
    diags = pc.check_conv_plan(plan, batch=8, vmem_budget=1024)
    bad = pc.errors(diags)
    assert bad and bad[0].rule == "conv.vmem"
    assert bad[0].hint                      # repair hint, not just a no


def test_mosaic_rules_warn_under_interpret_error_under_mosaic():
    # the paper's 1 MiB accounting plans are deliberately not
    # MXU-legal: tiny ci blocks attain the bound but underfill lanes
    plan = plan_conv(56, 56, 128, 256, 3, 3, batch=8, padding=(1, 1),
                     vmem_budget=MB)
    interp = pc.check_conv_plan(plan, batch=8, vmem_budget=MB,
                                target=pc.TARGET_INTERPRET)
    assert not pc.errors(interp)            # accounting profile: legal
    assert any(d.rule.startswith("mosaic.") for d in interp)
    mosaic = pc.check_conv_plan(plan, batch=8, vmem_budget=MB,
                                target=pc.TARGET_MOSAIC)
    assert pc.errors(mosaic)                # compiled profile: not


def test_wgrad_rules():
    plan = plan_conv(16, 16, 32, 32, 3, 3, padding=(1, 1))
    wp = plan_conv_wgrad(plan, vmem_budget=MB)
    assert not pc.errors(pc.check_wgrad_plan(wp, vmem_budget=MB))
    bad = dataclasses.replace(wp, ci_b=wp.ci + 1)
    assert {d.rule for d in pc.errors(pc.check_wgrad_plan(bad))} \
        == {"wgrad.grid"}
    assert pc.errors(pc.check_wgrad_plan(wp, vmem_budget=64))


# --------------------------------------------------------------------------
# planner gates: illegal plans raise, never return
# --------------------------------------------------------------------------

def test_plan_conv_mosaic_target_returns_mosaic_legal_plan():
    plan = plan_conv(56, 56, 128, 256, 3, 3, batch=8, padding=(1, 1),
                     vmem_budget=64 * MB, target="mosaic")
    diags = pc.check_conv_plan(plan, batch=8, vmem_budget=64 * MB,
                               target=pc.TARGET_MOSAIC)
    assert not pc.errors(diags), pc.format_diagnostics(diags)


def test_autotune_rejections_surface_as_diagnostics():
    seed = conv_lb_block_shape(56, 56, 256, 256, 3, 3, batch=8,
                               vmem_budget=MB)
    diags = []
    autotune_conv_blocks(8, 56, 56, 256, 256, 3, 3, stride=(1, 1),
                         dilation=(1, 1), vmem_budget=MB, seed=seed,
                         diagnostics=diags)
    assert any(d.rule == "autotune.vmem" for d in diags)
    assert all(d.severity == pc.WARN for d in diags)


def test_autotune_mosaic_snaps_candidates_before_scoring():
    seed = conv_lb_block_shape(56, 56, 256, 512, 3, 3, batch=8,
                               vmem_budget=64 * MB)
    diags = []
    blk = autotune_conv_blocks(8, 56, 56, 256, 512, 3, 3,
                               stride=(1, 1), dilation=(1, 1),
                               vmem_budget=64 * MB, seed=seed,
                               target="mosaic", diagnostics=diags)
    assert blk.ci % pc.LANE == 0 or blk.ci >= 256
    assert blk.co % pc.LANE == 0 or blk.co >= 512
    assert any(d.rule == "autotune.mosaic" for d in diags)


def test_autotune_raises_when_no_legal_candidate_fits():
    seed = conv_lb_block_shape(64, 64, 512, 512, 3, 3, batch=8,
                               vmem_budget=MB)
    with pytest.raises(pc.PlanLegalityError):
        # a 128-channel lane tile alone busts a 64 KiB budget
        autotune_conv_blocks(8, 64, 64, 512, 512, 3, 3, stride=(1, 1),
                             dilation=(1, 1), vmem_budget=64 * 1024,
                             seed=seed, target="mosaic")


def test_explain_renders_geometry_and_verifier_verdict():
    plan = plan_conv(56, 56, 128, 256, 3, 3, batch=8, padding=(1, 1),
                     vmem_budget=MB)
    text = plan.explain(batch=8, vmem_budget=MB)
    assert "blocks:" in text and "grid:" in text and "vmem:" in text
    assert "verifier [interpret]:" in text


def test_graph_plan_handles_verify_gate():
    from repro.models.graph import graph_plan_handles

    handles = graph_plan_handles(resnet_graph(), 32, 32, batch=8,
                                 vmem_budget=MB, training=True,
                                 verify=True)
    assert len(handles) == 21


def test_matmul_lb_rejects_over_budget_blocks():
    from repro.kernels.matmul_lb.ops import matmul_lb

    x = jnp.zeros((4096, 4096), jnp.float32)
    with pytest.raises(pc.PlanLegalityError):
        matmul_lb(x, x, blk=BlockShape(4096, 4096, 4096))
    assert pc.errors(pc.check_matmul_block(
        BlockShape(0, 128, 128), 128, 128, 128))


# --------------------------------------------------------------------------
# S1 regression: sublane alignment keyed by the word size
# --------------------------------------------------------------------------

def test_sublane_keyed_by_dtype_with_safe_fallback():
    assert sublane_for(4) == 8
    assert sublane_for(2) == 16
    assert sublane_for(1) == 32
    # unknown word sizes take the deepest-packing (safe) tile
    assert sublane_for(3) == 32 and sublane_for(8) == 32


def test_small_budget_seed_alignment_follows_dtype():
    # the old code hardcoded SUBLANE[4]=8 for every dtype: the bf16
    # seed then streamed 8-row ci slices, not a legal Mosaic tile
    for db, sub in ((4, 8), (2, 16), (1, 32)):
        blk = conv_lb_block_shape(28, 28, 256, 512, 3, 3, batch=8,
                                  dtype_bytes=db, vmem_budget=MB)
        assert blk.ci == sub, (db, blk)


# --------------------------------------------------------------------------
# property tests: random geometries (via the hypothesis-optional shim)
# --------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(6, 36), st.integers(6, 36), st.integers(1, 96),
       st.integers(1, 96), st.sampled_from([1, 3, 5]),
       st.sampled_from([1, 2]), st.integers(1, 8))
def test_random_geometry_plans_are_legal_and_account_exactly(
        h, w, ci, co, hk, stride, batch):
    if h < hk or w < hk:
        return
    pad = hk // 2
    plan = plan_conv(h, w, ci, co, hk, hk, batch=batch,
                     stride=(stride, stride), padding=(pad, pad),
                     vmem_budget=MB)
    # legality: plan_conv would have raised; assert independently too
    diags = pc.check_conv_plan(plan, batch=batch, vmem_budget=MB)
    assert not pc.errors(diags), pc.format_diagnostics(diags)
    # symbolic cross-audit: exact agreement with the accountant
    assert pc.symbolic_conv_traffic(plan, batch) == plan.traffic(batch)
    layer = _layer(h, w, ci, co, hk, stride, pad, batch)
    assert pc.symbolic_bound_words(plan, layer) \
        == plan.bound_words(layer)


@settings(max_examples=12, deadline=None)
@given(st.integers(8, 32), st.integers(8, 96), st.integers(8, 96),
       st.sampled_from([1, 3]))
def test_random_geometry_training_plans_audit_clean(n, ci, co, hk):
    pad = hk // 2
    plan = plan_conv(n, n, ci, co, hk, hk, batch=4,
                     padding=(pad, pad), vmem_budget=MB)
    tp = plan_conv_training(plan, batch=4, vmem_budget=MB)
    layer = _layer(n, n, ci, co, hk, 1, pad)
    audit = pc.audit_handles([(layer, tp)], batch=4, vmem_budget=MB)
    assert audit.n_plans == 3 and audit.ok, audit.report()


@settings(max_examples=12, deadline=None)
@given(st.integers(8, 48), st.integers(16, 256), st.integers(16, 256))
def test_random_geometry_mosaic_plans_are_mosaic_legal(n, ci, co):
    plan = plan_conv(n, n, ci, co, 3, 3, batch=2, padding=(1, 1),
                     vmem_budget=64 * MB, target="mosaic")
    diags = pc.check_conv_plan(plan, batch=2, vmem_budget=64 * MB,
                               target=pc.TARGET_MOSAIC)
    assert not pc.errors(diags), pc.format_diagnostics(diags)
