"""Static conv-plan verifier: legality pass, symbolic cross-audit, and
the planner gates that ride it (``repro.analysis.plan_check``).

The acceptance contract: every ``vgg_graph``/``resnet_graph`` node
(forward, dgrad, wgrad) audits clean at the paper's 1 MiB accounting
budget — zero legality errors, exact symbolic-vs-accountant traffic and
bound agreement — and the planners provably never return an illegal
plan (``plan_conv`` raises instead).
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.analysis import plan_check as pc
from repro.core.layer import ConvLayer
from repro.core.tpu_adapter import (BlockShape, ConvBlockShape,
                                    conv_lb_block_shape, sublane_for)
from repro.kernels.conv_lb.ops import (autotune_conv_blocks, plan_conv,
                                       plan_conv_training,
                                       plan_conv_wgrad)
from repro.models.cnn import init_vgg, resnet_graph, vgg_graph

MB = 1024 * 1024


def _layer(h, w, ci, co, hk, stride=1, pad=0, batch=4):
    return ConvLayer(name="t", batch=batch, ci=ci, co=co, hi=h, wi=w,
                     hk=hk, wk=hk, stride=stride, pad=pad)


# --------------------------------------------------------------------------
# the acceptance audit: every committed graph, every pass
# --------------------------------------------------------------------------

def test_vgg_graph_audits_clean_at_paper_budget():
    graph = vgg_graph(init_vgg(jax.random.PRNGKey(0)))
    audit = pc.audit_graph(graph, 224, 224, batch=8, vmem_budget=MB,
                           training=True)
    assert audit.n_plans == 3 * 13            # fwd+dgrad+wgrad per conv
    assert audit.n_legal == audit.n_plans, audit.report()
    assert audit.traffic_mismatches == 0, audit.report()
    assert audit.bound_mismatches == 0, audit.report()
    assert audit.ok and audit.legal_frac == 1.0
    assert audit.report().startswith("plan audit [interpret]: 39/39")


def test_resnet_graph_audits_clean_at_paper_budget():
    audit = pc.audit_graph(resnet_graph(), 32, 32, batch=8,
                           vmem_budget=MB, training=True)
    assert audit.n_plans == 3 * 21
    assert audit.ok, audit.report()


@pytest.mark.parametrize("model", ["vgg16_224", "resnet20_32"])
def test_compiled_path_plans_audit_clean_under_mosaic(model):
    """The plans a ``target="compiled"`` run executes — fwd, dgrad and
    wgrad of every node at the scoped VMEM limit, planned under the
    mosaic profile — are all mosaic-legal (the ResNet 1x1 stride-2
    projections included) and account exactly."""
    if model == "vgg16_224":
        graph, hw = vgg_graph(init_vgg(jax.random.PRNGKey(0))), 224
    else:
        graph, hw = resnet_graph(), 32
    audit = pc.audit_graph(graph, hw, hw, batch=8, training=True,
                           target=pc.TARGET_MOSAIC)
    assert audit.ok and audit.legal_frac == 1.0, audit.report()


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
