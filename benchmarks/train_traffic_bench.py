"""Training-step traffic benchmarks: the planned backward pass's HBM
economics at paper scale (account-only — the plan handles are analytic,
so the full VGG16/224x224 training geometry is measurable without
executing the interpret-mode kernel).

One training step moves the forward conv's words plus its two backward
convs (dgrad through the same batch-folded kernel dataflow, wgrad
through the dW-stationary schedule), and ``q_dram_training`` is the
per-step Eq. (15) sum the ratios are scored against.
"""

from __future__ import annotations

import time


def bench_train_traffic():
    """VGG16 training step at batch 8 and the paper's 1 MiB budget:
    accounted fwd+dgrad+wgrad bytes vs ``q_dram_training`` (each pass's
    Eq. (15) term at its realized plan footprint), the backward's byte
    share, and how many layers run dgrad through the planned kernel."""
    import jax

    from repro.models.cnn import init_vgg, vgg_training_step_report

    params = init_vgg(jax.random.PRNGKey(0), n_classes=10,
                      width_mult=1.0)
    t0 = time.perf_counter()
    rep = vgg_training_step_report(params, 224, 224, batch=8,
                                   vmem_budget=1 << 20)
    plan_us = (time.perf_counter() - t0) * 1e6
    rows = [
        ("train/vgg16_b8/train_vs_bound_x", plan_us,
         round(rep["train_vs_bound_x"], 3)),
        ("train/vgg16_b8/GB_per_step", None,
         round(rep["bytes_per_step"] / 1e9, 2)),
        ("train/vgg16_b8/bwd_share", None, round(rep["bwd_share"], 3)),
        ("train/vgg16_b8/dgrad_kernel_layers", None,
         rep["dgrad_kernel_layers"]),
    ]
    # inference-vs-training byte blowup at the same batch: what the
    # accountant was blind to before the backward was planned
    fwd_only = rep["bytes_per_step"] * (1.0 - rep["bwd_share"])
    rows.append(("train/vgg16_b8/step_vs_fwd_bytes_x", None,
                 round(rep["bytes_per_step"] / fwd_only, 2)))
    return rows


def bench_resnet_train_traffic():
    """Cross-model training step: ResNet-20 at batch 8 / 1 MiB through
    the graph-level planner — every layer, the stride-2 downsample
    convs included, now rides the kernel dgrad (the lhs-dilated
    compact-plane walk), and wgrad executes through the dW-stationary
    kernel; ``dgrad_kernel_frac`` gates that at 1.0 = 21/21."""
    t0 = time.perf_counter()

    from repro.models.cnn import resnet_graph
    from repro.models.graph import graph_training_step_report

    rep = graph_training_step_report(resnet_graph(), 32, 32, batch=8,
                                     vmem_budget=1 << 20)
    plan_us = (time.perf_counter() - t0) * 1e6
    return [
        ("train/resnet20_b8/resnet_train_vs_bound_x", plan_us,
         round(rep["train_vs_bound_x"], 3)),
        ("train/resnet20_b8/MB_per_step", None,
         round(rep["bytes_per_step"] / 1e6, 1)),
        ("train/resnet20_b8/bwd_share", None, round(rep["bwd_share"], 3)),
        ("train/resnet20_b8/dgrad_kernel_layers", None,
         rep["dgrad_kernel_layers"]),
        ("train/resnet20_b8/dgrad_kernel_frac", None,
         round(rep["dgrad_kernel_frac"], 3)),
    ]


def bench_wgrad_traffic_executed():
    """The dW-stationary kernel's *measured* traffic vs its Eq. (15)
    bound: execute ``wgrad_lb_call`` on early/mid/late VGG16
    geometries at the paper's 1 MiB budget and score the words the
    executing call reports (the ``kernel.wgrad`` event — realized grid
    x operand block volumes at the call site, not the symbolic plan)
    against ``q_dram_wgrad`` at the realized footprint, with a
    numerics check vs the lax wgrad."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro.core.lower_bound import q_dram_wgrad
    from repro.core.vgg import vgg16_conv_layers
    from repro.kernels.conv_lb import ops
    from repro.kernels.conv_lb.wgrad import wgrad_lb_call
    from repro.obs.tracer import Tracer

    layers = {l.name: l for l in vgg16_conv_layers(batch=1)}
    rng = np.random.default_rng(0)
    moved = bound = maxerr = 0.0
    t0 = time.perf_counter()
    for name in ("conv1_2", "conv3_2", "conv5_2"):
        l = layers[name]
        plan = ops.plan_conv(l.hi, l.wi, l.ci, l.co, l.hk, l.wk,
                             batch=1, stride=(l.stride, l.stride),
                             padding=(l.pad, l.pad),
                             vmem_budget=1 << 20)
        wplan = ops.plan_conv_wgrad(plan, vmem_budget=1 << 20)
        x = jnp.asarray(rng.standard_normal((1, l.hi, l.wi, l.ci)),
                        jnp.float32)
        dy = jnp.asarray(rng.standard_normal((1, l.ho, l.wo, l.co)),
                         jnp.float32)
        tracer = Tracer()
        with tracer.activate():
            gw = wgrad_lb_call(x, dy, wplan)[..., :l.ci, :l.co]
            gw.block_until_ready()
        ev = [r for r in tracer.records if r.name == "kernel.wgrad"]
        moved += ev[-1].attrs["words_moved"]
        bound += q_dram_wgrad(l, wplan.footprint_elems())
        _, vjp = jax.vjp(
            lambda ww: ops._lax_conv(x, ww, l.stride, l.stride,
                                     l.pad, l.pad, 1, 1, 1),
            jnp.zeros((l.hk, l.wk, l.ci, l.co), jnp.float32))
        (ref,) = vjp(dy)
        maxerr = max(maxerr, float(jnp.max(jnp.abs(gw - ref))
                                   / jnp.max(jnp.abs(ref))))
    us = (time.perf_counter() - t0) * 1e6
    return [
        ("train/wgrad_exec_vgg16/wgrad_vs_bound_x", us,
         round(moved / bound, 3)),
        ("train/wgrad_exec_vgg16/numeric_relerr", None,
         float(f"{maxerr:.2e}")),
    ]


ALL_TRAIN = [bench_train_traffic, bench_resnet_train_traffic,
             bench_wgrad_traffic_executed]
