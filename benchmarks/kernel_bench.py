"""Kernel micro-benchmarks: wall time of the interpret-mode kernels
(correctness-weighted) + the analytic HBM-traffic model per block shape
(the quantity the paper's technique optimizes — measurable without TPU).
"""

from __future__ import annotations

import jax

from repro.core.tpu_adapter import (BlockShape, arithmetic_intensity,
                                    hbm_traffic_model, lb_block_shape)
from repro.obs import timed_call


#: execution target for the walltime benches (run.py --target
#: overrides this module global before dispatching)
WALLTIME_TARGET = "interpret"


def _time_call(fn, *args, reps=3):
    # sync every rep: timing only the last rep's completion would
    # measure async dispatch for all earlier reps
    return timed_call(lambda: fn(*args).block_until_ready(),
                      reps=reps, name="bench.kernel")


def bench_matmul_traffic():
    """Eq.(14) HBM bytes for naive vs lower-bound block shapes."""
    rows = []
    for m, n, k in [(4096, 4096, 4096), (8192, 8192, 8192),
                    (32768, 5120, 5120)]:
        naive = BlockShape(bm=128, bn=128, bk=128)
        lb = lb_block_shape(m, n, k)
        t_n = hbm_traffic_model(m, n, k, naive)
        t_l = hbm_traffic_model(m, n, k, lb)
        rows.append((f"kernels/matmul_{m}x{n}x{k}/naive_GB", None,
                     round(t_n / 1e9, 2)))
        rows.append((f"kernels/matmul_{m}x{n}x{k}/lb_GB", None,
                     round(t_l / 1e9, 2)))
        rows.append((f"kernels/matmul_{m}x{n}x{k}/reduction_x", None,
                     round(t_n / t_l, 2)))
        rows.append((f"kernels/matmul_{m}x{n}x{k}/arith_intensity", None,
                     round(arithmetic_intensity(m, n, k, lb), 1)))
    return rows


def bench_conv_traffic():
    """Measured (per-BlockSpec) conv HBM traffic vs Eq. (15): the
    spatially-tiled kernel's attainment of the paper's bound, per VGG
    layer and on-chip budget — the headline quantity of the repro."""
    from repro.core.lower_bound import q_dram_practical
    from repro.core.vgg import vgg16_conv_layers
    from repro.kernels.conv_lb.ops import conv_lb_traffic

    rows = []
    for budget_kib in (256, 1024):
        total_meas = total_lb = 0.0
        for layer in vgg16_conv_layers(batch=3):
            t, plan = conv_lb_traffic(
                layer.batch, layer.hi, layer.wi, layer.ci, layer.co,
                layer.hk, layer.wk, stride=layer.stride,
                padding=layer.pad, vmem_budget=budget_kib * 1024)
            s = plan.blocks.footprint_elems(layer.hk, layer.wk)
            total_meas += t.total
            total_lb += q_dram_practical(layer, s)
        rows.append((f"kernels/conv_vgg16_S{budget_kib}K/measured_Mwords",
                     None, round(total_meas / 1e6, 1)))
        rows.append((f"kernels/conv_vgg16_S{budget_kib}K/eq15_Mwords",
                     None, round(total_lb / 1e6, 1)))
        rows.append((f"kernels/conv_vgg16_S{budget_kib}K/vs_bound_x",
                     None, round(total_meas / total_lb, 3)))
    return rows


def bench_conv_batch_fold():
    """Batch-folded u x z tiling at serving batch (B=8, 1 MiB): weight
    reads vs the per-image schedule (the batch-reuse term of Eq. 14)
    and the autotuned plan vs the closed-form seed."""
    from repro.kernels.conv_lb.ops import conv_lb_traffic, plan_conv
    from repro.core.tpu_adapter import ConvBlockShape
    from repro.core.vgg import vgg16_conv_layers

    rows = []
    budget = 1024 * 1024
    folded_w = per_image_w = tuned = closed = 0.0
    for layer in vgg16_conv_layers(batch=8):
        t, plan = conv_lb_traffic(
            layer.batch, layer.hi, layer.wi, layer.ci, layer.co,
            layer.hk, layer.wk, stride=layer.stride, padding=layer.pad,
            vmem_budget=budget)
        folded_w += t.reads_w
        tuned += t.total
        # per-image baseline: same layer, batch folded out (b_block=1)
        bk = plan.blocks
        base = plan_conv(layer.hi, layer.wi, layer.ci, layer.co,
                         layer.hk, layer.wk, batch=layer.batch,
                         stride=(layer.stride,) * 2,
                         padding=(layer.pad,) * 2,
                         blocks=ConvBlockShape(y=bk.y, x=bk.x, co=bk.co,
                                               ci=bk.ci, halo_y=bk.halo_y,
                                               halo_x=bk.halo_x, b=1),
                         vmem_budget=budget)
        tb, _ = conv_lb_traffic(
            layer.batch, layer.hi, layer.wi, layer.ci, layer.co,
            layer.hk, layer.wk, stride=layer.stride, padding=layer.pad,
            plan=base)
        per_image_w += tb.reads_w
        tc, _ = conv_lb_traffic(
            layer.batch, layer.hi, layer.wi, layer.ci, layer.co,
            layer.hk, layer.wk, stride=layer.stride, padding=layer.pad,
            vmem_budget=budget, autotune=False)
        closed += tc.total
    rows.append(("kernels/conv_vgg16_B8/folded_w_Mwords", None,
                 round(folded_w / 1e6, 1)))
    rows.append(("kernels/conv_vgg16_B8/per_image_w_Mwords", None,
                 round(per_image_w / 1e6, 1)))
    rows.append(("kernels/conv_vgg16_B8/w_reduction_x", None,
                 round(per_image_w / folded_w, 2)))
    rows.append(("kernels/conv_vgg16_B8/autotune_vs_closed_x", None,
                 round(closed / tuned, 3)))
    return rows


def bench_kernel_walltime():
    """Kernel sanity timings at ``WALLTIME_TARGET`` (interpret by
    default, or lax) — host wall clocks, not TPU performance."""
    from repro.core.exec_target import resolve_target
    from repro.kernels.conv_lb.ops import conv2d_lb
    from repro.kernels.matmul_lb.ops import matmul_lb

    tgt = resolve_target(WALLTIME_TARGET)
    tag = "interp" if tgt.name == "interpret" else tgt.name
    rows = []
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 256))
    rows.append((f"kernels/matmul_lb_256_{tag}_us",
                 _time_call(lambda a, b: matmul_lb(a, b, target=tgt),
                            x, w), 0))
    xi = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 16, 8))
    wi = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16))
    rows.append((f"kernels/conv_lb_16_{tag}_us",
                 _time_call(lambda a, b: conv2d_lb(a, b, padding=1,
                                                   target=tgt),
                            xi, wi), 0))
    xt = jax.random.normal(jax.random.PRNGKey(0), (1, 48, 48, 8))
    wt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16))
    rows.append((f"kernels/conv_lb_48_tiled_{tag}_us",
                 _time_call(lambda a, b: conv2d_lb(
                     a, b, padding=1, y_block=12, x_block=12,
                     ci_block=8, co_block=16, target=tgt), xt, wt), 0))
    return rows


ALL_KERNELS = [bench_matmul_traffic, bench_conv_traffic,
               bench_conv_batch_fold, bench_kernel_walltime]
