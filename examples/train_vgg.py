"""Train a VGG stack through the paper-dataflow conv kernel and
account the full training step's HBM traffic against the bound.

Every step is three planned convs per layer — forward, dgrad (dx
through the same batch-folded Pallas kernel, via the spatially-flipped
weights at full padding) and wgrad (dW-stationary schedule, batch
folded into the reduction) — and the traffic report scores the
accounted fwd+dgrad+wgrad bytes against ``q_dram_training``, the
per-step Eq. (15) sum.  The interpret-mode kernel keeps the demo small;
``--paper-scale`` additionally prints the account-only VGG16/224x224
step economics (milliseconds — the plans are analytic).

  PYTHONPATH=src python examples/train_vgg.py --steps 6
"""

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp

from repro.core.compile_cache import enable_compile_cache
from repro.models.cnn import (init_vgg, vgg_loss,
                              vgg_training_step_report)


def report_lines(rep: dict, tag: str) -> str:
    return (f"{tag}: {rep['bytes_per_step'] / 1e6:.2f} MB/step "
            f"(bwd {rep['bwd_share'] * 100:.0f}%), "
            f"{rep['train_vs_bound_x']:.3f}x q_dram_training, "
            f"dgrad-through-kernel on {rep['dgrad_kernel_layers']}"
            f"/{rep['layers']} layers")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--image", type=int, default=8)
    ap.add_argument("--width-mult", type=float, default=0.05)
    ap.add_argument("--lr", type=float, default=0.08)
    ap.add_argument("--budget-kib", type=int, default=1024,
                    help="on-chip accounting budget for the bound")
    ap.add_argument("--target", default="interpret",
                    choices=("interpret", "compiled", "lax"),
                    help="execution backend for the training step "
                         "(compiled runs the Mosaic kernels; needs a "
                         "TPU)")
    ap.add_argument("--paper-scale", action="store_true",
                    help="also report the account-only VGG16/224x224 "
                         "training-step economics")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Perfetto/Chrome trace JSON (+ JSONL "
                         "event log at PATH.jsonl): planning spans, "
                         "per-step spans, the training report span")
    args = ap.parse_args()
    enable_compile_cache()

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer()

    key = jax.random.PRNGKey(0)
    params = init_vgg(key, n_classes=4, width_mult=args.width_mult)
    imgs = jax.random.normal(key, (args.batch, args.image,
                                   args.image, 3))
    labels = jnp.arange(args.batch) % 4
    imgs = imgs + labels[:, None, None, None] * 0.5  # learnable shift
    batch = {"images": imgs, "labels": labels}

    # scope the ambient tracer over the run so planning spans (inside
    # the memoized plan_conv) and the training-report span all land in
    # one trace; without --trace this is a no-op context
    ctx = tracer.activate() if tracer is not None \
        else contextlib.nullcontext()
    with ctx:
        # the per-step traffic is plan-derived, hence step-invariant:
        # one report covers every step of the run
        rep = vgg_training_step_report(params, args.image, args.image,
                                       batch=args.batch,
                                       vmem_budget=args.budget_kib
                                       * 1024)
        print(report_lines(rep, "per-step traffic"))

        @jax.jit
        def step(p):
            loss, g = jax.value_and_grad(
                lambda q: vgg_loss(q, batch, args.target))(p)
            return loss, jax.tree_util.tree_map(
                lambda a, b: a - args.lr * b, p, g)

        t0 = time.time()
        for i in range(args.steps):
            if tracer is not None:
                with tracer.span("train.step", step=i,
                                 traffic_bytes=rep["bytes_per_step"]):
                    loss, params = step(params)
                    jax.block_until_ready(loss)
            else:
                loss, params = step(params)
            print(f"step {i}: loss {float(loss):.4f}  "
                  f"[{rep['bytes_per_step'] / 1e6:.2f} MB accounted, "
                  f"{rep['train_vs_bound_x']:.3f}x bound]")
        print(f"{args.steps} steps in {time.time() - t0:.2f}s "
              f"({args.target}-target kernel fwd + planned dgrad)")

        if args.paper_scale:
            big = init_vgg(key, n_classes=10, width_mult=1.0)
            rep224 = vgg_training_step_report(big, 224, 224, batch=8,
                                              vmem_budget=1 << 20)
            print(report_lines(rep224,
                               "VGG16/224 @ 1 MiB (account-only)"))

    if tracer is not None:
        from repro.obs import write_trace
        out = write_trace(args.trace, tracer)
        print(f"trace: {out} ({len(tracer.records)} records; open in "
              f"ui.perfetto.dev)")


if __name__ == "__main__":
    main()
