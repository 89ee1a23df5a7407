"""Batched image-serving driver (the CNN counterpart of serve.py).

Feeds a stream of mixed-size classification requests through the
bucketed :class:`repro.serve.ImageServer` and prints the per-request
traffic ledger: bytes/image, distance to the Eq. (15) bound at the
accounting budget, and the weight-read amortization the bucketing
bought vs per-image dispatch.

  # real compute on a reduced-width stack (interpret-mode kernel):
  PYTHONPATH=src python -m repro.launch.serve_images \
      --width-mult 0.08 --image 16 --requests 6

  # paper-scale serving economics (no compute, milliseconds):
  PYTHONPATH=src python -m repro.launch.serve_images \
      --account-only --width-mult 1.0 --image 224 --requests 32

  # cross-model: a ResNet-20 stack through the same bucketed ledger
  PYTHONPATH=src python -m repro.launch.serve_images \
      --model resnet --account-only --width-mult 1.0 --image 32

  # fault-tolerant loop: deadline shedding + seeded fault injection
  PYTHONPATH=src python -m repro.launch.serve_images \
      --account-only --width-mult 1.0 --image 224 --requests 32 \
      --deadline 0.25 --fault-plan "fail@1,delay@3:0.05,service:0.02"
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.core.compile_cache import enable_compile_cache
from repro.models.cnn import init_resnet, init_vgg, resnet_graph
from repro.serve import FaultPlan, ImageServer, ServingLoop, VirtualClock


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("vgg", "resnet"), default="vgg",
                    help="serve the VGG stack or a ResNet-20 "
                         "BasicBlock stack (width-mult scales both)")
    ap.add_argument("--width-mult", type=float, default=0.08)
    ap.add_argument("--image", type=int, default=16,
                    help="square image edge")
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--buckets", type=int, nargs="+",
                    default=[1, 2, 4, 8])
    ap.add_argument("--wait-ms", type=float, default=20.0,
                    help="deadline flush budget for partial buckets")
    ap.add_argument("--budget-kib", type=int, default=1024,
                    help="on-chip accounting budget (ledger scale)")
    ap.add_argument("--target", default=None,
                    choices=("interpret", "compiled", "lax",
                             "account-only"),
                    help="execution backend: interpret (Pallas "
                         "interpreter, the default), compiled "
                         "(Mosaic kernels; needs a TPU), lax (XLA "
                         "reference), account-only (plan + ledger, "
                         "no compute)")
    ap.add_argument("--account-only", action="store_true",
                    help="deprecated alias for --target account-only")
    ap.add_argument("--no-kernel", action="store_true",
                    help="deprecated alias for --target lax")
    ap.add_argument("--deadline", type=float, default=None,
                    metavar="SECONDS",
                    help="serve through the fault-tolerant ServingLoop "
                         "with this per-request latency budget "
                         "(deadline shedding + retry/backoff + "
                         "circuit-breaker degradation)")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="inject a deterministic fault schedule, e.g. "
                         "'fail@1,delay@3:0.05,service:0.02' or "
                         "'random:7' (implies the ServingLoop; "
                         "account-only runs use a virtual clock so "
                         "delays cost no wall time)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Perfetto/Chrome trace JSON (+ JSONL "
                         "event log at PATH.jsonl); under a virtual "
                         "clock the trace is bit-deterministic per "
                         "seed")
    args = ap.parse_args()
    enable_compile_cache()

    key = jax.random.PRNGKey(args.seed)
    if args.model == "resnet":
        graph = resnet_graph(width_mult=args.width_mult)
        params = init_resnet(key, graph, n_classes=args.classes)
    else:
        graph = None
        params = init_vgg(key, n_classes=args.classes,
                          width_mult=args.width_mult)
    target = args.target or ("account-only" if args.account_only
                             else "lax" if args.no_kernel
                             else "interpret")
    account_only = target == "account-only"
    fault_tolerant = (args.deadline is not None
                      or args.fault_plan is not None)
    # account-only fault-tolerant runs ride a virtual clock so
    # injected delays and backoff waits are free; compute runs keep
    # real time (the pipeline cost is the point)
    clock = VirtualClock() if fault_tolerant and account_only \
        else None
    tracer = None
    if args.trace:
        from repro.obs import Tracer

        # a virtual-clock run gets a virtual-clock trace: replaying
        # the same seed/schedule exports byte-identical files
        tracer = Tracer(**({"clock": clock} if clock else {}))
    server = ImageServer(params, args.image, args.image, graph=graph,
                         buckets=args.buckets,
                         wait_budget=args.wait_ms / 1e3,
                         account_budget=args.budget_kib * 1024,
                         target=target,
                         tracer=tracer,
                         **({"clock": clock} if clock else {}))
    loop = None
    if fault_tolerant:
        plan = FaultPlan.parse(args.fault_plan) if args.fault_plan \
            else None
        loop = ServingLoop(server,
                           deadline_s=args.deadline,
                           fault_plan=plan, seed=args.seed)

    max_req = max(1, min(4, max(args.buckets)))
    t0 = time.time()
    results = []
    for rid in range(args.requests):
        k = jax.random.fold_in(key, 1000 + rid)
        n = 1 + int(jax.random.randint(k, (), 0, max_req))
        imgs = None if account_only else jax.random.normal(
            k, (n, args.image, args.image, 3))
        if loop is not None:
            loop.submit(imgs, n_images=n if imgs is None else None)
            results += loop.pump()
        elif imgs is None:
            server.submit(n_images=n)
            results += server.poll()
        else:
            server.submit(imgs)
            results += server.poll()
    results += loop.run_sync() if loop is not None else server.drain()
    dt = time.time() - t0

    s = server.ledger.summary()
    print(server.ledger.format_summary())
    print(f"stats: {server.stats}")
    if loop is not None:
        print(f"loop: {loop.stats}")
    print(f"served {s['requests']} requests / {s['images']} images in "
          f"{dt:.2f}s ({s['images'] / max(dt, 1e-9):.1f} img/s)")
    if tracer is not None:
        from repro.obs import write_trace

        out = write_trace(args.trace, tracer, server.metrics)
        print(f"trace: {out} ({len(tracer.records)} records; open in "
              f"ui.perfetto.dev)")


if __name__ == "__main__":
    main()
