"""Chip smoke test: the paper's main path on one TPU, at full width.

Runs in one process on one chip, with images and weights made from
``--seed``:

  * serve — VGG16 (width 1.0) on 224x224 images, then ResNet-20 on
    32x32, through ``ImageServer(target="compiled")`` behind a
    ``ServingLoop``, buckets {1, 2, 4, 8}; every request's logits are
    compared with a plain float32 ``lax`` forward of the same params
    at ``Precision.HIGHEST``;
  * train — two SGD steps of VGG16/224 at batch 8 under
    ``target="compiled"`` (the Mosaic forward, dgrad and wgrad
    kernels); the first step's gradients are compared with the
    HIGHEST-precision lax VJP (and XLA's default-precision VJP beside
    it, see ``GRAD_VS_XLA``), and both losses must be finite.

Any ``exec.fallback`` (a conv pass that left the kernel for lax), any
circuit-breaker degradation, any request completed without logits, or
any error past its tolerance fails the run.  Each phase prints one
line; the last line of a passing run is the JSON verdict
``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero before printing any result.

  python chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

# Errors are ||kernel - reference|| / ||reference|| against the
# HIGHEST-precision float32 reference.  The kernels run f32 matmuls at
# Mosaic's default MXU precision, which rounds operands to bf16 just as
# XLA's default-precision conv does: on one v5e both put VGG16/224
# logits 3.4e-3 and the first layer's weight gradient 0.24 off the
# reference, leaf for leaf within 10% of each other.  So logits meet a
# fixed bound, and each gradient leaf may be off by at most GRAD_VS_XLA
# times XLA's own default-precision error (or GRAD_FLOOR, for leaves
# XLA gets nearly exact)
LOGIT_RTOL = 1e-2
GRAD_VS_XLA = 1.5
GRAD_FLOOR = 1e-2
HIGHEST = jax.lax.Precision.HIGHEST


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def reference_logits(graph, params, images):
    """Plain f32 lax forward of a ConvGraph at HIGHEST precision: each
    node's conv, bias, residual join, ReLU and VALID max-pool, then the
    global mean pool and the linear head."""
    from repro.models.graph import GRAPH_INPUT

    tensors = {GRAPH_INPUT: images}
    prev = GRAPH_INPUT
    for node, p in zip(graph.nodes, params["convs"]):
        y = jax.lax.conv_general_dilated(
            tensors[node.src or prev], p["w"], (node.stride,) * 2,
            [(node.pad, node.pad)] * 2,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=node.groups, precision=HIGHEST)
        if node.bias:
            y = y + p["b"]
        if node.residual is not None:
            y = y + tensors[node.residual]
        if node.relu:
            y = jnp.maximum(y, 0.0)
        if node.pool > 1 and min(y.shape[1:3]) >= node.pool:
            win = (1, node.pool, node.pool, 1)
            y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, win, win,
                                      "VALID")
        tensors[node.name] = y
        prev = node.name
    return jnp.dot(tensors[prev].mean(axis=(1, 2)), params["head"],
                   precision=HIGHEST)


def rel_err(a, ref) -> float:
    return float(jnp.linalg.norm((a - ref).ravel())
                 / jnp.linalg.norm(ref.ravel()))


def peak_gib() -> float:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2 ** 30


def serve_phase(name, graph, params, hw, key, n_requests,
                target="compiled") -> None:
    from repro.kernels.conv_lb.ops import (exec_fallback_counts,
                                           reset_fallback_counts)
    from repro.models.graph import graph_logits
    from repro.serve import ImageServer, ServingLoop

    reset_fallback_counts()
    server = ImageServer(params, hw, hw, graph=graph, buckets=(1, 2, 4, 8),
                         target=target)
    loop = ServingLoop(server, deadline_s=None, max_retries=0)
    t0 = time.perf_counter()
    server.warm()                       # plans + compiles every bucket
    compile_s = time.perf_counter() - t0
    sizes = [1 + int(v) for v in
             jax.random.randint(jax.random.fold_in(key, 0), (n_requests,),
                                0, 4)]
    images = jax.random.normal(jax.random.fold_in(key, 1),
                               (sum(sizes), hw, hw, 3))
    rids, off = [], 0
    for n in sizes:
        rids.append(loop.submit(images[off:off + n]))
        off += n
    loop.run_sync()
    ref = jax.jit(lambda p, x: reference_logits(graph, p, x))(params,
                                                              images)
    xla_err = rel_err(jax.jit(lambda p, x: graph_logits(
        graph, p, x, target="lax"))(params, images), ref)
    err, off, no_logits = 0.0, 0, 0
    for rid, n in zip(rids, sizes):
        tracked = loop.requests[rid]
        if tracked.result is None:
            fail(f"{name}: request {rid} ended {tracked.state.value} "
                 f"({tracked.error or tracked.shed_reason})")
        if tracked.result.logits is None:
            no_logits += 1
        else:
            err = max(err, rel_err(tracked.result.logits,
                                   ref[off:off + n]))
        off += n
    health = server.ledger.summary()
    fallbacks = sum(exec_fallback_counts().values())
    degraded = health["degraded_dispatches"]
    print(f"serve {name}: requests={health['served_requests']} "
          f"images={sum(sizes)} fallbacks={fallbacks} "
          f"degraded_dispatches={degraded} account_only={no_logits} "
          f"logit_rel_err={err:.3e} (tol {LOGIT_RTOL:.0e}; xla "
          f"default precision {xla_err:.3e}) "
          f"compile_s={compile_s:.1f} peak_mem_gib={peak_gib():.2f}",
          flush=True)
    if health["served_requests"] != n_requests:
        fail(f"{name}: served {health['served_requests']} of "
             f"{n_requests} requests")
    if fallbacks or degraded or no_logits:
        fail(f"{name}: fallbacks={dict(exec_fallback_counts())} "
             f"degraded={degraded} account_only={no_logits}")
    if not err <= LOGIT_RTOL:
        fail(f"{name}: logits off the reference by {err:.3e}")


def train_phase(key, batch: int = 8, steps: int = 2, lr: float = 1e-4,
                width_mult: float = 1.0, hw: int = 224,
                target="compiled") -> None:
    from repro.kernels.conv_lb.ops import (exec_fallback_counts,
                                           reset_fallback_counts)
    from repro.models.cnn import init_vgg, vgg_graph, vgg_loss

    reset_fallback_counts()
    params = init_vgg(jax.random.fold_in(key, 0), n_classes=10,
                      width_mult=width_mult)
    graph = vgg_graph(params)
    data = {"images": jax.random.normal(jax.random.fold_in(key, 1),
                                        (batch, hw, hw, 3)),
            "labels": jnp.arange(batch) % 10}

    def ref_loss(p):
        logp = jax.nn.log_softmax(reference_logits(graph, p,
                                                   data["images"]))
        return -jnp.take_along_axis(logp, data["labels"][:, None],
                                    axis=1).mean()

    grad_fn = jax.jit(jax.value_and_grad(
        lambda p: vgg_loss(p, data, target)))
    t0 = time.perf_counter()
    grad_fn = grad_fn.lower(params).compile()
    compile_s = time.perf_counter() - t0
    losses = []
    for step in range(steps):
        loss, grads = grad_fn(params)
        losses.append(float(loss))
        if step == 0:
            ref_l, ref_g = jax.jit(jax.value_and_grad(ref_loss))(params)
            xla_g = jax.jit(jax.grad(
                lambda p: vgg_loss(p, data, "lax")))(params)
            # per leaf: (kernel error, XLA default-precision error)
            errs = [(rel_err(g, r), rel_err(x, r)) for g, x, r in zip(
                *map(jax.tree_util.tree_leaves, (grads, xla_g, ref_g)))]
        params = jax.tree_util.tree_map(lambda a, g: a - lr * g,
                                        params, grads)
    err, xla_err = max(errs)
    worst = max(k / max(GRAD_VS_XLA * x, GRAD_FLOOR) for k, x in errs)
    fallbacks = sum(exec_fallback_counts().values())
    print(f"train vgg16/{hw} b{batch}: steps={steps} "
          f"losses={[round(v, 4) for v in losses]} "
          f"(reference {float(ref_l):.4f}) fallbacks={fallbacks} "
          f"degraded_dispatches=0 grad_rel_err={err:.3e} (xla default "
          f"precision {xla_err:.3e}; worst leaf at {worst:.2f} of its "
          f"tol max({GRAD_VS_XLA}x xla, {GRAD_FLOOR:.0e})) "
          f"compile_s={compile_s:.1f} peak_mem_gib={peak_gib():.2f}",
          flush=True)
    if fallbacks:
        fail(f"train: fallbacks={dict(exec_fallback_counts())}")
    if not all(jnp.isfinite(jnp.asarray(losses))):
        fail(f"train: non-finite loss {losses}")
    if not worst <= 1.0:
        fail(f"train: a gradient leaf is {worst:.2f}x its tolerance")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {device}")
    print(f"device: {device['kind']} x{device['count']}", flush=True)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.core.compile_cache import enable_compile_cache
    from repro.models.cnn import (init_resnet, init_vgg, resnet_graph,
                                  vgg_graph)

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    key = jax.random.PRNGKey(args.seed)
    vgg = init_vgg(jax.random.fold_in(key, 1), n_classes=10)
    serve_phase("vgg16/224", vgg_graph(vgg), vgg, 224,
                jax.random.fold_in(key, 2), n_requests=12)
    resnet = resnet_graph()
    serve_phase("resnet20/32", resnet,
                init_resnet(jax.random.fold_in(key, 3), resnet), 32,
                jax.random.fold_in(key, 4), n_requests=12)
    train_phase(jax.random.fold_in(key, 5))
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
