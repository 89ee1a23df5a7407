"""Serving-path traffic benchmarks: the bucketed image server's
per-request HBM economics at paper scale (account-only mode, so the
full VGG16/224x224 geometry is measurable without running the
interpret-mode kernel)."""

from __future__ import annotations


def bench_serve_traffic():
    """16 mixed-size requests (32 images) through the bucketed server
    at the paper's 1 MiB accounting budget: distance to Eq. (15),
    weight amortization vs per-image dispatch, and the serving-horizon
    ratio (weights amortized over every image the plans served)."""
    import jax

    from repro.models.cnn import init_vgg
    from repro.serve import ImageServer

    params = init_vgg(jax.random.PRNGKey(0), n_classes=10,
                      width_mult=1.0)
    t = [0.0]
    server = ImageServer(params, 224, 224, compute=False,
                         clock=lambda: t[0], wait_budget=0.05)
    # FIFO-packs into four full 8-buckets (the steady-traffic regime)
    for n in (1, 2, 1, 4, 2, 1, 1, 4, 2, 1, 3, 2, 1, 2, 4, 1):
        server.submit(n_images=n, now=t[0])
    server.poll(now=t[0])
    server.drain(now=t[0])
    s = server.ledger.summary()
    rows = [
        ("serve/vgg16_mixed16/vs_bound_x", None,
         round(s["vs_bound_x"], 3)),
        ("serve/vgg16_mixed16/w_amortization_x", None,
         round(s["w_amortization_x"], 2)),
        ("serve/vgg16_mixed16/vs_serving_x", None,
         round(s["vs_serving_x"], 3)),
        ("serve/vgg16_mixed16/MB_per_image", None,
         round(s["bytes_per_image"] / 1e6, 1)),
        ("serve/vgg16_mixed16/dispatches", None, s["dispatches"]),
    ]

    # tail scenario: a lone odd-size request flushed on deadline — the
    # padding cost the bucket ladder charges a partial dispatch
    t2 = [0.0]
    tail = ImageServer(params, 224, 224, compute=False,
                       clock=lambda: t2[0], wait_budget=0.05)
    tail.submit(n_images=3, now=0.0)
    t2[0] = 0.1                              # past the wait budget
    tail.poll(now=t2[0])
    st = tail.ledger.summary()
    rows.append(("serve/vgg16_partial3of4/vs_bound_x", None,
                 round(st["vs_bound_x"], 3)))
    rows.append(("serve/vgg16_partial3of4/padded_images", None,
                 st["padded_images"]))
    return rows


def bench_resnet_serve_traffic():
    """Cross-model serving: a full-width ResNet-20 (CIFAR 32x32
    geometry — stride-2 downsampling, 1x1 projection shortcuts, fused
    residual joins) through the same bucketed account-only server at
    the 1 MiB budget.  The ``resnet_vs_bound_x`` family regression-
    gates the cross-model ratios like VGG's."""
    import jax

    from repro.models.cnn import init_resnet, resnet_graph
    from repro.serve import ImageServer

    graph = resnet_graph()
    params = init_resnet(jax.random.PRNGKey(0), graph, n_classes=10)
    t = [0.0]
    server = ImageServer(params, 32, 32, graph=graph, compute=False,
                         clock=lambda: t[0], wait_budget=0.05)
    for n in (1, 2, 1, 4, 2, 1, 1, 4, 2, 1, 3, 2, 1, 2, 4, 1):
        server.submit(n_images=n, now=t[0])
    server.poll(now=t[0])
    server.drain(now=t[0])
    s = server.ledger.summary()
    model = s["by_model"][graph.name]
    return [
        ("serve/resnet20_mixed16/resnet_vs_bound_x", None,
         round(model["vs_bound_x"], 3)),
        ("serve/resnet20_mixed16/w_amortization_x", None,
         round(s["w_amortization_x"], 2)),
        ("serve/resnet20_mixed16/vs_serving_x", None,
         round(s["vs_serving_x"], 3)),
        ("serve/resnet20_mixed16/MB_per_image", None,
         round(s["bytes_per_image"] / 1e6, 2)),
        ("serve/resnet20_mixed16/dispatches", None, s["dispatches"]),
    ]


def bench_serve_loop_bursty():
    """Fault-tolerant serving loop under a bursty arrival trace
    (virtual clock; a uniform 50 ms injected service time is the load
    model): steady bursts the deadline policy absorbs, plus one storm
    that overruns capacity — its tail is shed at admission instead of
    timing out silently.  Rows: shed fraction (bounded by the policy,
    lower better), goodput in requests/s over the virtual horizon
    (higher better), p99 latency as a fraction of the 0.3 s budget
    (lower better), and the served requests' vs-bound ratio (the shed
    ledger rows keep the economics honest)."""
    import jax

    from repro.models.cnn import init_vgg
    from repro.serve import FaultPlan, ImageServer, ServingLoop, VirtualClock

    params = init_vgg(jax.random.PRNGKey(0), n_classes=10,
                      width_mult=1.0)
    clock = VirtualClock()
    server = ImageServer(params, 224, 224, compute=False, clock=clock,
                         wait_budget=0.02)
    loop = ServingLoop(server, deadline_s=0.30,
                       fault_plan=FaultPlan(service_s=0.05),
                       service_estimate_s=0.05, seed=0)
    # 6 steady bursts of 16 images (two full 8-buckets each, 0.1 s of
    # service per 0.25 s gap), then a 72-image storm (9 groups =
    # 0.45 s of backlog against a 0.3 s budget: the tail must shed)
    bursts = [(t * 0.25, (4, 2, 1, 1, 4, 2, 1, 1)) for t in range(6)]
    bursts.append((6 * 0.25, (4, 4, 2, 2, 4, 1, 1, 2, 4, 2, 4, 2,
                              4, 4, 2, 2, 4, 1, 1, 2, 4, 2, 4, 2)))
    for at, sizes in bursts:
        if clock.now < at:
            clock.sleep(at - clock.now)
        for n in sizes:
            loop.submit(n_images=n)
        loop.pump()
    loop.run_sync(tick_s=0.01)
    horizon = max(clock.now, 1e-9)
    s = server.ledger.summary()
    assert loop.all_terminal()
    return [
        ("serve_loop/vgg16_bursty/serve_shed_frac", None,
         round(s["shed_frac"], 3)),
        ("serve_loop/vgg16_bursty/serve_goodput_rps", None,
         round(s["served_requests"] / horizon, 1)),
        ("serve_loop/vgg16_bursty/serve_p99_x_budget", None,
         round(s["p99_latency_s"] / 0.30, 3)),
        ("serve_loop/vgg16_bursty/vs_bound_x", None,
         round(s["vs_bound_x"], 3)),
        ("serve_loop/vgg16_bursty/dispatches", None, s["dispatches"]),
    ]


ALL_SERVE = [bench_serve_traffic, bench_resnet_serve_traffic,
             bench_serve_loop_bursty]
