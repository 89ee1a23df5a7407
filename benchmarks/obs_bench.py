"""Observability-layer benchmarks: what the tracer itself costs, and
what it buys.

Two families of rows:

* ``obs_overhead_frac`` — the diff_bench-gated cost of full tracing
  (tracer + metrics) over a *compute* serving smoke, computed
  *analytically*: (records emitted x measured per-record cost +
  registry lookups x measured per-lookup cost) / the untraced smoke's
  wall time.  A direct traced-vs-plain A/B at this scale is noise; the
  per-op costs are measured over 20k reps and are stable.  The
  account-only smoke's obs census rides along untracked — against a
  pure-accounting run (microseconds of work per request) the span tax
  is visible by construction, and that worst case is worth printing,
  but the budget is defined against serving that actually serves.

* ``achieved_gbps`` — real, synced wall-clock rows for every
  kernel-bench geometry, timed through the tracer's accounted spans
  (``conv2d_lb_timed`` / ``timed_call``), with the plan's analytic
  ``traffic_bytes`` turned into an achieved-GB/s sample.  These are
  interpret-mode numbers (not TPU performance) and are deliberately
  *not* diff_bench-gated; the point is that the bytes-vs-seconds
  attribution pipeline runs end to end.
"""

from __future__ import annotations

import time

import jax

from repro.obs import MetricsRegistry, NULL_TRACER, Tracer, timed_call

_REPS = 20000


def _span_cost_us() -> float:
    """Measured cost of one enabled span open/close (attrs included)."""
    tr = Tracer()
    t0 = time.perf_counter()
    for i in range(_REPS):
        with tr.span("bench.noop", i=i):
            pass
    return (time.perf_counter() - t0) / _REPS * 1e6


def _null_span_cost_us() -> float:
    """Cost of the disabled path — the price every untraced call pays."""
    t0 = time.perf_counter()
    for i in range(_REPS):
        with NULL_TRACER.span("bench.noop", i=i):
            pass
    return (time.perf_counter() - t0) / _REPS * 1e6


def _lookup_cost_us() -> float:
    """Cost of one registry instrument lookup + inc (the labeled-key
    construction dominates; the hot path in serve goes through it)."""
    reg = MetricsRegistry()
    t0 = time.perf_counter()
    for _ in range(_REPS):
        reg.counter("bench_noop", bucket=4).inc()
    return (time.perf_counter() - t0) / _REPS * 1e6


class _CountingRegistry(MetricsRegistry):
    """MetricsRegistry that counts instrument lookups (the costed op)."""

    def __init__(self):
        super().__init__()
        self.ops = 0

    def counter(self, name, **labels):
        self.ops += 1
        return super().counter(name, **labels)

    def gauge(self, name, **labels):
        self.ops += 1
        return super().gauge(name, **labels)

    def histogram(self, name, window=2048, **labels):
        self.ops += 1
        return super().histogram(name, window=window, **labels)


def _account_smoke(params, tracer=None, metrics=None) -> float:
    """Account-only bursty smoke (virtual service clock, real wall
    time measured around it); returns wall seconds."""
    from repro.serve import FaultPlan, ImageServer, ServingLoop, VirtualClock

    clock = VirtualClock()
    server = ImageServer(params, 224, 224, compute=False, clock=clock,
                         wait_budget=0.02, tracer=tracer, metrics=metrics)
    loop = ServingLoop(server, deadline_s=0.30,
                       fault_plan=FaultPlan(service_s=0.05),
                       service_estimate_s=0.05, seed=0)
    t0 = time.perf_counter()
    for burst in range(6):
        if clock.now < burst * 0.25:
            clock.sleep(burst * 0.25 - clock.now)
        for n in (4, 2, 1, 1, 4, 2, 1, 1):
            loop.submit(n_images=n)
        loop.pump()
    loop.run_sync(tick_s=0.01)
    return time.perf_counter() - t0


def _compute_smoke(params, tracer=None, metrics=None) -> float:
    """Real-compute smoke: mixed 1-/2-image requests through the
    interpret-mode kernel pipeline; returns wall seconds."""
    from repro.serve import ImageServer

    server = ImageServer(params, 16, 16, buckets=(1, 2, 4),
                         wait_budget=0.01, compute=True,
                         tracer=tracer, metrics=metrics)
    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    for rid in range(4):
        k = jax.random.fold_in(key, rid)
        server.submit(jax.random.normal(k, (1 + rid % 2, 16, 16, 3)))
        server.poll()
    server.drain()
    return time.perf_counter() - t0


def bench_obs_overhead():
    from repro.models.cnn import init_vgg

    span_us = _span_cost_us()
    null_us = _null_span_cost_us()
    lookup_us = _lookup_cost_us()

    # worst-case census: full tracing over a run that does nothing but
    # plan + account (untracked rows — microseconds of work/request)
    acct = init_vgg(jax.random.PRNGKey(0), n_classes=10,
                    width_mult=1.0)
    a_tr, a_reg = Tracer(), _CountingRegistry()
    acct_s = _account_smoke(acct, tracer=a_tr, metrics=a_reg)
    a_records = len(a_tr.records) + a_tr.dropped

    # the gated budget: same instrumentation over serving that serves
    params = init_vgg(jax.random.PRNGKey(0), n_classes=10,
                      width_mult=0.08)
    _compute_smoke(params)                   # warm jit + plan caches
    plain_s = min(_compute_smoke(params) for _ in range(2))
    tracer, metrics = Tracer(), _CountingRegistry()
    traced_s = _compute_smoke(params, tracer=tracer, metrics=metrics)
    records = len(tracer.records) + tracer.dropped
    overhead_us = records * span_us + metrics.ops * lookup_us
    frac = overhead_us / max(plain_s * 1e6, 1e-9)
    return [
        ("obs/tracer/span_us", span_us, round(span_us, 3)),
        ("obs/tracer/null_span_us", null_us, round(null_us, 4)),
        ("obs/metrics/lookup_us", lookup_us, round(lookup_us, 3)),
        ("obs/serve_vgg16_account/records", acct_s * 1e6, a_records),
        ("obs/serve_vgg16_account/metric_ops", None, a_reg.ops),
        ("obs/serve_compute/records", traced_s * 1e6, records),
        ("obs/serve_compute/metric_ops", None, metrics.ops),
        # raw (full-precision, untracked) next to the gated row, which
        # is rounded to 1e-3 so op-cost jitter can't flap the gate
        ("obs/serve_compute/obs_tax_raw", None, round(frac, 6)),
        ("obs/serve_compute/obs_overhead_frac", plain_s * 1e6,
         round(frac, 3)),
    ]


def bench_obs_kernel_gbps():
    """Every kernel-bench geometry, timed through accounted spans."""
    from repro.core.tpu_adapter import hbm_traffic_model, lb_block_shape
    from repro.kernels.conv_lb.ops import conv2d_lb_timed
    from repro.kernels.matmul_lb.ops import matmul_lb

    rows = []

    def conv_row(tag, x, w, target=None):
        tr = Tracer()
        kw = {} if target is None else {"target": target}
        conv2d_lb_timed(x, w, padding=1, tracer=tr, **kw)  # warm
        for _ in range(3):
            conv2d_lb_timed(x, w, padding=1, tracer=tr, **kw)
        sps = tr.find(name="kernel.conv2d_lb")[-3:]
        us = sum(s.attrs["us"] for s in sps) / len(sps)
        gbps = sum(s.attrs["achieved_gbps"] for s in sps) / len(sps)
        rows.append((f"obs/{tag}/achieved_gbps", us, round(gbps, 4)))

    conv_row("conv_lb_16",
             jax.random.normal(jax.random.PRNGKey(0), (1, 16, 16, 8)),
             jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16)))
    conv_row("conv_lb_48",
             jax.random.normal(jax.random.PRNGKey(0), (1, 48, 48, 8)),
             jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16)))

    x = jax.random.normal(jax.random.PRNGKey(0), (256, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 256))
    tr = Tracer()
    us = timed_call(lambda: matmul_lb(x, w).block_until_ready(),
                    tracer=tr, name="kernel.matmul_lb")
    n_bytes = hbm_traffic_model(256, 256, 256, lb_block_shape(256, 256, 256))
    rows.append(("obs/matmul_lb_256/achieved_gbps", us,
                 round(n_bytes / (us / 1e6) / 1e9, 4)))
    return rows


ALL_OBS = [bench_obs_overhead, bench_obs_kernel_gbps]
