"""Batched CNN inference server over the paper-dataflow conv kernel.

Serves *any* conv network expressed as a
:class:`repro.models.graph.ConvGraph` (VGG remains the default: a
server built from bare VGG params reconstructs its graph): bucketed
admission (:mod:`repro.serve.bucketing`) pads arrival batches to a
plan-friendly bucket ladder, a per-(graph, bucket, geometry) plan +
jit cache makes every steady-state dispatch hit a compiled
fused-epilogue pipeline whose conv ``b_block`` tiling tracks the
bucket (the batch-reuse term of Eq. (14)/(15) is only attainable when
the kernel folds the *actual* arrival batch), and a per-request
traffic ledger (:mod:`repro.serve.ledger`) charges each request its
share of the accounted ``conv_lb_traffic`` bytes — residual joins,
strided downsampling and 1x1 projection layers included, so ResNet
stacks ride the same ledger path as VGG.

Two costs are cached independently and paid once per bucket:

  * *planning* — ``plan_conv`` is memoized on (batch, layer geometry),
    so bucket b's 13-layer plan search runs once per process;
  * *tracing*  — one ``jax.jit`` pipeline per bucket; padded dispatch
    shapes are always (bucket, H, W, C), so no retraces in steady
    state (``stats["traces"]`` counts them; watch it stay flat).

``compute=False`` runs the whole serving loop — admission, bucketing,
planning, ledger — without executing the pipelines (account-only
mode): full-scale VGG16/224x224 serving economics are measurable in
milliseconds, which is how the benchmarks and acceptance tests drive
the paper-scale geometry the interpret-mode kernel could never run.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.exec_target import ExecTarget, from_flags, resolve_target
from repro.models.cnn import vgg_graph
from repro.models.graph import (ConvGraph, graph_logits,
                                graph_plan_handles)
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracer import NULL_TRACER
from repro.serve.bucketing import (DEFAULT_BUCKETS, AdmissionQueue,
                                   ImageRequest)
from repro.serve.ledger import RequestCharge, TrafficLedger


@dataclasses.dataclass
class ServeResult:
    """One completed request: logits per image + its traffic charge."""

    rid: int
    logits: Any                # host (n_images, n_classes) or None
    charge: RequestCharge
    latency_s: float


class ImageServer:
    """Bucketed, ledger-accounted image-classification server for any
    :class:`~repro.models.graph.ConvGraph` model.

    ``params`` is the ``{"convs", "head"}`` pytree of the served graph
    (:func:`repro.models.graph.init_graph` /
    :func:`repro.models.cnn.init_vgg`); ``graph=None`` reconstructs
    the VGG graph from the param shapes — the historical default.  A
    custom ``forward`` callable ``(params, images, target) -> logits``
    overrides the generic :func:`graph_logits` pipeline (``target`` is
    the resolved :class:`~repro.core.exec_target.ExecTarget` of the
    dispatch).  Every request carries 1..max(buckets) images of the
    ``(h, w, in_ch)`` serving geometry.  ``account_budget`` is the
    on-chip scale the ledger scores distance-to-bound at (default: the
    paper's 1 MiB GBuf); execution plans use the kernel's own VMEM
    default regardless.

    ``target`` is the server's execution ceiling (default
    ``INTERPRET``, the historical ``use_kernel=True``); per-dispatch
    overrides clamp *downward* against it
    (:meth:`ExecTarget.clamp`) — a lax-only or account-only server can
    never be upgraded by a caller or by the circuit breaker.  The
    legacy ``use_kernel=``/``compute=`` booleans remain as deprecated
    spellings and are ignored when ``target`` is given.
    """

    def __init__(self, params, h: int, w: int, in_ch: int = 3, *,
                 graph: ConvGraph | None = None,
                 forward=None,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 wait_budget: float = 0.02,
                 account_budget: int = 1 << 20,
                 dtype=jnp.float32,
                 target: ExecTarget | str | None = None,
                 use_kernel: bool = True,
                 compute: bool = True,
                 keep_results: int = 1024,
                 clock=time.monotonic,
                 tracer=None,
                 metrics: MetricsRegistry | None = None):
        self.params = params
        if graph is None and forward is not None:
            # a custom forward with no graph would have the ledger
            # charging a VGG graph fabricated from non-VGG params —
            # silently wrong accounting for every dispatch
            raise ValueError("a custom forward= needs an explicit "
                             "graph= (the ledger charges plan handles "
                             "walked from the graph, and only bare VGG "
                             "params can reconstruct one)")
        self.graph = vgg_graph(params) if graph is None else graph
        self._forward = forward
        self.h, self.w, self.in_ch = int(h), int(w), int(in_ch)
        if target is not None:
            self.target = resolve_target(target)
        else:
            self.target = from_flags(use_kernel=bool(use_kernel),
                                     compute=bool(compute))
        self.dtype = jnp.dtype(dtype)
        self.account_budget = int(account_budget)
        self._clock = clock
        # observability is opt-in and injectable: the default tracer
        # is the shared no-op (zero-cost), the registry is per-server
        # (process-local, hermetic across tests); both are shared with
        # the ledger and any ServingLoop mounted on this server
        self.tracer = NULL_TRACER if tracer is None else tracer
        self.metrics = MetricsRegistry() if metrics is None else metrics
        self.queue = AdmissionQueue(buckets, wait_budget)
        self.ledger = TrafficLedger(vmem_budget=account_budget,
                                    dtype_bytes=self.dtype.itemsize,
                                    metrics=self.metrics)
        self._handles: dict[tuple, list] = {}
        self._pipelines: dict[int, Any] = {}
        # bounded lookup of recent results (insertion-ordered dict,
        # oldest evicted past keep_results): dispatch return values are
        # the durable hand-off, this is a convenience window — a
        # long-serving process must not pin every logits array alive
        self.keep_results = int(keep_results)
        self.results: dict[int, ServeResult] = {}
        self._counters = {"dispatches": 0, "traces": 0,
                          "pipeline_hits": 0, "plan_hits": 0,
                          "host_copies": 0, "results_evicted": 0}
        self._next_rid = 0

    @property
    def use_kernel(self) -> bool:
        """Deprecated boolean view of :attr:`target` (kernel vs lax)."""
        return self.target.kernel

    @property
    def compute(self) -> bool:
        """Deprecated boolean view of :attr:`target` (account-only)."""
        return self.target.compute

    @property
    def stats(self) -> dict:
        """Counters plus live health gauges: ``queue_depth`` /
        ``oldest_wait_s`` expose how far behind admission is *right
        now* (the serving loop's shed policy projects from these),
        ``results_evicted`` counts results aged out of the bounded
        lookup window."""
        return {**self._counters,
                "queue_depth": self.queue.depth,
                "oldest_wait_s": self.queue.oldest_wait(self._clock())}

    # -- request intake ----------------------------------------------------

    def submit(self, images=None, *, n_images: int | None = None,
               now: float | None = None) -> int:
        """Enqueue one request; returns its rid.

        ``images``: (n, H, W, C) or (H, W, C); account-only servers may
        pass ``n_images`` alone."""
        now = self._clock() if now is None else now
        if images is None:
            if self.compute:
                raise ValueError("compute servers need image payloads")
            n = 1 if n_images is None else int(n_images)
        else:
            # the host-to-device copy, on the submitting thread
            with self.tracer.span("serve.h2d") as h2d:
                images = jnp.asarray(images, self.dtype)
            if images.ndim == 3:
                images = images[None]
            h2d.set(n_images=int(images.shape[0]))
            if images.shape[1:] != (self.h, self.w, self.in_ch):
                raise ValueError(f"expected (*, {self.h}, {self.w}, "
                                 f"{self.in_ch}) images, got "
                                 f"{images.shape}")
            n = int(images.shape[0])
            if n_images is not None and n_images != n:
                raise ValueError("n_images disagrees with payload")
        rid = self.reserve_rid()
        self.queue.submit(ImageRequest(rid=rid, n_images=n, arrival=now,
                                       images=images))
        self.metrics.counter("serve_admitted").inc()
        self.metrics.gauge("serve_queue_depth").set(self.queue.depth)
        return rid

    def reserve_rid(self) -> int:
        """Allocate the next request id without enqueueing anything —
        the serving loop uses this for requests it sheds at admission
        (they get a terminal state and a ledger row, never a queue
        slot), keeping one rid space across admitted and shed work."""
        rid = self._next_rid
        self._next_rid += 1
        return rid

    # -- bucket caches -----------------------------------------------------

    def plan_handles(self, bucket: int):
        """The (ConvLayer, ConvPlan) accounting handles for a bucket —
        planned once, then served from the cache.

        The cache key is the full plan identity — (graph, bucket,
        image geometry, word size) — not the bucket alone, so a server
        whose serving geometry is re-pointed (or a future
        multi-geometry server) can never silently reuse plans for the
        wrong image size; every distinct geometry pays exactly one
        planning pass and keeps its handles warm.

        ``verify=True`` on the insert path: every plan set is run
        through the static verifier before it enters the cache, so an
        unexecutable (or mis-accounted) plan is a raised
        ``PlanLegalityError`` at warm-up, never a served charge."""
        key = (self.graph, int(bucket), self.h, self.w, self.in_ch,
               self.dtype.itemsize)
        if key not in self._handles:
            with self.tracer.span("plan.handles", bucket=int(bucket),
                                  model=self.graph.name,
                                  plan_key=f"{self.graph.name}/b{bucket}"
                                           f"/{self.h}x{self.w}"):
                self._handles[key] = graph_plan_handles(
                    self.graph, self.h, self.w, batch=bucket,
                    in_ch=self.in_ch, dtype_bytes=self.dtype.itemsize,
                    vmem_budget=self.account_budget, verify=True)
            self.metrics.counter("plan_cache_miss").inc()
        else:
            self._counters["plan_hits"] += 1
            self.metrics.counter("plan_cache_hit").inc()
        return self._handles[key]

    def pipeline(self, bucket: int, target: ExecTarget | str | None = None):
        """The compiled (bucket, H, W, C) -> logits pipeline.

        ``target`` clamps (never upgrades) against the server's — the
        circuit breaker's kernel -> lax degradation dispatches through
        a separately cached lax pipeline instead of retracing the
        kernel one; the cache key carries the resolved target name."""
        tgt = self.target.clamp(target)
        key = (bucket, tgt.name)
        if key in self._pipelines:
            self._counters["pipeline_hits"] += 1
            return self._pipelines[key]

        def fwd(params, imgs):
            self._counters["traces"] += 1    # bumped at trace time only
            if self._forward is not None:
                return self._forward(params, imgs, tgt)
            return graph_logits(self.graph, params, imgs, target=tgt)

        self._pipelines[key] = jax.jit(fwd)
        return self._pipelines[key]

    def warm(self, buckets: Sequence[int] | None = None) -> None:
        """Pre-plan (and pre-trace, when computing) the bucket ladder
        so first-arrival latency doesn't eat the compile."""
        for b in buckets or self.queue.buckets:
            self.plan_handles(b)
            if self.compute:
                zeros = jnp.zeros((b, self.h, self.w, self.in_ch),
                                  self.dtype)
                jax.block_until_ready(self.pipeline(b)(self.params,
                                                       zeros))

    # -- dispatch ----------------------------------------------------------

    def _execute(self, group: list[ImageRequest], bucket: int, *,
                 target: ExecTarget | str | None = None):
        """Run the compute half of a dispatch (no shared-state
        bookkeeping beyond cache counters): the serving loop calls
        this off-lock so bucket N+1 admission overlaps bucket N's
        pipeline.  ``target`` clamps *downward* against the server's
        (:meth:`ExecTarget.clamp`, the one negotiation) — a lax-only
        or account-only server never upgrades; an ``ACCOUNT_ONLY``
        resolution skips execution entirely.

        Returns the whole ``(bucket, classes)`` logits as one host
        array, padding rows included: no device op runs between the
        pipeline and the answers, so a group of any composition
        compiles nothing after its pipeline."""
        tgt = self.target.clamp(target)
        if not tgt.compute:
            return None
        tr = self.tracer
        n_images = sum(r.n_images for r in group)
        pad = bucket - n_images
        # eager device ops, dispatched one by one from the host
        with tr.span("serve.assemble", bucket=int(bucket),
                     n_images=n_images):
            payload = jnp.concatenate([r.images for r in group], axis=0)
            if pad:
                payload = jnp.pad(payload,
                                  ((0, pad), (0, 0), (0, 0), (0, 0)))
        # the dispatch's accounted bytes (same handles the ledger
        # charges) ride on the span next to the measured seconds —
        # one span, both halves of the achieved-GB/s ratio
        n_bytes = None
        if tr.active:
            n_bytes = sum(p.traffic(bucket).total
                          for _, p in self.plan_handles(bucket)) \
                * self.dtype.itemsize
        with tr.span("serve.execute", bucket=int(bucket),
                     mode=tgt.name,
                     n_images=n_images,
                     traffic_bytes=n_bytes) as sp:
            t0 = tr.now()
            out = jax.block_until_ready(
                self.pipeline(bucket, tgt)(self.params, payload))
            dt = tr.now() - t0
            sp.set(us=dt * 1e6,
                   achieved_gbps=(n_bytes / dt / 1e9)
                   if (n_bytes and dt > 0) else None)
        # one device-to-host copy a dispatch, of the array as it is
        with tr.span("serve.fetch", bucket=int(bucket),
                     bytes=int(out.nbytes)):
            host = np.asarray(out)
        self.metrics.counter("serve_logits_d2h_bytes").inc(host.nbytes)
        return host

    def _complete(self, group: list[ImageRequest], bucket: int, logits,
                  now: float) -> list[ServeResult]:
        """Bookkeeping half of a dispatch: stamp completion, charge
        the ledger, publish results into the bounded window.  Each
        request's logits are a host slice of ``logits`` (what
        :meth:`_execute` returned), so this holds no device call."""
        with self.tracer.span("serve.complete", bucket=int(bucket)):
            # virtual clocks (tests) may stand still or even be skewed
            # backwards mid-flight; a completion never predates the
            # dispatch call or any member's arrival (latencies >= 0)
            done = max(self._clock(), now, *(r.arrival for r in group))
            for r in group:
                r.done = done
            handles = self.plan_handles(bucket)
            entries = [(r.rid, r.n_images) for r in group]
            charges = self.ledger.charge_batch(
                entries, handles, bucket=bucket,
                latencies={r.rid: r.latency for r in group},
                model=self.graph.name)
            self._counters["dispatches"] += 1
            if logits is not None:
                self._counters["host_copies"] += 1
            results = []
            off = 0
            for r, charge in zip(group, charges):
                sl = (None if logits is None
                      else logits[off:off + r.n_images])
                off += r.n_images
                res = ServeResult(rid=r.rid, logits=sl, charge=charge,
                                  latency_s=r.latency)
                self.results[r.rid] = res
                results.append(res)
            # evict oldest-first, but never a result this dispatch just
            # returned: with keep_results smaller than the group, naive
            # tail-trimming would drop results the caller is handed
            current = {r.rid for r in group}
            for rid in list(self.results):
                if len(self.results) <= self.keep_results:
                    break
                if rid in current:
                    continue
                del self.results[rid]
                self._counters["results_evicted"] += 1
            return results

    def _dispatch(self, group: list[ImageRequest], bucket: int,
                  now: float) -> list[ServeResult]:
        logits = self._execute(group, bucket)
        return self._complete(group, bucket, logits, now)

    def poll(self, now: float | None = None) -> list[ServeResult]:
        """Dispatch every ready group (full buckets immediately,
        partial ones past the wait budget)."""
        now = self._clock() if now is None else now
        out = []
        while (ready := self.queue.pop_ready(now)) is not None:
            out.extend(self._dispatch(*ready, now=now))
        return out

    def drain(self, now: float | None = None) -> list[ServeResult]:
        """Flush the queue to empty regardless of deadlines (the
        queue's ``drain`` loops ``flush`` until ``None`` — one
        ``flush()`` pops a single group and would drop the rest)."""
        now = self._clock() if now is None else now
        out = []
        for ready in self.queue.drain():
            out.extend(self._dispatch(*ready, now=now))
        return out
