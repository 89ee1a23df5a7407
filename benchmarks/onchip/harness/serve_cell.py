"""A serving cell: `ServingLoop.run_async` over `ImageServer`, driven by
the `Plan` of the mix's kind: an open or a closed loop of requests.

Set-up makes the weights and the image pool from the seed, builds the
server, and warms every bucket and every group the mix dispatches (the
eager concatenate, pad and slices around each pipeline compile per
group of request sizes).  The window then runs `seconds`; every request
sent in it is followed to its end.  Afterwards each answer is compared
with the reference's logits of its images.
"""

from __future__ import annotations

import asyncio
import functools
import gc
import math
import time

import jax
import numpy as np

from harness import model, reference, trace
from harness import traffic as gen
from harness.parts import parts_of, program_graph
from harness.readers import TracedWindow

DRAIN_S = 60.0          # how long an answer may come after the window
TICK_S = 0.0005         # how often the driver looks at the clock
TRACE_AFTER_S = 1.0     # the traced sub-window starts this far in,
TRACE_S = 2.0           # and lasts this long (at most 1/4 and 1/2 of
                        # a shorter window)


def control_forward(cfg):
    """The configuration's reference with float8 product operands, in
    the program's place: one step below the precision the configuration
    states, that of every product's operands (PERF.md, section 2)."""
    ctl = functools.partial(parts_of(cfg).logits, cfg,
                            operands="float8_e4m3fn")
    return lambda params, images, _target: ctl(params, images)


class GcPauses:
    """Durations of the full (generation 2) garbage collections."""

    def __init__(self):
        self.ms: list[float] = []
        self._t = 0.0

    def __call__(self, phase, info):
        if info["generation"] == 2:
            if phase == "start":
                self._t = time.perf_counter()
            else:
                self.ms.append(1e3 * (time.perf_counter() - self._t))


class Driver:
    """Sends the window's requests from the event loop that runs
    `ServingLoop.run_async`; traces a sub-window when asked."""

    def __init__(self, loop, pool, rows, plan, clock, tracing):
        self.loop, self.pool, self.rows = loop, pool, rows
        self.sizes = plan.sizes
        self.clock = clock
        self.sent: list[tuple[int, float, int]] = []   # rid, due, row
        self.tracing = tracing
        self.trace_t: list[float] = []
        self.capture = None

    def send(self, due: float) -> int:
        i = len(self.sent)
        row = int(self.rows[i % len(self.rows)])
        n = int(self.sizes[i % len(self.sizes)])
        rid = self.loop.submit(self.pool[row:row + n])
        self.sent.append((rid, due, row))
        return rid

    def tick(self, t0: float) -> None:
        if not self.tracing or len(self.trace_t) == 2:
            return
        now = self.clock()
        if not self.trace_t and now >= t0 + self.tracing["after_s"]:
            self.capture.begin()
            self.trace_t.append(self.clock())
        elif self.trace_t and now >= self.trace_t[0] + self.tracing["seconds"]:
            self.trace_t.append(self.clock())
            self.capture.end()

    def done(self, rid: int) -> bool:
        return self.loop.requests[rid].terminal

    async def run(self, plan, seconds: float) -> float:
        if self.tracing:
            self.capture = trace.Capture()
            self.capture.start()
        task = asyncio.create_task(self.loop.run_async(until_idle=False))
        t0 = self.clock() + 0.01
        t_end = t0 + seconds
        if plan.offsets is None:
            await asyncio.sleep(max(0.0, t0 - self.clock()))
            waiting = [self.send(self.clock()) for _ in range(plan.clients)]
            while self.clock() < t_end:
                self.tick(t0)
                # under the loop's lock a dispatch's requests end
                # together, so each completion sends a whole bucket
                with self.loop._lock:
                    now = self.clock()
                    waiting = [self.send(now)
                               if self.done(r) and now < t_end else r
                               for r in waiting]
                await asyncio.sleep(TICK_S)
        else:
            for off in plan.offsets:
                due = t0 + off
                while (now := self.clock()) < due:
                    self.tick(t0)
                    await asyncio.sleep(min(due - now, TICK_S))
                self.send(due)
        while self.tracing and len(self.trace_t) < 2:
            self.tick(t0)
            await asyncio.sleep(TICK_S)
        while (self.clock() < t_end + DRAIN_S
               and not all(self.done(r) for r, _, _ in self.sent)):
            await asyncio.sleep(TICK_S)
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        if self.tracing:
            self.capture.stop()
        return t0


class ServeWindow:
    """One serving cell's run: `start()` is set-up, `measure()` the
    window, then `release()` and `compare()`."""

    def __init__(self, cell, seed: int, *, target, control: bool = False):
        self.cell, self.seed, self.target = cell, seed, target
        self.control = control
        self.parts = parts_of(cell.cfg)

    def start(self) -> None:
        from repro.serve import ImageServer, ServingLoop

        self.fallbacks0 = model.fallbacks()
        cfg, tr = self.cell.cfg, self.cell.traffic
        t = [time.monotonic()]
        self.params = self.parts.init_params(cfg, self.seed)
        graph = program_graph(cfg, self.params)
        self.pool = gen.image_pool(tr, cfg, self.seed)
        h, w, c = cfg["image"]
        self.server = ImageServer(
            self.params, h, w, c, graph=graph, buckets=tr["buckets"],
            forward=control_forward(cfg) if self.control else None,
            target=self.target)
        self.loop = ServingLoop(self.server)
        # a group is a number of single-image requests, or a list of
        # the request sizes it holds
        groups = [[1] * g if isinstance(g, int) else g
                  for g in tr["warm_groups"]]
        largest = max(groups, key=sum)
        t.append(time.monotonic())
        self.server.warm(sorted({self.server.queue.bucket_for(sum(g))
                                 for g in groups}))
        t.append(time.monotonic())
        # every group once, to compile what surrounds the pipeline
        # (never shed: a compile may outlast the deadline), then the
        # largest to settle the loop's service-time estimate
        for g in groups + [largest] * 16:
            for n in g:
                self.loop.submit(self.pool[:n], deadline_s=1e9)
            self.loop.run_sync()
        t.append(time.monotonic())
        self.setup_parts = dict(zip(
            ("weights_pool_server_s", "warm_buckets_s", "warm_groups_s"),
            np.diff(t).tolist()))

    def measure(self, seconds: float, trace_window: bool) -> dict:
        tr = self.cell.traffic
        plan = self.cell.kind.plan(tr, seconds, self.seed)
        rows = gen.request_rows(tr, 4096, int(plan.sizes.max()), self.seed)
        tracing = ({"after_s": min(TRACE_AFTER_S, seconds / 4),
                    "seconds": min(TRACE_S, seconds / 2)}
                   if trace_window else None)
        drv = Driver(self.loop, self.pool, rows, plan, self.server._clock,
                     tracing)
        pauses = GcPauses()
        gc.callbacks.append(pauses)
        t0 = asyncio.run(drv.run(plan, seconds))
        gc.callbacks.remove(pauses)
        t_end = t0 + seconds
        reqs = [(self.loop.requests[rid], due, row)
                for rid, due, row in drv.sent]
        lat = [t.terminal_at - due if t.result is not None else None
               for t, due, _ in reqs]
        served = sum(t.n_images for t, _, _ in reqs
                     if t.result is not None and t.terminal_at <= t_end)
        self.answered = [(t.result.logits, row) for t, _, row in reqs
                         if t.result is not None]
        out = {"attempted": len(reqs),
               "failed": sum(1 for t, _, _ in reqs if t.result is None),
               "values": {"p95_ms": 1e3 * gen.percentile(lat, 95),
                          "served_img_per_s": served / seconds},
               "notes": {**{f"p{q}_ms": 1e3 * gen.percentile(lat, q)
                            for q in (50, 90, 99, 99.9)},
                         "gc_full_pauses": len(pauses.ms),
                         "gc_full_pause_ms_max": max(pauses.ms, default=0.0),
                         "late_ms_p99": 1e3 * gen.percentile(
                   [max(0.0, t.arrival - due) for t, due, _ in reqs], 99),
                         **self.setup_parts}}
        if trace_window:
            lo, hi = drv.trace_t
            groups = {}
            for t, _, _ in reqs:
                if t.result is not None and lo <= t.terminal_at <= hi:
                    c = t.result.charge
                    groups[(t.terminal_at, c.bucket)] = c.group_images
            out["traced"] = TracedWindow(
                cfg=self.cell.cfg, peak={},
                summary=drv.capture.summary(), passes=("fwd",),
                batches=[b for _, b in groups],
                real_images=sum(groups.values()),
                flops_per_image=self.parts.forward_flops(self.cell.cfg))
        return out

    def health(self) -> dict:
        return {"fallbacks": model.fallbacks() - self.fallbacks0,
                "degraded": self.server.ledger.summary()[
                    "degraded_dispatches"],
                "account_only": sum(1 for z, _ in self.answered if z is None)}

    def release(self) -> None:
        got = [(z, row) for z, row in self.answered if z is not None]
        self.got = (np.concatenate(jax.device_get([z for z, _ in got]))
                    if got else np.zeros((0, self.cell.cfg["classes"])))
        # the pool index of each answered image
        self.rows = np.asarray([row + i for z, row in got
                                for i in range(z.shape[0])], dtype=np.int64)
        del self.server, self.loop, self.answered

    def compare(self) -> dict:
        """Widest gap of a served logit below or above the reference's,
        over every image of every answered request, as a share of the
        largest reference logit of its image."""
        if not len(self.rows):
            return {"logit_gap": math.inf}
        ref = np.asarray(reference.logits_in_blocks(
            self.cell.cfg, self.params, self.pool, block=32,
            logits=self.parts.logits))[self.rows]
        gap = np.abs(self.got - ref).max(axis=1) / np.abs(ref).max(axis=1)
        return {"logit_gap": float(gap.max())}
