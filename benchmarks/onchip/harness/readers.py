"""What the per-layer metric files under `metrics/` share.

A metric file defines `read(ctx) -> float | None`.  `ctx` is a
`TracedWindow`: the trace summary of a traced run and what the run
counted in the same window.  A reader that finds nothing to read
returns None, and the metric is left out of the result line.
"""

from __future__ import annotations

import dataclasses

from harness import trace, work
from harness.parts import parts_of


@dataclasses.dataclass
class TracedWindow:
    cfg: dict
    peak: dict                 # a row of peaks.json
    summary: dict              # harness.trace summary of the window
    passes: tuple[str, ...]    # conv passes one batch runs
    batches: list[int]         # batch (bucket) of each dispatch or step
    real_images: int           # images users asked for, padding excluded
    flops_per_image: float     # required FLOPs of one such image

    @property
    def seconds(self) -> float:
        return trace.window_s(self.summary)


def conv_roofline(ctx: TracedWindow, patterns) -> float | None:
    """% of the conv passes' roofline-minimum time in the device time of
    the conv operations named by `patterns`."""
    device_s = trace.op_seconds(ctx.summary, patterns)
    if device_s <= 0 or not ctx.batches:
        return None
    pass_work = parts_of(ctx.cfg).pass_work
    least = sum(work.roofline_seconds(pass_work(ctx.cfg, b, ctx.passes),
                                      ctx.peak) for b in ctx.batches)
    return 100.0 * least / device_s


def mfu(ctx: TracedWindow) -> float | None:
    """% of the chip's peak FLOP/s that the required work of the images
    completed in the window amounts to."""
    if ctx.seconds <= 0 or not ctx.real_images:
        return None
    rate = ctx.real_images * ctx.flops_per_image / ctx.seconds
    n = len(ctx.summary["devices"]) or 1
    return 100.0 * rate / (ctx.peak["flops_per_s"] * n)


def step_mfu(ctx: TracedWindow) -> float | None:
    """% of the chip's peak FLOP/s that the required work of the images
    completed in the window amounts to over the device's busy time: the
    model step's own share while it runs, whatever the load offered."""
    busy = trace.busy_s(ctx.summary)
    if busy <= 0 or not ctx.real_images:
        return None
    n = len(ctx.summary["devices"])
    return 100.0 * ctx.real_images * ctx.flops_per_image / busy / (
        ctx.peak["flops_per_s"] * n)


def idle(ctx: TracedWindow) -> float | None:
    if ctx.seconds <= 0 or not ctx.summary["devices"]:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.summary) / ctx.seconds)


def batch_fill(ctx: TracedWindow) -> float | None:
    if not ctx.batches:
        return None
    return 100.0 * ctx.real_images / sum(ctx.batches)


def ms_per_batch(ctx: TracedWindow, patterns) -> float | None:
    """Device ms per dispatch or step in the operations of `patterns`."""
    device_s = trace.op_seconds(ctx.summary, patterns)
    if device_s <= 0 or not ctx.batches:
        return None
    return 1e3 * device_s / len(ctx.batches)
