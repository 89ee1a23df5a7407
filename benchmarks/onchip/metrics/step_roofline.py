"""% of the whole forward's roofline-minimum time (every row of the
configuration's `pass_work` at each dispatch's bucket, peaks from
peaks.json) in the seconds in which the device was busy in the traced
window: the same work whatever implements a pass, so fusing a pass, or
splitting one out, moves it."""

from harness import trace, work
from harness.parts import parts_of


def read(ctx):
    busy = trace.busy_s(ctx.summary)
    if busy <= 0 or not ctx.batches:
        return None
    pass_work = parts_of(ctx.cfg).pass_work
    least = sum(work.roofline_seconds(pass_work(ctx.cfg, b, ("fwd",)),
                                      ctx.peak) for b in ctx.batches)
    return 100.0 * least / busy / len(ctx.summary["devices"])
