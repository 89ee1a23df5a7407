"""% of the depthwise convolutions' roofline-minimum time (their rows of
the configuration's `pass_work`: the layers with groups == ci == co,
peaks from peaks.json) in the device time of the operations named
`conv_dw`, the program's depthwise kernel, over the traced window's
dispatches."""

from harness import trace, work
from harness.parts import parts_of

EVENTS = (r"^%conv_dw[.\s]",)


def depthwise_layers(cfg: dict) -> set[str]:
    return {layer["name"] for layer in cfg["layers"]
            if 1 < layer.get("groups", 1) == layer["ci"] == layer["co"]}


def read(ctx):
    device_s = trace.op_seconds(ctx.summary, EVENTS)
    names = depthwise_layers(ctx.cfg)
    if device_s <= 0 or not ctx.batches or not names:
        return None
    pass_work = parts_of(ctx.cfg).pass_work
    least = sum(work.roofline_seconds(
        [r for r in pass_work(ctx.cfg, b, ("fwd",)) if r["layer"] in names],
        ctx.peak) for b in ctx.batches)
    return 100.0 * least / device_s
