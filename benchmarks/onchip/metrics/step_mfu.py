"""% of the chip's peak FLOP/s that the required FLOPs of the images
completed in the traced window (padding excluded) amount to over the
seconds in which the device was busy: the whole serving step's share
of peak while it runs, which bounds every kernel's roofline share."""

from harness.readers import step_mfu


def read(ctx):
    return step_mfu(ctx)
