"""% of the chip's peak FLOP/s that the required FLOPs of the images
completed in the traced window amount to (padding excluded)."""

from harness.readers import mfu


def read(ctx):
    return mfu(ctx)
