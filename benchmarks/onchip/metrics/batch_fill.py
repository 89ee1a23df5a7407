"""% of the bucket images of the traced window's dispatches that were
real images: the rest is padding."""

from harness.readers import batch_fill


def read(ctx):
    return batch_fill(ctx)
