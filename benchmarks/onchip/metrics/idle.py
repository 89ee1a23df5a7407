"""% of the traced window in which no operation ran on the device."""

from harness.readers import idle


def read(ctx):
    return idle(ctx)
