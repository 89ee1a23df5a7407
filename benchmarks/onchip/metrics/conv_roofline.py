"""% of the conv passes' roofline-minimum time (operations and
compulsory bytes from the configuration's layer table, peaks from
peaks.json) in the device time of the conv operations of the traced
window: the Mosaic kernels (every Pallas call of the program is a conv
pass) and XLA's convolutions, fused or not (an output fusion that
takes a 1x1 or 3x3 kernel)."""

from harness.readers import conv_roofline

EVENTS = (r'custom_call_target="tpu_custom_call"',
          r"= \S+ convolution\(",
          r"fusion\(.*?\[[13],[13],\d+,\d+\]\{.*kind=kOutput")


def read(ctx):
    return conv_roofline(ctx, EVENTS)
