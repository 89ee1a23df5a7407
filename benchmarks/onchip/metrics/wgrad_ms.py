"""Device ms per training step in the weight-gradient kernel: the
Pallas calls whose output is a 1x1 or 3x3 kernel's gradient."""

from harness.readers import ms_per_batch

EVENTS = (r"= f32\[[13],[13],\d+,\d+\]\{[^}]*\} custom-call\(.*"
          r'custom_call_target="tpu_custom_call"',)


def read(ctx):
    return ms_per_batch(ctx, EVENTS)
