"""The program's own spans in the profiler's trace: a `repro.obs.Tracer`
span opened inside the benchmark's traced window reads back, through
`harness.trace`, as a host event of the same name and length; and a
trimmed trace of `resnet20_32.server` on a TPU v5e, served with a
tracer, holds the serving spans beside the device's operations."""

import gzip
import json
import time

import pytest

import onchip_tiny  # noqa: F401  (puts the harness on the path)
from harness import trace
from repro.obs import Tracer


def test_tracer_span_is_a_host_event_of_the_profile():
    tr = Tracer()
    capture = trace.Capture()
    capture.start()
    try:
        capture.begin()
        with tr.span("x"):
            time.sleep(0.02)
        capture.end()
    finally:
        capture.stop()
    s = capture.summary()
    (event,) = [h for h in s["host"] if h[0] == "x"]
    (span,) = tr.find(name="x")
    assert abs(event[2] / 1e9 - span.dur) < 1e-3
    lo, hi = s["window"]
    assert lo <= event[1] and event[1] + event[2] <= hi


def recorded():
    """The first 0.25 s of a traced `resnet20_32.server` window at 480/s
    on one v5e whose server ran a `Tracer` (host spans of 20 us or
    more kept), made with `trace.trimmed`."""
    path = onchip_tiny.DATA / "trace_resnet20_32_server_spans.json.gz"
    with gzip.open(path, "rt") as f:
        return json.load(f)


def spans_ms(s, name):
    lo, hi = s["window"]
    return [d / 1e6 for n, start, d in s["host"]
            if n == name and lo <= start < hi]


def test_recorded_serving_spans_lie_beside_the_device_ops():
    s = recorded()
    assert trace.window_s(s) == pytest.approx(0.25)
    assert trace.busy_s(s) == pytest.approx(0.002244072)
    counts = {n: len(spans_ms(s, n)) for n in
              ("loop.admit", "serve.h2d", "serve.assemble",
               "serve.execute", "serve.complete")}
    # one admission and one copy a request, one of the rest a dispatch
    assert counts == {"loop.admit": 91, "serve.h2d": 91,
                      "serve.assemble": 12, "serve.execute": 12,
                      "serve.complete": 12}
    h2d = spans_ms(s, "serve.h2d")
    assert sum(h2d) / len(h2d) == pytest.approx(0.3285041, rel=1e-6)
    # completing a dispatch (per-request logits slices, the ledger)
    # takes the host longer than executing it
    assert (sum(spans_ms(s, "serve.complete"))
            > 5 * sum(spans_ms(s, "serve.execute")))
    # the longest idle gap falls where the host runs nothing: the
    # batching wait, with requests queued for their bucket
    assert trace.idle_gaps(s, 1) == [["no host span",
                                      pytest.approx(0.022002457)]]
