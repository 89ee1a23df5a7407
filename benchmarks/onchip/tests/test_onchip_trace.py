"""The reduction from a trace summary to busy time, kernel time, idle
gaps and the per-layer metrics: on a made-up summary whose answers are
known, and on a trimmed trace recorded on a TPU v5e."""

import gzip
import json

import pytest

import onchip_tiny
from harness import readers, spec, trace

MS = 1_000_000      # ns


def made_up():
    """10 ms window; device ops at 0-2, 1-3 (overlapping), 5-6 ms and
    one from 9 ms running past the window's end."""
    return {"window": [0, 10 * MS],
            "devices": {"/device:TPU:0": [
                ["_conv_kernel.1", 0, 2 * MS],
                ["fusion.3", 1 * MS, 2 * MS],
                ["_wgrad_kernel", 5 * MS, 1 * MS],
                ["convolution.7", 9 * MS, 3 * MS]]},
            "host": [["serve.execute", 3 * MS, 2 * MS],
                     ["onchip.send", 6 * MS, 3 * MS],
                     ["onchip.sleep", 6 * MS, 1 * MS]]}


def test_busy_is_the_union_of_device_ops_inside_the_window():
    s = made_up()
    assert trace.window_s(s) == pytest.approx(0.010)
    assert trace.busy_s(s) == pytest.approx(0.005)    # 0-3, 5-6, 9-10


def test_kernel_seconds_match_names_and_clip_to_the_window():
    s = made_up()
    assert trace.op_seconds(s, ["_conv_kernel", "convolution"]) == \
        pytest.approx(0.003)
    assert trace.op_seconds(s, ["_wgrad_kernel"]) == pytest.approx(0.001)
    assert trace.top_ops(s, 2) == [["_conv_kernel.1", 0.002],
                                   ["fusion.3", 0.002]]


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    gaps = trace.idle_gaps(made_up())
    assert gaps == [["onchip.send", pytest.approx(0.003)],
                    ["serve.execute", pytest.approx(0.002)]]


def test_trimmed_keeps_the_start_of_the_window():
    t = trace.trimmed(made_up(), 0.004, min_host_ns=2 * MS)
    assert t["window"] == [0, 4 * MS]
    assert [e[0] for e in t["devices"]["/device:TPU:0"]] == \
        ["_conv_kernel.1", "fusion.3"]
    assert [h[0] for h in t["host"]] == ["serve.execute"]


def test_readers_on_the_made_up_window():
    cfg = json.loads((spec.HERE / "configs" / "resnet20_32.json")
                     .read_text())
    ctx = readers.TracedWindow(
        cfg=cfg, summary=made_up(), passes=("fwd",),
        peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        batches=[8, 4], real_images=10, flops_per_image=81.6e6)
    assert readers.idle(ctx) == pytest.approx(50.0)
    assert readers.batch_fill(ctx) == pytest.approx(100 * 10 / 12)
    assert readers.mfu(ctx) == pytest.approx(
        100 * 10 * 81.6e6 / 0.010 / 197e12)
    assert readers.step_mfu(ctx) == pytest.approx(
        100 * 10 * 81.6e6 / 0.005 / 197e12)
    assert readers.ms_per_batch(ctx, ["_wgrad_kernel"]) == \
        pytest.approx(0.5)
    assert 0 < readers.conv_roofline(ctx, ["_conv_kernel"]) < 100
    assert readers.conv_roofline(ctx, ["no such op"]) is None


def test_recorded_chip_trace_reduces_to_its_numbers():
    """The first 0.25 s of a traced `vgg16_224.server` window on one v5e
    (host spans of 20 us or more kept), made with `trace.trimmed`."""
    path = onchip_tiny.DATA / "trace_vgg16_224_server.json.gz"
    with gzip.open(path, "rt") as f:
        s = json.load(f)
    assert list(s["devices"]) == ["/device:TPU:0"]
    assert trace.window_s(s) == pytest.approx(0.25)
    assert trace.busy_s(s) == pytest.approx(0.046206841)
    events = spec.metric_module("conv_roofline.server").EVENTS
    assert trace.op_seconds(s, events) == pytest.approx(0.028363026)
    top = trace.top_ops(s, 2)
    assert [t[0] for t in top] == ["%conv2d_lb.13 f32[8,224,224,64]",
                                   "%conv2d_lb.14 f32[8,112,112,64]"]
    # the host was linearizing request images for the device
    assert trace.idle_gaps(s, 1) == [["Transpose",
                                      pytest.approx(0.022126305)]]
