"""The ConvNeXt configuration's own parts and its two metrics: a tiny
ConvNeXt (`data/tiny_convnext.json`, read through the real
`configs/convnext_t_224_model.py`) served through the harness on the
CPU comes out correct and its float8 control does not; `dw_roofline`
reads the depthwise rows and ops alone, `step_roofline` the whole
forward; the full configuration's counts are the published ones."""

import copy
import json

import jax
import numpy as np
import pytest

import onchip_tiny
from harness import parts, spec, work
from harness.readers import TracedWindow

DATA = onchip_tiny.DATA
FULL = json.loads((spec.HERE / "configs" / "convnext_t_224.json")
                  .read_text())
TINY = json.loads((DATA / "tiny_convnext.json").read_text())
CELL = "tiny_convnext.offline"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def bench() -> dict:
    """onchip_tiny's bench with the tiny ConvNeXt and a closed-loop cell
    that reports what `convnext_t_224.offline` reports."""
    b = onchip_tiny.bench()
    b["configs"].append({"name": "tiny_convnext", "source": "test",
                         "file": str((DATA / "tiny_convnext.json")
                                     .relative_to(spec.ROOT)),
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": CELL, "config": "tiny_convnext",
                           "traffic": "tiny_closed", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "convnext_t_224.offline" in m.get("workloads", []):
            m["workloads"].append(CELL)
    return b


@pytest.fixture(scope="module")
def served():
    return onchip_tiny.run(CELL, bench=bench())


def test_the_tiny_convnext_serves_correct(served):
    assert served["correct"], served["checks"]
    assert served["failed"] == 0
    assert served["checks"]["fallbacks"]["value"] == 0
    assert served["checks"]["logit_gap"]["value"] < 1e-4
    assert set(served["metrics"]) == {"served_img_per_s", "setup_s"}


def test_its_float8_control_is_refused():
    out = onchip_tiny.run(CELL, bench=bench(), control=True)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > 1e-2


def test_a_traced_run_reads_the_new_metrics_where_there_are_ops():
    """On the CPU the trace holds no device operations: both readers
    find nothing and the line leaves them out, without raising; what
    they read where there is something is tested below."""
    out = onchip_tiny.run(CELL, bench=bench(), trace=1)
    assert out["correct"], out["checks"]
    names = {"dw_roofline.convnext_offline",
             "step_roofline.convnext_offline"}
    ops = out["breakdown"]["device_ops"]
    assert names <= set(out["metrics"]) if ops else \
        not names & set(out["metrics"])


def test_the_module_and_the_graph_check_are_taken():
    p = parts.parts_of(FULL)
    own = parts.own_module(FULL["model"])
    for name in ("init_params", "check_graph", "logits", "forward_flops",
                 "pass_work"):
        assert getattr(p, name) is getattr(own, name), name


def test_a_graph_that_drops_a_norm_is_refused():
    from repro.models.cnn import convnext_graph
    from repro.models.graph import ConvGraph

    g = convnext_graph()
    nodes = list(g.nodes)
    i = next(i for i, n in enumerate(nodes) if n.name == "s2b1_dw")
    nodes[i] = nodes[i].__class__(**{**nodes[i].__dict__, "norm": False})
    with pytest.raises(ValueError, match="row 14"):
        parts.parts_of(FULL).check_graph(FULL, ConvGraph(
            name=g.name, nodes=tuple(nodes), head_norm=True))
    assert parts.parts_of(FULL).check_graph(FULL, g) is g


def test_the_full_configuration_counts_the_published_work():
    p = parts.parts_of(FULL)
    assert p.forward_flops(FULL) == pytest.approx(8.911e9, rel=1e-3)
    rows = p.pass_work(FULL, 8, ("fwd",))
    assert len(rows) == 59 and rows[-1]["layer"] == "head"
    assert {r["pass"] for r in rows} == {"fwd"}
    assert sum(r["bytes"] for r in rows) == pytest.approx(1.048e9,
                                                          rel=1e-3)
    assert p.pass_work(FULL, 8, ("wgrad",)) == []
    leaves = jax.eval_shape(lambda: p.init_params(FULL, 1))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        leaves))
    assert n == 28_589_128


def _summary(ops) -> dict:
    """A trace summary of one device over a 1 s window, each op given as
    (name, start_ms, dur_ms)."""
    return {"window": [0, 1_000_000_000], "host": [],
            "devices": {"/device:TPU:0": [[n, int(s * 1e6), int(d * 1e6)]
                                          for n, s, d in ops]}}


def _metric(name, summary, batches):
    ctx = TracedWindow(cfg=FULL, peak=PEAK, summary=summary,
                       passes=("fwd",), batches=batches,
                       real_images=sum(batches), flops_per_image=0.0)
    return spec.metric_module(name).read(ctx)


DW_OP = ("%conv_dw.3 = f32[8,56,56,96]{3,2,1,0:T(8,128)} custom-call(...), "
         'custom_call_target="tpu_custom_call"')
FWD_OP = ("%conv_fwd.7 = f32[8,56,56,384]{3,2,1,0:T(8,128)} custom-call("
          '...), custom_call_target="tpu_custom_call"')


def test_dw_roofline_reads_the_depthwise_rows_and_ops_alone():
    rows = parts.parts_of(FULL).pass_work(FULL, 8, ("fwd",))
    dw = [r for r in rows if r["layer"].endswith("_dw")]
    assert len(dw) == 18
    least = work.roofline_seconds(dw, PEAK)
    # 2 ms of conv_dw and 5 ms of other kernels in two dispatches
    got = _metric("dw_roofline.convnext_offline",
                  _summary([(DW_OP, 10, 2.0), (FWD_OP, 20, 5.0)]), [8, 8])
    assert got == pytest.approx(100 * 2 * least / 2e-3)
    assert _metric("dw_roofline.convnext_offline",
                   _summary([(FWD_OP, 20, 5.0)]), [8]) is None


def test_step_roofline_reads_the_whole_forward_over_busy_time():
    rows = parts.parts_of(FULL).pass_work(FULL, 8, ("fwd",))
    least = work.roofline_seconds(rows, PEAK)
    # busy 6 ms of the window: two overlapping ops and one apart
    s = _summary([(DW_OP, 10, 2.0), (FWD_OP, 11, 2.0), (FWD_OP, 50, 3.0)])
    got = _metric("step_roofline.convnext_offline", s, [8, 4])
    least4 = work.roofline_seconds(
        parts.parts_of(FULL).pass_work(FULL, 4, ("fwd",)), PEAK)
    assert got == pytest.approx(100 * (least + least4) / 6e-3)
    empty = copy.deepcopy(s)
    empty["devices"]["/device:TPU:0"] = []
    assert _metric("step_roofline.convnext_offline", empty, [8]) is None
