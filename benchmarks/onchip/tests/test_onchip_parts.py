"""A configuration brings its own model parts through `harness/parts.py`:
weights, graph check, reference, controls and work counts.  One that
names no module gets the defaults, which must read exactly what they
read before the seam existed (`data/default_parts.json`, recorded from
the harness before it), and a module that re-exports the defaults reads
the same as none."""

import json
import shutil

import jax
import numpy as np
import pytest

import onchip_tiny
from harness import model, parts, readers, reference, spec, work

DATA = onchip_tiny.DATA
DEFAULTS = json.loads((DATA / "default_parts.json").read_text())
FILES = {"vgg16_224": spec.HERE / "configs" / "vgg16_224.json",
         "resnet20_32": spec.HERE / "configs" / "resnet20_32.json",
         "tiny_vgg": DATA / "tiny_vgg.json"}
SEED = 20260419     # the seed the recorded weights were made from


def load(path):
    return json.loads(path.read_text())


def with_config(tmp_path, name: str, cfg: dict) -> dict:
    """onchip_tiny's bench with configuration `name` read from a copy of
    `cfg` under `tmp_path` (the root the run reads it from)."""
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    b = onchip_tiny.bench()
    next(c for c in b["configs"] if c["name"] == name)["file"] = "cfg.json"
    return b


@pytest.mark.parametrize("name", sorted(FILES))
def test_default_work_counts_are_unchanged(name):
    cfg = load(FILES[name])
    p = parts.parts_of(cfg)
    want = DEFAULTS[name]
    assert p.forward_flops(cfg) == want["forward_flops"]
    assert p.train_flops(cfg) == want["train_flops"]
    for batch, rows in want["pass_work"].items():
        got = p.pass_work(cfg, int(batch), ("fwd", "wgrad", "dgrad"))
        assert [[r["layer"], r["pass"], r["flops"], r["bytes"]]
                for r in got] == rows, batch


@pytest.mark.parametrize("name", sorted(FILES))
def test_default_weights_are_unchanged(name):
    cfg = load(FILES[name])
    leaves = jax.tree_util.tree_leaves(parts.parts_of(cfg).init_params(
        cfg, SEED))
    assert [[list(x.shape), str(x.dtype),
             float(np.asarray(x, np.float64).sum())]
            for x in leaves] == DEFAULTS[name]["init_params"]


def test_a_config_without_a_module_gets_every_default():
    p = parts.parts_of(load(FILES["vgg16_224"]))
    assert p.init_params is model.init_params
    assert p.check_graph is model.check_graph
    assert p.logits is reference.logits
    assert p.loss.func is reference.loss
    assert p.loss.keywords == {"logits": reference.logits}
    assert (p.forward_flops, p.train_flops, p.pass_work) == (
        work.forward_flops, work.train_flops, work.pass_work)


def test_a_module_s_parts_are_taken_and_the_rest_are_defaults():
    cfg = load(DATA / "tiny_grouped.json")
    own = parts.own_module(cfg["model"])
    p = parts.parts_of(cfg)
    for name in ("init_params", "check_graph", "logits", "forward_flops",
                 "pass_work"):
        assert getattr(p, name) is getattr(own, name), name
    assert p.train_flops is work.train_flops
    # the loss left out is the cross-entropy of the module's own logits
    assert p.loss.func is reference.loss
    assert p.loss.keywords == {"logits": own.logits}
    # the module's grouped counts and the default's agree
    assert own.forward_flops(cfg) == work.forward_flops(cfg)
    for batch in (1, 8):
        assert own.pass_work(cfg, batch, ("fwd", "wgrad", "dgrad")) == \
            work.pass_work(cfg, batch, ("fwd", "wgrad", "dgrad"))
    # a depthwise 3x3 layer on an 8x8 plane: 9 MACs a pixel and channel
    dw = cfg["layers"][1]
    assert work.conv_macs(dw) == 8 * 8 * 9 * 8


@pytest.mark.parametrize("path", ["../run.py", "/outside/model.py",
                                  "configs/vgg16_224.json"])
def test_a_model_module_outside_the_benchmark_is_refused(path):
    with pytest.raises(ValueError, match="model module"):
        parts.parts_of({"model": path})


def test_grouped_config_serves_correct_through_its_own_parts():
    rec = onchip_tiny.run("tiny_grouped.server")
    assert rec["correct"], rec["checks"]
    assert rec["attempted"] > 0 and rec["failed"] == 0


def test_the_grouped_serving_control_is_not_correct():
    rec = onchip_tiny.run("tiny_grouped.server", control=True)
    assert not rec["correct"]
    assert rec["checks"]["logit_gap"]["value"] > \
        rec["checks"]["logit_gap"]["limit"]


def test_the_grouped_config_without_its_module_fails_at_setup(tmp_path):
    cfg = load(DATA / "tiny_grouped.json")
    del cfg["model"]
    b = with_config(tmp_path, "tiny_grouped", cfg)
    with pytest.raises(ValueError, match="departs from the layer table"):
        onchip_tiny.run("tiny_grouped.server", bench=b, root=tmp_path)


def test_conv_roofline_counts_the_configuration_s_own_work(monkeypatch):
    cfg = load(DATA / "tiny_grouped.json")
    rows = [{"layer": "x", "pass": "fwd", "flops": 3e6, "bytes": 2e4}]
    monkeypatch.setattr(parts.own_module(cfg["model"]), "pass_work",
                        lambda cfg, batch, passes: rows * batch)
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    summary = {"window": [0, 10 ** 6], "host": [], "devices": {
        "/device:TPU:0": [['custom_call_target="tpu_custom_call"', 0,
                           10 ** 5]]}}
    ctx = readers.TracedWindow(cfg=cfg, peak=peak, summary=summary,
                               passes=("fwd",), batches=[8, 8],
                               real_images=16, flops_per_image=1.0)
    # 16 rows of 20 us (bytes bound) in 100 us of kernels
    got = readers.conv_roofline(ctx, ["tpu_custom_call"])
    assert got == pytest.approx(320.0, rel=1e-12)


def test_serving_counts_the_configuration_s_own_flops(monkeypatch,
                                                      tmp_path):
    cfg = load(DATA / "tiny_grouped.json")
    monkeypatch.setattr(parts.own_module(cfg["model"]), "forward_flops",
                        lambda cfg: 12345.0)
    metrics = shutil.copytree(spec.HERE / "metrics", tmp_path / "metrics")
    (metrics / "flops.py").write_text(
        "def read(ctx):\n    return ctx.flops_per_image\n")
    b = onchip_tiny.bench()
    b["per_layer"].append({"name": "flops.server", "unit": "FLOP",
                           "better": "higher", "source": "program_counter",
                           "layer": "model step", "moves": "p95_ms",
                           "workloads": ["tiny_grouped.server"]})
    rec = onchip_tiny.run("tiny_grouped.server", trace=1, bench=b,
                          metrics_dir=metrics)
    assert rec["metrics"]["flops.server"]["value"] == 12345.0


def test_training_through_a_module_of_defaults_reads_the_same(tmp_path):
    cfg = load(DATA / "tiny_vgg.json")
    cfg["model"] = "tests/data/tiny_defaults_model.py"
    b = with_config(tmp_path, "tiny_vgg", cfg)
    seam = onchip_tiny.run("tiny.train", seed=987654321098, bench=b,
                           root=tmp_path)
    plain = onchip_tiny.run("tiny.train", seed=987654321098)
    assert seam["correct"] and plain["correct"]
    for name in ("loss_gap", "grad_gap", "change_gap"):
        assert seam["checks"][name] == plain["checks"][name], name
