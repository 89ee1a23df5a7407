"""BENCHMARK.json against the rules it is held to, its files, and the
harness's behaviour without a chip."""

import gzip
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import pytest

import onchip_tiny
from harness import parts, spec

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer",
                      "moves"}}


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/onchip"]
    assert all(w.startswith("benchmarks/onchip/") or not w.endswith(".py")
               for w in BENCH["command"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_their_keys_names_and_units(section):
    for entry in BENCH[section]:
        assert set(entry) - {"workloads"} == KEYS[section], entry["name"]
        assert NAME.match(entry["name"]), entry["name"]
        if "unit" in entry:
            assert UNIT.match(entry["unit"]), entry["unit"]
            assert entry["better"] in ("lower", "higher")
        for text in ("why", "layer", "source"):
            if text in entry:
                assert 1 <= len(entry[text]) <= 200
                assert "\n" not in entry[text] and "\t" not in entry[text]
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    bounds = {m["name"]: m["bound"] for m in BENCH["end_to_end"]}
    assert bounds["setup_s"] <= 0.25
    assert all(0.01 <= b <= 0.25 for b in bounds.values())
    for w in BENCH["workloads"]:
        assert w["chips"] == 1
        cell = spec.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]


def test_each_per_layer_metric_moves_what_its_cells_report():
    for m in BENCH["per_layer"]:
        assert callable(spec.metric_module(m["name"]).read)
        for w in m["workloads"]:
            cell = spec.load_cell(w)
            assert m["moves"] in {e["name"] for e in cell.end_to_end}, \
                (m["name"], w)
        assert [x["layer"] for x in BENCH["per_layer"]
                if x["layer"].lower() == m["layer"].lower()] == \
            [m["layer"]] * sum(x["layer"].lower() == m["layer"].lower()
                               for x in BENCH["per_layer"])


def test_configs_files_and_pairs():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["file"].startswith("benchmarks/onchip/")
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("name", ["vgg16_224", "resnet20_32"])
def test_program_graph_matches_the_layer_table(name):
    cfg = json.loads((spec.HERE / "configs" / f"{name}.json").read_text())
    params = jax.eval_shape(lambda: parts.parts_of(cfg).init_params(cfg, 0))
    graph = parts.program_graph(cfg, params)
    assert len(graph.nodes) == len(cfg["layers"])


def test_a_changed_layer_table_is_refused():
    cfg = json.loads((spec.HERE / "configs" / "resnet20_32.json").read_text())
    cfg["layers"][3]["stride"] = 2
    with pytest.raises(ValueError, match="row 3"):
        parts.program_graph(cfg, None)


def test_without_a_tpu_the_harness_exits_nonzero_and_prints_nothing():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, str(spec.HERE / "run.py"), "--workload",
         "vgg16_224.server", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_a_config_traffic_and_metric_are_added_by_files_alone(tmp_path):
    """A throwaway configuration, kind of load, mix and per-layer
    metric: new files and new entries only, and the harness drives the
    new kind's requests and reports the new metric."""
    b = onchip_tiny.bench()
    shutil.copy(onchip_tiny.DATA / "tiny_vgg.json", tmp_path / "cfg.json")
    b["configs"].append({"name": "throwaway", "source": "test",
                         "file": "cfg.json", "reduced": [], "why": "t"})
    traffic = tmp_path / "traffic"
    traffic.mkdir()
    (traffic / "bursts.py").write_text(
        "import numpy as np\n"
        "from harness import traffic\n"
        "CELL = 'serve'\n"
        "def plan(mix, seconds, seed):\n"
        "    starts = np.arange(0, seconds, mix['every_s'])\n"
        "    offsets = np.repeat(starts, mix['burst'])\n"
        "    return traffic.Plan(\n"
        "        sizes=traffic.request_sizes(mix, len(offsets), seed),\n"
        "        offsets=offsets)\n")
    (traffic / "bursts_of_3.json").write_text(json.dumps(
        {"kind": "bursts", "burst": 3, "every_s": 0.1, "pool_images": 6,
         "images_per_request": [[1, 2], [2, 1]], "buckets": [1, 2, 4],
         "warm_groups": [[1], [2], [1, 1], [1, 2], [2, 1], [1, 1, 1], [1, 1, 2],
                         [1, 2, 1], [2, 1, 1], [2, 2], [1, 1, 1, 1]]}))
    b["workloads"].append({"name": "throwaway.bursts", "config":
                           "throwaway", "traffic": "bursts_of_3", "chips": 1,
                           "why": "t"})
    metrics = tmp_path / "metrics"
    metrics.mkdir()
    (metrics / "images.py").write_text(
        "def read(ctx):\n    return float(ctx.real_images)\n")
    b["per_layer"].append({"name": "images.bursts", "unit": "images",
                           "better": "higher", "source": "program_counter",
                           "layer": "serving loop and buckets",
                           "moves": "p95_ms",
                           "workloads": ["throwaway.bursts"]})
    p95 = next(m for m in b["end_to_end"] if m["name"] == "p95_ms")
    p95["workloads"].append("throwaway.bursts")
    kept = tmp_path / "trace.json.gz"
    rec = onchip_tiny.run("throwaway.bursts", trace=1, bench=b,
                          root=tmp_path, traffic_dir=traffic,
                          metrics_dir=metrics, keep_trace=kept)
    assert rec["correct"], rec["checks"]
    assert rec["attempted"] == 30 and rec["failed"] == 0
    assert rec["notes"]["compiled_in_window"] == []
    assert rec["metrics"]["images.bursts"]["value"] > 0
    assert list(rec["metrics"]) == ["images.bursts"]
    with gzip.open(kept, "rt") as f:
        assert set(json.load(f)) == {"window", "devices", "host"}
