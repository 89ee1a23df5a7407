"""A cell of BENCHMARK.json with the files it names: its configuration,
its traffic mix and the kind of load the mix is, and the readers of its
metrics."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    kind: ModuleType           # traffic/<kind>.py of the mix
    end_to_end: list[dict]     # the end-to-end metrics this cell reports
    per_layer: list[dict]      # the per-layer metrics this cell reports
    metrics_dir: Path = HERE / "metrics"

    def reader(self, metric: str):
        """`read(ctx)` of the metric's reader (see `metric_module`)."""
        return metric_module(metric, self.metrics_dir).read


def _reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, bench: dict | None = None, root: Path = ROOT,
              traffic_dir: Path = HERE / "traffic",
              metrics_dir: Path = HERE / "metrics") -> Cell:
    """The cell `name`: its configuration file, the traffic file named
    after its mix, the module of the mix's kind and the metrics it
    reports, all found by name."""
    if bench is None:
        bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((traffic_dir / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, chips=w["chips"], cfg=cfg, traffic=traffic,
                kind=_module(_first(traffic_dir, HERE / "traffic",
                                    name=f"{traffic['kind']}.py"),
                             "onchip_kind_"),
                end_to_end=e2e, per_layer=per_layer,
                metrics_dir=metrics_dir)


def metric_module(metric: str, metrics_dir: Path = HERE / "metrics"):
    """The reader of a metric: `metrics/<metric>.py`, or else that of
    its family, `metrics/<name up to the first dot>.py`, which serves
    `conv_roofline.server`, `conv_roofline.train` and the like alike."""
    path = metrics_dir / f"{metric}.py"
    if not path.exists():
        path = metrics_dir / f"{metric.split('.')[0]}.py"
    return _module(path, "onchip_metric_")


def _first(*dirs: Path, name: str) -> Path:
    """`name` in the first of `dirs` that holds it (a mix directory
    beside the benchmark's own may bring kinds of its own)."""
    return next((d / name for d in dirs if (d / name).exists()),
                dirs[-1] / name)


def _module(path: Path, prefix: str) -> ModuleType:
    name = prefix + re.sub(r"\W", "_", path.stem)
    loader = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(loader)
    loader.loader.exec_module(mod)
    return mod
