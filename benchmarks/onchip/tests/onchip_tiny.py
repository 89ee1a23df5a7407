"""A run of the harness on the CPU at a tiny size: the real BENCHMARK.json
with 16x16 configurations (a VGG, and a net of grouped convolutions
that brings its own parts) and tiny traffic mixes beside it, the
program on its `lax` target, no chip asked for, and a serving deadline
long enough that a busy CPU sheds nothing."""

from __future__ import annotations

import copy
import functools
import json
import sys
import time
from pathlib import Path
from unittest import mock

HERE = Path(__file__).resolve().parent
ONCHIP = HERE.parent
sys.path[:0] = [str(ONCHIP), str(ONCHIP.parents[1] / "src")]

from harness import cell as cell_mod   # noqa: E402
from harness import spec               # noqa: E402

DATA = HERE / "data"
TINY = {"tiny.server": ("tiny_vgg", "tiny_poisson"),
        "tiny.offline": ("tiny_vgg", "tiny_closed"),
        "tiny.train": ("tiny_vgg", "tiny_sgd"),
        "tiny_grouped.server": ("tiny_grouped", "tiny_poisson")}


def bench() -> dict:
    """BENCHMARK.json with the tiny cells added to every metric whose
    cells they mirror."""
    b = copy.deepcopy(json.loads((spec.ROOT / "BENCHMARK.json").read_text()))
    for cfg in sorted({cfg for cfg, _ in TINY.values()}):
        b["configs"].append({"name": cfg, "source": "test",
                             "file": str((DATA / f"{cfg}.json").relative_to(
                                 spec.ROOT)),
                             "reduced": [], "why": "test"})
    for name, (cfg, traffic) in TINY.items():
        b["workloads"].append({"name": name, "config": cfg,
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    suffix = {".server": ".server", ".offline": ".offline",
              ".train": ".train", "train_img": ".train",
              "served_img": ".offline", "p95": ".server"}
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [c for k, end in suffix.items()
                               if k in m["name"]
                               for c in TINY if c.endswith(end)]
    return b


def grouped_graph():
    """The program's ConvGraph of `data/tiny_grouped.json`: a dense 3x3
    stem, a depthwise 3x3 conv and a 1x1 conv in 2 groups."""
    from repro.models.graph import ConvGraph, ConvNode

    return ConvGraph(name="tiny_grouped", nodes=(
        ConvNode(name="stem", ci=3, co=8, pool=2),
        ConvNode(name="dw", ci=8, co=8, groups=8),
        ConvNode(name="pw", ci=8, co=16, hk=1, wk=1, pad=0, groups=2,
                 pool=2)))


def run(workload: str, *, seed: int = 123456789012, seconds: float = 1.0,
        trace: int = 0, control: bool = False, fault: str | None = None,
        keep_trace=None, **where) -> dict:
    args = cell_mod.parse(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)]
                          + (["--control"] if control else [])
                          + (["--fault", fault] if fault else [])
                          + (["--keep-trace", str(keep_trace)]
                             if keep_trace else []))
    where.setdefault("bench", bench())
    where.setdefault("traffic_dir", DATA / "traffic")
    import repro.serve
    loop = functools.partial(repro.serve.ServingLoop, deadline_s=30.0)
    with mock.patch.object(repro.serve, "ServingLoop", loop):
        return cell_mod.execute(args, time.monotonic(), target="lax",
                                require_chip=False, **where)
