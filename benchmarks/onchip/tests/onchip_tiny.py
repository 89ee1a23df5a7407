"""A run of the harness on the CPU at a tiny size: the real BENCHMARK.json
with a 16x16 VGG configuration and tiny traffic mixes beside it, the
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
TINY = {"tiny.server": "tiny_poisson", "tiny.offline": "tiny_closed",
        "tiny.train": "tiny_sgd"}


def bench() -> dict:
    """BENCHMARK.json with the tiny cells added to every metric whose
    cells they mirror."""
    b = copy.deepcopy(json.loads((spec.ROOT / "BENCHMARK.json").read_text()))
    b["configs"].append({"name": "tiny_vgg", "source": "test",
                         "file": str((DATA / "tiny_vgg.json").relative_to(
                             spec.ROOT)),
                         "reduced": [], "why": "test"})
    for name, traffic in TINY.items():
        b["workloads"].append({"name": name, "config": "tiny_vgg",
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    suffix = {".server": "tiny.server", ".offline": "tiny.offline",
              ".train": "tiny.train", "train_img": "tiny.train",
              "served_img": "tiny.offline", "p95": "tiny.server"}
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [c for k, c in suffix.items()
                               if k in m["name"]]
    return b


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
