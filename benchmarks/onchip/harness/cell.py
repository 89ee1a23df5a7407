"""One run of one cell, from the command line to the result line.

    python3 benchmarks/onchip/run.py --workload <name> --seed <n>
        --seconds <s> --trace <0|1> [--control]

The last line of standard output is the result:
`{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}`.  `checks` holds each number compared with its limit, and
the same lines end standard error.  `--control` puts the reference, one
precision step down, in the program's place: what the limits must
refuse;
`--fault` plants a fault under the timed path (harness/faults.py).
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time

from harness import device, faults, spec

def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=("answer_altered", "half_batch",
                                        "unchanged"),
                    help="plant a fault under the timed path (see "
                         "harness/faults.py)")
    ap.add_argument("--keep-trace", help="write the trace summary here "
                    "(gzipped JSON)")
    return ap.parse_args(argv)


def window_for(cell, seed: int, *, target, control: bool):
    if cell.kind.CELL == "serve":
        from harness.serve_cell import ServeWindow
        return ServeWindow(cell, seed, target=target, control=control)
    from harness.train_cell import TrainWindow
    return TrainWindow(cell, seed, target=target, control=control)


def limits(cell) -> dict:
    return {**cell.cfg["limits"][cell.kind.CELL], "fallbacks": 0, "degraded": 0,
            "account_only": 0, "compiles_in_window": 0}


def execute(args, t_start: float, *, target="compiled",
            require_chip: bool = True, **where) -> dict:
    """Set up, measure, compare; returns the result record.  `where`
    goes to `spec.load_cell` (another BENCHMARK.json, traffic or
    metrics directory, as tests give)."""
    cell = spec.load_cell(args.workload, **where)
    if require_chip:
        device.enable_compile_cache(spec.ROOT)
    dev = (device.require_chips(cell.chips) if require_chip
           else device.describe(cell.chips))
    compiles = device.CompileCounter()
    run = window_for(cell, args.seed, target=target, control=args.control)
    with faults.planted(args.fault, cell.kind.CELL):
        run.start()
        gc.collect()
        setup_s = time.monotonic() - t_start
        compiles.armed = True
        out = run.measure(args.seconds, bool(args.trace))
        compiles.armed = False
    dev["memory_peak_bytes"] = device.memory_peak_bytes(cell.chips)
    numbers = {**run.health(), "compiles_in_window": compiles.count}
    run.release()
    gc.collect()
    numbers.update(run.compare())
    lim = limits(cell)
    checks = {k: {"value": v, "limit": lim[k]} for k, v in numbers.items()
              if k in lim}
    out.setdefault("notes", {}).update(
        {k: v for k, v in numbers.items() if k not in lim})
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    values = {**out["values"], "setup_s": setup_s}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    breakdown = None
    if args.trace:
        from harness import trace
        traced = out["traced"]
        if args.keep_trace:
            import gzip
            with gzip.open(args.keep_trace, "wt") as f:
                json.dump(traced.summary, f)
        traced.peak = device.peaks(dev["kind"]) if require_chip else \
            {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(traced)
            if v is not None:
                metrics[m["name"]] = v
        dev["busy_s"] = trace.busy_s(traced.summary)
        dev["window_s"] = trace.window_s(traced.summary)
        breakdown = {"device_ops": trace.top_ops(traced.summary),
                     "idle_gaps": trace.idle_gaps(traced.summary)}
    else:
        metrics = {m["name"]: values[m["name"]] for m in cell.end_to_end}
    record = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"],
              "metrics": {k: {"value": _finite(v), "unit": units[k]}
                          for k, v in metrics.items()},
              "device": dev}
    if breakdown is not None:
        record["breakdown"] = breakdown
    record["notes"] = {**{k: _finite(v) if isinstance(v, float) else v
                          for k, v in out.get("notes", {}).items()},
                       "compiled_in_window": compiles.names[:20]}
    record["checks"] = {k: {"value": _finite(c["value"]),
                            "limit": c["limit"]} for k, c in checks.items()}
    return record


def _finite(v: float) -> float:
    """JSON has no infinity: a tail made of misses reads 1e9."""
    return v if math.isfinite(v) else 1e9


def main(argv, t_start: float) -> int:
    args = parse(argv)
    try:
        record = execute(args, t_start)
    except device.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    for name, c in record["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(record), flush=True)
    return 0
