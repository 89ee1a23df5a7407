"""Traffic-ratio regression gate across committed BENCH_<n>.json files.

``benchmarks/run.py --json BENCH_<n>.json`` emits one machine-readable
record per PR; this script diffs the *tracked ratio metrics* between
the two most recent records that report each metric and exits nonzero
on a >10% regression — the ROADMAP's traffic-regression tracking.

Tracked metrics (by row-name suffix):

  * ``.../vs_bound_x``, ``.../vs_serving_x``,
    ``.../train_vs_bound_x`` — measured/bound ratios (the last over a
    full fwd+dgrad+wgrad training step), lower is better;
  * ``.../resnet_vs_bound_x``, ``.../resnet_train_vs_bound_x`` — the
    cross-model (graph-level) serve/train ratio families, gated like
    VGG's (listed first: most-specific suffix wins);
  * ``.../w_reduction_x``, ``.../w_amortization_x``,
    ``.../reduction_x``, ``.../autotune_vs_closed_x`` — improvement
    factors, higher is better;
  * ``.../plan_audit_legal_frac`` (higher is better) and
    ``.../plan_audit_traffic_mismatches`` / ``.../lint_errors``
    (lower is better, 0 baseline: any nonzero value trips the gate)
    — the static-analysis rows from ``plan_audit_bench``;
  * ``.../serve_shed_frac`` / ``.../serve_p99_x_budget`` (lower is
    better) and ``.../serve_goodput_rps`` (higher is better) — the
    fault-tolerant serving loop's bursty-trace health rows;
  * ``.../obs_overhead_frac`` (lower is better) — the tracing layer's
    analytic cost over the account-only serve smoke
    (``obs_bench.py``): observability must stay ~free.

Usage:  python benchmarks/diff_bench.py [BENCH_2.json BENCH_3.json ...]
(no args: every BENCH_*.json next to the repo root, ordered by n).
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

# suffix -> True when lower values are better; iteration order is
# match precedence, so most-specific suffixes come first
TRACKED = {
    "resnet_train_vs_bound_x": True,  # cross-model training ratio
    "resnet_vs_bound_x": True,        # cross-model serving ratio
    "train_vs_bound_x": True,    # training-step fwd+dgrad+wgrad ratio
    # executing-backward gates: the wgrad kernel's *measured* traffic
    # vs its dW-stationary Eq. (15) bound; the fraction of layers whose
    # dgrad rides the kernel (1.0 = strided downsamples included); and
    # the executed wgrad's numerics vs the lax VJP
    "wgrad_vs_bound_x": True,
    "dgrad_kernel_frac": False,
    "numeric_relerr": True,
    "vs_bound_x": True,
    "vs_serving_x": True,
    "w_reduction_x": False,
    "w_amortization_x": False,
    "reduction_x": False,
    "autotune_vs_closed_x": False,
    # static-analysis gates: the audited legal fraction must not
    # regress (higher better); mismatch/lint counts must stay 0 —
    # with a 0 baseline ANY nonzero value trips the ratio gate
    "plan_audit_legal_frac": False,
    "plan_audit_traffic_mismatches": True,
    "lint_errors": True,
    # fault-tolerant serving loop (bursty trace, virtual clock):
    # shedding and tail latency must not creep up, goodput not down
    "serve_shed_frac": True,
    "serve_p99_x_budget": True,
    "serve_goodput_rps": False,
    # observability tax: analytic cost of full tracing over the
    # account-only serve smoke; must stay a rounding error
    "obs_overhead_frac": True,
}


def _tracked_direction(name: str) -> bool | None:
    for suffix, lower_better in TRACKED.items():
        if name.endswith(suffix):
            return lower_better
    return None


def _bench_index(path: Path) -> int:
    m = re.search(r"BENCH_(\d+)", path.name)
    return int(m.group(1)) if m else -1


def load_series(paths: list[Path]) -> dict[str, list[tuple[str, float]]]:
    """metric name -> [(file label, value)] in file order."""
    series: dict[str, list[tuple[str, float]]] = {}
    for path in paths:
        rows = json.loads(path.read_text())
        for row in rows:
            name = row.get("name", "")
            if _tracked_direction(name) is None:
                continue
            try:
                val = float(row["derived"])
            except (TypeError, ValueError, KeyError):
                continue
            series.setdefault(name, []).append((path.name, val))
    return series


def diff(series: dict[str, list[tuple[str, float]]],
         threshold: float = 0.10) -> list[str]:
    """Human-readable report lines; regression lines start with FAIL."""
    lines = []
    for name in sorted(series):
        points = series[name]
        if len(points) < 2:
            lines.append(f"  ok   {name}: {points[-1][1]} "
                         f"({points[-1][0]}, no prior record)")
            continue
        (old_f, old), (new_f, new) = points[-2], points[-1]
        lower_better = _tracked_direction(name)
        if lower_better:
            regressed = new > old * (1.0 + threshold)
        else:
            regressed = new < old * (1.0 - threshold)
        delta = (new - old) / old * 100.0 if old else float("inf")
        tag = "FAIL" if regressed else "ok  "
        lines.append(f"  {tag} {name}: {old} ({old_f}) -> {new} "
                     f"({new_f}) [{delta:+.1f}%]")
    return lines


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("files", nargs="*",
                    help="BENCH_*.json records (default: repo root)")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="fractional regression tolerance")
    args = ap.parse_args(argv)

    if args.files:
        paths = [Path(f) for f in args.files]
    else:
        root = Path(__file__).resolve().parent.parent
        paths = sorted(root.glob("BENCH_*.json"), key=_bench_index)
    missing = [p for p in paths if not p.exists()]
    if missing:
        print(f"missing record(s): {', '.join(map(str, missing))}",
              file=sys.stderr)
        return 2
    if not paths:
        print("no BENCH_*.json records found; run "
              "benchmarks/run.py --json BENCH_<n>.json first")
        return 0

    series = load_series(paths)
    if not series:
        print("no tracked ratio metrics in the given records")
        return 0
    lines = diff(series, args.threshold)
    print(f"traffic regression gate over {len(paths)} record(s), "
          f"threshold {args.threshold:.0%}:")
    print("\n".join(lines))
    failures = sum(l.lstrip().startswith("FAIL") for l in lines)
    if failures:
        print(f"{failures} metric(s) regressed >"
              f"{args.threshold:.0%}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
