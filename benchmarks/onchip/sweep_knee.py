"""Find the highest Poisson rate a serving cell sustains, on the chip.

    python3 benchmarks/onchip/sweep_knee.py --workload vgg16_224.server \
        --rates 200,300,400 --windows 3 --seconds 10 --seed 1 [--out k.json]

One process: set-up once, then `windows` windows at each rate, lowest
rate first, until two rates in a row are not sustained.  A window is
sustained when none of its requests was shed or failed and its p99 is
at most half the serving loop's deadline; a rate is sustained when
every one of its windows is.  The knee is the highest rate below the
lowest rate that is not sustained, so it rises monotonically with the
rates tried; a cell runs at four fifths of it, written into its traffic
file as a number.
"""

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.monotonic()
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from harness import device, spec                    # noqa: E402
from harness.serve_cell import ServeWindow          # noqa: E402

DEADLINE_S = 0.25       # ServingLoop's default deadline


def window_row(out: dict, rate: float) -> dict:
    p99 = float(out["notes"]["p99_ms"])
    return {"rate_per_s": rate,
            "sustained": bool(out["failed"] == 0
                              and p99 <= 1e3 * DEADLINE_S / 2),
            "p95_ms": float(out["values"]["p95_ms"]), "p99_ms": p99,
            "attempted": out["attempted"], "failed": out["failed"],
            **{k: float(v) for k, v in out["notes"].items()
               if isinstance(v, (int, float))}}


def knee_of(rows) -> float | None:
    """The highest rate below the lowest rate with a window that was
    not sustained (every rate tried, if none failed)."""
    failing = [r["rate_per_s"] for r in rows if not r["sustained"]]
    below = [r["rate_per_s"] for r in rows
             if not failing or r["rate_per_s"] < min(failing)]
    return max(below, default=None)


def sweep(win: ServeWindow, rates, windows: int, seconds: float):
    rows, misses = [], 0
    for rate in sorted(rates):
        win.cell.traffic["rate_per_s"] = rate
        ok = True
        for _ in range(windows):
            rows.append(window_row(win.measure(seconds, False), rate))
            print(json.dumps(rows[-1]), flush=True)
            ok = ok and rows[-1]["sustained"]
        misses = 0 if ok else misses + 1
        if misses == 2:
            break
    return rows, knee_of(rows)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    device.enable_compile_cache(spec.ROOT)
    dev = device.require_chips(cell.chips)
    win = ServeWindow(cell, args.seed, target="compiled")
    win.start()
    rows, knee = sweep(win, [float(r) for r in args.rates.split(",")],
                       args.windows, args.seconds)
    result = {"workload": args.workload, "device": dev, "knee": knee,
              "cell_rate": None if knee is None else 0.8 * knee,
              "windows": args.windows, "seconds": args.seconds,
              "rates": rows}
    print(json.dumps({k: result[k] for k in ("workload", "knee",
                                              "cell_rate")}))
    if args.out:
        Path(args.out).write_text(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
