# One function per paper table. Print ``name,us_per_call,derived`` CSV;
# ``--json PATH`` additionally writes the rows as a machine-readable
# BENCH_<n>.json-style record so the perf trajectory (traffic ratios,
# walltimes) is comparable across PRs.
import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark function names")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON to PATH "
                         "(e.g. BENCH_2.json)")
    ap.add_argument("--target", default=None,
                    choices=("interpret", "lax"),
                    help="execution target for the kernel walltime "
                         "benches (default: interpret)")
    args = ap.parse_args()

    if args.target:
        import benchmarks.kernel_bench as kernel_bench
        kernel_bench.WALLTIME_TARGET = args.target

    from benchmarks.kernel_bench import ALL_KERNELS
    from benchmarks.obs_bench import ALL_OBS
    from benchmarks.paper_tables import ALL_TABLES
    from benchmarks.plan_audit_bench import ALL_AUDIT
    from benchmarks.roofline_bench import ALL_ROOFLINE
    from benchmarks.serve_bench import ALL_SERVE
    from benchmarks.train_traffic_bench import ALL_TRAIN

    benches = (ALL_TABLES + ALL_KERNELS + ALL_SERVE + ALL_TRAIN
               + ALL_AUDIT + ALL_OBS)
    if not args.skip_roofline:
        benches = benches + ALL_ROOFLINE

    rows = []
    print("name,us_per_call,derived")
    for fn in benches:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            for name, us, derived in fn():
                # us is None for analytic/derived-only rows: no wall
                # clock was involved, and pretending 0.0 us would be a
                # placeholder masquerading as a measurement
                print(f"{name},"
                      f"{'null' if us is None else format(us, '.1f')},"
                      f"{derived}")
                rows.append({"name": name,
                             "us_per_call":
                                 None if us is None else round(us, 1),
                             "derived": derived})
        except Exception as e:  # noqa: BLE001
            print(f"{fn.__name__}/ERROR,0.0,{e!r}", file=sys.stderr)
            raise

    if args.json:
        with open(args.json, "w") as fh:
            json.dump(rows, fh, indent=1)
        print(f"wrote {len(rows)} rows to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
