"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Prints ``name,us_per_call,derived`` CSV — one block per paper
table/figure (benchmarks/figures.py), the live-compute microbenchmarks
(benchmarks/microbench.py) and, when dry-run artifacts exist, the
roofline summary (benchmarks/roofline.py).

Full runs also write ``BENCH_relay.json`` (override with
``--relay-json``): the machine-readable per-mode perf headline — P99,
SLO-compliant throughput, hit rates — so successive PRs have a
serving-perf trajectory to diff.  ``--quick`` skips the write unless a
path is given, so reduced runs never clobber the committed trajectory.

``--quick`` runs a reduced subset (used by CI / test_benchmarks).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

# every BENCH_relay.json must report these serving modes
RELAY_MODES = ("baseline", "relay", "relay_dram", "relay_batched",
               "relay_paged", "relay_devpool", "relay_segments",
               "relay_multihost", "relay_disagg", "relay_cold",
               "relay_tenants")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="",
                    help="substring filter on benchmark function names")
    ap.add_argument("--relay-json", default=None,
                    help="perf-headline output path ('' disables; default "
                         "BENCH_relay.json, or skipped under --quick so a "
                         "reduced run never overwrites the committed "
                         "full-run trajectory)")
    args = ap.parse_args(argv)
    if args.relay_json is None:
        args.relay_json = "" if args.quick else "BENCH_relay.json"

    from benchmarks import ablations, figures, microbench

    fig_fns = list(figures.ALL_FIGURES) + list(ablations.ALL_ABLATIONS)
    micro_fns = list(microbench.ALL_MICRO)
    if args.quick:
        fig_fns = [figures.fig11d_slo_throughput,
                   figures.fig12_local_vs_remote,
                   figures.table1_kv_footprint]
        micro_fns = []
    if args.only:
        fig_fns = [f for f in fig_fns if args.only in f.__name__]
        micro_fns = [f for f in micro_fns if args.only in f.__name__]

    print("name,us_per_call,derived")
    failed = []
    for fn in fig_fns + micro_fns:
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # report, run the rest, exit non-zero
            traceback.print_exc()
            print(f"{fn.__name__},0,ERROR: {type(e).__name__}: {e}")
            failed.append(fn.__name__)
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        print(f"# {fn.__name__} took {time.time() - t0:.1f}s",
              file=sys.stderr)

    if args.relay_json and not args.only:
        t0 = time.time()
        headline = figures.bench_relay_summary(quick=args.quick)
        missing = [f"{mode}.{field}"
                   for mode in RELAY_MODES
                   for field in ("slo_qps", "p99_ms")
                   if field not in headline.get(mode, {})]
        if missing:  # CI gates on the headline schema — fail loudly
            raise SystemExit(f"BENCH_relay headline incomplete: {missing}")
        with open(args.relay_json, "w") as f:
            json.dump(headline, f, indent=1, sort_keys=True)
        print(f"# wrote {args.relay_json} in {time.time() - t0:.1f}s",
              file=sys.stderr)

    # roofline summary (empty unless the dry-run has produced artifacts)
    from benchmarks import roofline
    for r in roofline.load():
        print(f"roofline/{r['arch']}/{r['shape']},"
              f"{r['roofline_bound_s'] * 1e6:.1f},"
              f"dominant={r['dominant']} useful={r['useful_ratio']}")
    if failed:
        raise SystemExit(f"benchmark phases raised: {failed}")


if __name__ == "__main__":
    main()
