#!/usr/bin/env python3
"""A traced run of one cell with the program's own spans and counters on.

    python3 bench/trace_program.py --workload <cell> --seed <n> \\
        --seconds <s> [--rehearse] [--rate <r>]

(``bench/run.py``'s arguments; ``--trace`` and ``--control`` do nothing
here.)

It builds, warms and serves the cell as ``bench/run.py --trace 1`` does
(same deployment, traffic, pacing clock and profiler window), and also
hands the built runtime a ``repro.core.tracing.Tracer`` that is on.
After the window it prints, as the last line of standard output, one
JSON object: the window's rank-stage p50 and p95, the cell's per-layer
metrics as ``bench/run.py --trace 1`` reads them, the readings of
``bench/lib/program_trace.py`` (rank_wait_ms, rank_deliver_ms,
psi_host_ms, psi_host_mb, rank_pad_share, idle_unattributed_share), the
window's rank counters and ``h2d`` ledger, the idle gaps named by
program spans (the longest of all, and the longest outside
``wait_arrival``), the idle time split by the span the host was in, and
the device time of each jitted program (by ``hlo_module``).  No reference check runs: ``bench/run.py`` decides
``correct``.  ``--rehearse`` runs on any platform with the smoke model,
as in ``bench/run.py``; its numbers are not measurements.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402
from bench.lib import harness, spec, stats, traffic  # noqa: E402
from bench.lib import program_trace as pt  # noqa: E402
from bench.lib import trace as tr  # noqa: E402


def _sum(ledgers) -> dict:
    out: dict = defaultdict(int)
    for ledger in ledgers:
        for k, v in ledger.items():
            out[k] += v
    return out


def _ledgers(dep):
    """(executor counters, pool h2d ledgers) of the deployment, summed."""
    pools = [i.hbm.pool for i in dep.svc.instances.values()
             if getattr(i.hbm, "pool", None) is not None]
    return (_sum(ex.counters for ex in dep.executors),
            _sum(p.h2d for p in pools))


def _less(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def main(argv=None) -> int:
    args = run.parse_args(argv)
    profile_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        line = traced_run(args, profile_dir)
    finally:
        shutil.rmtree(profile_dir, ignore_errors=True)
    if line is None:
        return 2
    print(json.dumps(line), flush=True)
    return 0


def traced_run(args, profile_dir: str):
    """One traced window of ``args.workload``, profiled into
    ``profile_dir``; returns the result object (None without the
    chips the cell needs)."""
    cell = spec.cell(args.workload)
    work, config, mix = cell["workload"], cell["config"], cell["traffic"]

    import jax
    from bench.lib.peaks import peaks_of
    from bench.lib.readings import Run
    from bench.lib.spans import CompileLedger, window_annotation
    from repro.core.tracing import Tracer
    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu"
                              or len(devices) < work["chips"]):
        print(f"trace_program: cell {args.workload} needs {work['chips']} "
              f"TPU chip(s)", file=sys.stderr)
        return None
    if not args.rehearse:
        run.use_compile_cache()
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    peaks = None if args.rehearse else peaks_of(devices[0].device_kind)
    warm_s = float(mix["warmup_seconds"])
    if args.rehearse:
        warm_s = min(warm_s, 1.0)
    seconds = float(args.seconds)
    window = traffic.stream(mix, seconds, mix["base_seed"], args.rate)
    warmup = traffic.stream(mix, warm_s, mix["warmup_base_seed"], args.rate)
    ledger = CompileLedger().install()
    dep = harness.build(config, mix, args.seed, args.rehearse, annotate=True)
    tracer = Tracer(on=True)
    dep.svc.runtime.use_tracer(tracer)
    harness.warm(dep, [dep.store.prefix_len(u) for _, u in window + warmup])
    harness.serve(dep, warmup, dep.clock.now() + 0.05)

    before = _ledgers(dep)
    tracer.clear()
    t_open = dep.clock.now() + 0.05
    jax.profiler.start_trace(profile_dir)
    ledger.recording = dep.log.recording = True
    with window_annotation(True):
        reqs = harness.serve(dep, window, t_open,
                             t_open + seconds + float(mix["grace_seconds"]))
    closed = dep.clock.now()
    ledger.recording = dep.log.recording = False
    jax.profiler.stop_trace()
    counters, h2d = (_less(a, b) for a, b in zip(_ledgers(dep), before))

    lat = [r.latency_ms(closed) for r in reqs]
    completed = [r for r in reqs if r.done is not None]
    staged = sum(s.name == "window.stage" for s in tracer.spans)
    t0 = time.monotonic()
    trace = tr.load(profile_dir)
    program = pt.load(profile_dir)
    reduced = tr.reduce(trace, work["chips"])
    lo, hi = trace.window()
    bench_run = Run(dep.model_cfg, dep.n_incr, dep.n_items,
                    list(dep.log.launches),
                    [r.result.hit.value for r in completed],
                    list(ledger.names), reduced, peaks)
    metrics = {k: v["value"] for k, v in
               spec.read_metrics(cell["per_layer"], bench_run).items()}
    metrics.update({
        "rank_wait_ms": pt.rank_wait_ms(tracer.marks),
        "rank_deliver_ms": pt.rank_deliver_ms(tracer.marks),
        "psi_host_ms": pt.psi_host_ms(tracer.spans),
        "psi_host_mb": pt.psi_host_mb(h2d, staged),
        "rank_pad_share": pt.rank_pad_share(counters),
        "idle_unattributed_share": pt.idle_unattributed_share(
            trace, program, work["chips"])})
    return {
        "workload": args.workload, "seed": args.seed,
        "platform": devices[0].platform, "attempted": len(reqs),
        "completed": len(completed),
        "rank_p50_ms": stats.percentile(lat, 50),
        "rank_p95_ms": stats.percentile(lat, 95),
        "metrics": metrics, "counters": counters, "h2d": h2d,
        "psi_staged": staged, "spans": len(tracer.spans),
        "program_spans_in_trace": len(program),
        "idle_gaps": pt.idle_gaps(trace, program, work["chips"]),
        "idle_by_span": pt.idle_by_span(trace, program, work["chips"]),
        "host_gaps": pt.idle_gaps(trace, program, work["chips"],
                                  skip=("wait_arrival",)),
        "program_device_s": pt.program_device_s(profile_dir, lo, hi),
        "reduce_s": time.monotonic() - t0}


if __name__ == "__main__":
    sys.exit(main())
