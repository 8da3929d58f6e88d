#!/usr/bin/env python3
"""Chip benchmark of the live relay path: one process, one run, one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/``) and a traffic mix
(``bench/traffic/``).  The run builds the deployment through the
program's public constructors, warms every program the cell's traffic
can reach and a few seconds of that traffic, then offers the window's
arrivals on the wall clock for ``--seconds`` and drains for a bounded
grace.  Each request's rank-stage latency runs from the wall time it
fell due (arrival + retrieval + pre-processing) to the wall time its
scores reached the sink.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1``
profiles the window, with the program's own tracer on, prints its
per-layer metrics, and logs the idle time split by the program span
the host was in, the longest idle gaps outside ``wait_arrival`` and
the device time of each jitted program (``phase: program``).  After the
window, a sample of the served scores is compared with the plain
reference (``bench/lib/check.py``); ``correct`` is that comparison.
The model's sizes, weights, reference and work counts come from
``bench/models/<arch>.py`` (``harness.model_module``).  The last line
of standard output is one JSON object.  Without a TPU
(or with fewer chips than the cell asks for) the run exits non-zero
and prints no result.

``--rehearse`` runs the same path on whatever JAX finds, with the
model's reduced smoke configuration and short histories; its result
names the platform it ran on and is not a measurement.  ``--rate``
overrides the mix's offered rate (the knee sweep); ``--control`` also
reads the controls' gaps on the same sample: the reference at each of
the model module's lower precisions (for HSTU ``high``, the next one
down, and ``bf16``), each in the program's place.
"""

from __future__ import annotations

import os
import time

T_START = time.monotonic()
# libtpu logs under /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import check, harness, spec, stats, traffic  # noqa: E402
from bench.lib import program_trace as pt  # noqa: E402


def log(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on any platform with the smoke model; not a "
                         "measurement")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered rate override (knee sweep)")
    ap.add_argument("--control", action="store_true",
                    help="also read the lower-precision controls' gaps")
    return ap.parse_args(argv)


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else the fixed ``<checkout>/.jax_cache``; every program is kept,
    however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices) -> dict:
    d = devices[0]
    peak = 0
    for dev in devices:
        st = dev.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def read_program(dep, before, profile_dir: str, chips: int, hits,
                 compiles, peaks):
    """The traced window's ``Run``: the launches, the program's spans,
    marks and counter differences (``Run.program``) and the profile
    read back.  Logs what the program's record says beyond the
    per-layer metrics: the idle time split by the span the host was in,
    the longest idle gaps outside ``wait_arrival`` and the device time
    of each jitted program."""
    from bench.lib import trace as tr
    from bench.lib.readings import Program, Run
    t0 = time.monotonic()
    counters, h2d = (pt.less(a, b) for a, b in zip(pt.ledgers(dep), before))
    trace = tr.load(profile_dir)
    program = Program(list(dep.tracer.spans), dict(dep.tracer.marks),
                      counters, h2d, pt.load(profile_dir), trace, chips)
    reduced = tr.reduce(trace, chips)
    log(phase="program", counters=counters, h2d=h2d,
        psi_staged=sum(s.name == "window.stage" for s in program.spans),
        spans=len(program.spans), spans_in_trace=len(program.profiled),
        idle_by_span=pt.idle_by_span(trace, program.profiled, chips),
        host_gaps=pt.idle_gaps(trace, program.profiled, chips,
                               skip=("wait_arrival",)),
        program_device_s=reduced.program_s)
    log(phase="trace", seconds=time.monotonic() - t0,
        window_s=reduced.window_s, busy_s=reduced.busy_s)
    return Run(dep.arch, dep.model_cfg, dep.n_incr, dep.n_items,
               list(dep.log.launches), hits, list(compiles), reduced,
               peaks, program)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = spec.cell(args.workload)
    work, config, mix = cell["workload"], cell["config"], cell["traffic"]

    import jax

    import repro.core  # noqa: F401  (no program, no result)
    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu"
                              or len(devices) < work["chips"]):
        print(f"bench: cell {args.workload} needs {work['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 2
    if not args.rehearse:
        log(compile_cache=use_compile_cache())
    # the precision the configuration states for float32 matmuls
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    from bench.lib.peaks import peaks_of
    peaks = None if args.rehearse else peaks_of(devices[0].device_kind)

    seconds = float(args.seconds)
    warm_s = float(mix["warmup_seconds"])
    if args.rehearse:
        warm_s = min(warm_s, 1.0)
    window = traffic.stream(mix, seconds, mix["base_seed"], args.rate)
    warmup = traffic.stream(mix, warm_s, mix["warmup_base_seed"], args.rate)
    from bench.lib.spans import (CompileLedger, batched_tails,
                                 window_annotation)
    ledger = CompileLedger().install()
    dep = harness.build(config, mix, args.seed, args.rehearse,
                        annotate=bool(args.trace))
    lens = [dep.store.prefix_len(u) for _, u in window + warmup]
    t0 = time.monotonic()
    warmed = harness.warm(dep, lens)
    log(phase="warm", seconds=time.monotonic() - t0, **warmed,
        window_bytes_per_instance=dep.window_bytes)
    t0 = time.monotonic()
    wreqs = harness.serve(dep, warmup, dep.clock.now() + 0.05)
    log(phase="warm_traffic", requests=len(wreqs),
        completed=sum(r.done is not None for r in wreqs),
        seconds=time.monotonic() - t0)

    # --- the measured window ------------------------------------------------
    grace = float(mix["grace_seconds"])
    t_open = dep.clock.now() + 0.05
    setup_s = dep.clock.origin + t_open - T_START
    profile_dir = tempfile.mkdtemp(prefix="bench_trace_") \
        if args.trace else None
    if profile_dir:
        before = pt.ledgers(dep)
        dep.tracer.clear()
        jax.profiler.start_trace(profile_dir)
    ledger.recording = dep.log.recording = True
    slept0 = dep.clock.slept_s
    with window_annotation(bool(args.trace)):
        reqs = harness.serve(dep, window, t_open, t_open + seconds + grace)
    closed = dep.clock.now()
    ledger.recording = dep.log.recording = False
    if profile_dir:
        jax.profiler.stop_trace()

    lat = [r.latency_ms(closed) for r in reqs]
    done = [r.done is not None for r in reqs]
    completed = [r for r in reqs if r.done is not None]
    e2e = {"rank_p50_ms": stats.percentile(lat, 50),
           "rank_p95_ms": stats.percentile(lat, 95),
           "slo_goodput_rps": stats.goodput(lat, done, dep.rank_budget_ms,
                                            seconds),
           "setup_s": setup_s}
    hits = [r.result.hit.value for r in completed]
    svc_stats = dep.svc.stats()
    quarter = max(len(lat) // 4, 1)
    log(phase="window", attempted=len(reqs), completed=len(completed),
        p50_first_quarter_ms=stats.percentile(lat[:quarter], 50),
        p50_last_quarter_ms=stats.percentile(lat[-quarter:], 50),
        offered_rps=len(reqs) / seconds,
        rank_p99_ms=stats.percentile(lat, 99), samples=len(lat),
        hits={h: hits.count(h) for h in sorted(set(hits))},
        modelled_reload_ms=sum(r.result.components.get("load", 0.0)
                               for r in completed),
        loop_late_max_ms=dep.clock.max_late_s * 1e3,
        slept_s=dep.clock.slept_s - slept0,
        compiles_in_window=ledger.names,
        batch={n: i.batcher.stats for n, i in dep.svc.instances.items()
               if i.batcher is not None and i.batcher.stats["requests"]},
        h2d=svc_stats.get("h2d"), **e2e)

    run = None
    if args.trace:
        run = read_program(dep, before, profile_dir, work["chips"], hits,
                           ledger.names, peaks)
        import shutil
        shutil.rmtree(profile_dir, ignore_errors=True)

    device = device_info(dep.devices)
    # free the program's state before the reference runs: the sample's
    # scores and tokens come to the host first
    tails = batched_tails(dep.log.launches)
    sample = check.collect(check.pick(completed, int(mix["check_sample"]),
                                      args.seed, tails), dep.store, dep.arch)
    arch, dims, weights = dep.arch, dep.model_cfg, dep.weights
    del dep, reqs, completed, wreqs
    gc.collect()

    t0 = time.monotonic()
    gaps = check.compare(arch, dims, weights, sample)
    limit = float(config["check"]["score_gap_limit"])
    number = max([gaps.get("cached", 0.0), gaps.get("full", 0.0)])
    log(phase="check", seconds=time.monotonic() - t0, **gaps)
    if args.control:
        for served in arch.MODES[1:]:
            ctl = check.compare(arch, dims, weights, sample,
                                served_mode=served)
            log(phase="control", served=served,
                control_score_gap=max(ctl.get("cached", 0.0),
                                      ctl.get("full", 0.0)), **ctl)
    correct = bool(sample) and number <= limit

    line = {"correct": correct, "attempted": len(lat),
            "failed": len(lat) - sum(done), "device": device}
    if args.trace:
        line["metrics"] = spec.read_metrics(cell["per_layer"], run)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {
            "device_ops": run.trace.device_ops,
            "idle_gaps": pt.idle_gaps(run.program.trace,
                                      run.program.profiled, work["chips"])}
    else:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        line["metrics"] = {k: {"value": v, "unit": units[k]}
                           for k, v in e2e.items() if k in units}
    checks = {"score_gap": {"value": number, "limit": limit}}
    line["checks"] = checks
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
