"""Bring-up smoke of the live relay path on a TPU at hstu_gr's widths.

    python chip_smoke.py             one chip
    python chip_smoke.py --chips 4   four rank instances, one per chip

One chip: serves a synthetic stream through ``repro.launch.serve`` —
the ``batched`` executor over the device-resident paged window, the
published hstu_gr model (8 layers, d=256, 4x64 heads, fp32) with
random weights from a fixed seed — then checks the served scores
against a float32 reference computed on the host CPU, and checks on
the chip that ranking from cached psi equals full inference.

``--chips 4``: the same stream with each live instance bound to its
own chip behind the affinity router.  Checks that every psi is ranked
on the chip it rests on, and that the scores match the same requests
recomputed on one chip.  No other phase runs.

The last line of standard output is one JSON object naming the device,
printed only when every phase passed.  Where JAX finds no TPU the
script exits non-zero before serving anything.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# the served configuration: the batched executor over the device-resident
# paged window; --max-batch 2 keeps the warmed (bucket, batch) grid small
# enough to compile in a few minutes cold
SERVE_ARGS = ["--batched", "--device-pool", "--requests", "32",
              "--qps", "400", "--max-batch", "2"]
MIN_REQUESTS = 32
# The chip runs fp32 matmuls at its default precision, which rounds
# their inputs to bfloat16 (8 mantissa bits), while the reference runs
# them in float32 ("highest").  Scores are compared by
#   max |served - reference| / max |reference|
# per request.  Measured on TPU v5e: 1.7e-2 for hits, 1.8e-2 for
# misses (the worst of 16 requests each); the bound leaves ~3x margin.
SCORE_TOL = 5e-2
# ranking from cached psi vs full inference, both on the chip at its
# default precision: the same arithmetic in two programs.  Measured on
# TPU v5e: 0.0 (bit-identical); the bound admits a reordered reduction,
# not a change of precision.
RELAY_TOL = 1e-5
RELAY_PREFIX = 2048


def log(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


class CompileLedger:
    """Backend compiles seen through JAX's monitoring events.  A
    persistent-cache hit also passes through the compile event (with
    the short retrieval time) and is counted apart."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.events = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def on_duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.events += 1
            self.seconds += duration

    def on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def summary(self):
        return {"compiles": self.events - self.cache_hits,
                "cache_hits": self.cache_hits,
                "compile_s": round(self.seconds, 1)}


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def is_cached(result) -> bool:
    return result.hit.value != "miss"


# --- phases --------------------------------------------------------------------


def serve_phase(serve_argv, compiles=None):
    """Build and replay the live deployment through the launcher's own
    code; returns (LiveService, results)."""
    import jax
    from repro.launch import serve
    args = serve.parse_args(serve_argv)
    t0 = time.perf_counter()
    live = serve.build_live(args)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = serve.replay(live)
    t_serve = time.perf_counter() - t0
    hits = serve.report(results)
    serve.report_h2d(live.svc, args)
    h2d = live.svc.stats()["h2d"]
    stats = jax.local_devices()[0].memory_stats() or {}
    rank_ms = [r.components["rank"] for r in results]
    log(phase="serve", model=live.model.cfg.name, requests=len(results),
        hits=hits, window_bytes_per_instance=live.window_bytes,
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"),
        bytes_limit=stats.get("bytes_limit", "not reported"),
        launch_reships=h2d["launch_reships"],
        bytes_scattered=h2d["bytes_scattered"],
        warmed=len(live.warmed), build_s=round(t_build, 1),
        serve_s=round(t_serve, 1),
        **(compiles.summary() if compiles else {}),
        informational_rank_ms={"p50": float(np.percentile(rank_ms, 50)),
                               "p99": float(np.percentile(rank_ms, 99)),
                               "note": "not a benchmark metric"})
    if len(results) < MIN_REQUESTS:
        fail(f"served {len(results)} < {MIN_REQUESTS} requests")
    if not hits.get("hbm_hit") or not hits.get("miss"):
        fail(f"need at least one hbm_hit and one miss, got {hits}")
    if h2d["launch_reships"] != 0 or not h2d["device_resident"]:
        fail(f"device pool not resident: {h2d}")
    return live, results


def reference_phase(live, results):
    """Served scores against a float32 reference on the host CPU: the
    same params and tokens, recomputed by the model's own forward
    functions under ``default_matmul_precision("highest")``.

    A hit ranks against psi of the history tiled to the 64-token
    prefill grid, zero-padded to the rank bucket; a miss runs full
    inference over the history tiled to the bucket.  The reference
    rebuilds exactly those inputs (ROADMAP R2 is about the tiling
    itself)."""
    import jax
    import jax.numpy as jnp
    from repro.serving.batching import bucket_of, prefill_grid
    cpu = jax.devices("cpu")[0]
    model, store = live.model, live.store
    params = jax.device_put(live.params, cpu)
    metas = {m.user_id: m for _, m in live.arrivals}
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t})[1])
    rank = jax.jit(model.rank_with_cache)
    full = jax.jit(model.full_rank)

    def host(x):
        return jax.device_put(np.asarray(x)[None], cpu)

    errs = {"cached": [], "miss": []}
    t0 = time.perf_counter()
    for r in results:
        kind = "cached" if is_cached(r) else "miss"
        plen = metas[r.user_id].prefix_len
        bucket = bucket_of(plen)
        hist = store.long_term(r.user_id)
        incr = host(store.short_term(r.user_id))
        items = host(store.candidates(r.user_id))
        with jax.default_matmul_precision("highest"):
            if kind == "cached":
                k, v = prefill(params, host(np.resize(hist,
                                                      prefill_grid(plen))))
                pad = ((0, 0), (0, 0), (0, bucket - k.shape[2]), (0, 0),
                       (0, 0))
                want = rank(params, (jnp.pad(k, pad), jnp.pad(v, pad)),
                            incr, items)
            else:
                want = full(params, host(np.resize(hist, bucket)), incr,
                            items)
        errs[kind].append(rel_err(r.scores, np.asarray(want)[0]))
    worst = {k: max(v) if v else None for k, v in errs.items()}
    log(phase="reference", checked={k: len(v) for k, v in errs.items()},
        max_rel_err=worst, tol=SCORE_TOL,
        reason="TPU default precision rounds fp32 matmul inputs to "
               "bfloat16; the reference runs float32 on the host CPU",
        seconds=round(time.perf_counter() - t0, 1))
    if not errs["cached"] or not errs["miss"]:
        fail(f"reference needs both hit classes, checked {errs}")
    if max(worst.values()) > SCORE_TOL:
        fail(f"served scores off the float32 reference: {worst} > "
             f"{SCORE_TOL}")


def relay_phase(live, n_prefix=RELAY_PREFIX):
    """On the chip: ranking from cached psi equals full inference (the
    paper's eps-equivalence, examples/quickstart.py) at the widths
    served, over a ``n_prefix``-token history."""
    import jax
    model, store, params = live.model, live.store, live.params
    uid = live.arrivals[0][1].user_id
    prefix = np.resize(store.long_term(uid), n_prefix)[None]
    incr = store.short_term(uid)[None]
    items = store.candidates(uid)[None]
    _, psi = jax.jit(model.prefill)(params, {"tokens": prefix})
    cached = jax.jit(model.rank_with_cache)(params, psi, incr, items)
    full = jax.jit(model.full_rank)(params, prefix, incr, items)
    err = rel_err(cached, full)
    log(phase="relay", n_prefix=n_prefix, max_rel_err=err, tol=RELAY_TOL,
        reason="two programs, same arithmetic at the chip's default "
               "precision")
    if not np.isfinite(np.asarray(full)).all() or err > RELAY_TOL:
        fail(f"cached path off full inference: {err} > {RELAY_TOL}")


def watch_psi(executors, seen):
    """Record, for every rank launch that consumes psi, the launching
    executor's device and the devices the psi lives on."""
    import jax
    from repro.core.paging import PagedPsi

    def where(psi):
        arrays = ([psi.pool.device_buffer] if isinstance(psi, PagedPsi)
                  else jax.tree.leaves(psi))
        return {d for a in arrays for d in a.devices()}

    for ex in executors:
        def rank_group(group, _ex=ex, _inner=ex.rank_group):
            for w in group:
                if w.psi is not None:
                    seen.append((_ex.device, where(w.psi)))
            return _inner(group)

        def rank_cached(meta, psi, _ex=ex, _inner=ex.rank_cached):
            seen.append((_ex.device, where(psi)))
            return _inner(meta, psi)

        ex.rank_group, ex.rank_cached = rank_group, rank_cached


def four_instance_phase(serve_argv, n=4, compiles=None):
    """Each live instance on its own device behind the affinity router:
    every psi is ranked on the device it rests on, and the scores match
    the same requests recomputed on one device (the one-chip path)."""
    import jax
    from repro.launch import serve
    if len(jax.local_devices()) < n:
        fail(f"--chips {n} needs {n} local devices, found "
             f"{len(jax.local_devices())}")
    args = serve.parse_args(list(serve_argv) + ["--devices", str(n)])
    t0 = time.perf_counter()
    live = serve.build_live(args)
    t_build = time.perf_counter() - t0
    seen = []
    watch_psi(live.executors, seen)
    results = serve.replay(live)
    hits = serve.report(results)
    serve.report_h2d(live.svc, args)
    placement = {name: str(inst.executor.device)
                 for name, inst in live.svc.instances.items()}
    off = [(str(d), sorted(map(str, where))) for d, where in seen
           if where != {d}]
    ranked_on = sorted({str(d) for d, _ in seen})
    log(phase="four_instances", requests=len(results), hits=hits,
        placement=placement, psi_ranks=len(seen), psi_ranked_on=ranked_on,
        psi_off_device=len(off), build_s=round(t_build, 1),
        **(compiles.summary() if compiles else {}))
    if len(set(placement.values())) != n:
        fail(f"instances not spread over {n} devices: {placement}")
    if not seen or off:
        fail(f"psi ranked off its device ({off}) or never ({len(seen)})")
    if live.svc.stats()["h2d"]["launch_reships"] != 0:
        fail("device-pool launch re-shipped the pool")
    # the one-chip path: each request recomputed by device 0's executor
    one = live.executors[0]
    metas = {m.user_id: m for _, m in live.arrivals}
    errs = {"cached": [], "miss": []}
    for r in results:
        meta = metas[r.user_id]
        if is_cached(r):
            psi, _, _ = one.pre_infer(meta)
            want, _ = one.rank_cached(meta, psi)
            errs["cached"].append(rel_err(r.scores, np.asarray(want)[0]))
        else:
            want, _ = one.rank_full(meta)
            errs["miss"].append(rel_err(r.scores, np.asarray(want)[0]))
    worst = {k: max(v) if v else None for k, v in errs.items()}
    log(phase="four_vs_one", checked={k: len(v) for k, v in errs.items()},
        max_rel_err=worst, tol=SCORE_TOL)
    if not errs["cached"] or not errs["miss"]:
        fail(f"comparison needs both hit classes: {hits}")
    if max(worst.values()) > SCORE_TOL:
        fail(f"four-device scores off the one-device path: {worst}")


# --- entry point ---------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: four rank instances, one per chip, and no "
                         "other phase")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch import serve
    log(phase="device", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()),
        compile_cache=serve.use_compile_cache())
    compiles = CompileLedger().install()
    if args.chips == 4:
        four_instance_phase(SERVE_ARGS, 4, compiles)
    else:
        live, results = serve_phase(SERVE_ARGS, compiles)
        reference_phase(live, results)
        relay_phase(live)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
