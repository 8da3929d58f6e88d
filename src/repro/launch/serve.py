"""Serving launcher: the end-to-end RelayGR driver (paper's kind).

``python -m repro.launch.serve --requests 200`` boots a live RelayGR
service (real HSTU compute on the local device) at the architecture's
published widths, replays a synthetic request stream through the
shared event-driven relay runtime — retrieval -> trigger -> affinity
routing -> ranking — and reports hit rates + latency components.
``--smoke`` swaps in the reduced same-family model (2 layers, d=64),
which is what the CPU tests and CI smokes run.  ``--sim`` switches to
the virtual-clock cluster simulation at production QPS.  ``--batched``
swaps in the registered ``batched`` executor: rank requests
micro-batch through the per-instance aggregator into single bucketed
jitted launches, with the bucket x batch-size jit entries pre-warmed
from the sampled arrival stream so compiles leave the P99 path.
``--devices N`` spreads the instances over N local devices, one
executor per device, so each instance's psi rests and is ranked on its
own chip.  All modes drive the identical ``RelayRuntime`` state machine
(repro.core.runtime); only the clock and the executor differ.

``chip_smoke.py`` at the checkout root drives ``build_live`` /
``replay`` below on a TPU and checks the scores against a float32
reference.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any, Dict, List

import jax
import numpy as np

from repro.core import (BatchingConfig, ClusterConfig, GRCostModel,
                        LiveExecutor, RelayGRService, TriggerConfig,
                        get_executor, relay_config)
from repro.data.synthetic import (UserBehaviorStore, WorkloadConfig,
                                  request_stream)
from repro.models import build_model, get_config

# the checkout root: src/repro/launch/serve.py -> three levels up
CHECKOUT = Path(__file__).resolve().parents[3]
# live instance pool of the launcher (2 special + 2 normal)
N_INSTANCES = 4
# share of a device's free memory that the paged windows on it may
# take; the rest holds launch activations, warmup buffers and compiler
# scratch
WINDOW_SHARE = 0.5
# a device that reports no memory (the CPU backend) gets a host-friendly
# window: a paged window preallocates its whole page buffer
HOST_WINDOW_BYTES = 128e6


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has
    read it already and nothing is set here.  Otherwise the cache is
    the fixed ``<checkout>/.jax_cache``: the path is part of the cache
    key, so it never depends on a temp dir, pid or time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def window_bytes(devices, instances: int) -> int:
    """Per-instance paged-window budget: ``WINDOW_SHARE`` of the least
    free memory among ``devices``, split over the instances each device
    holds.  Call after the params are placed, so they are counted."""
    per_device = -(-instances // len(devices))
    free = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return int(HOST_WINDOW_BYTES)
        free.append(stats["bytes_limit"] - stats["bytes_in_use"])
    return int(min(free) * WINDOW_SHARE) // per_device


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hstu-gr")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced same-family model (2 layers, "
                         "d=64) instead of the arch's published widths — "
                         "what the CPU tests and CI smokes run")
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--qps", type=float, default=200.0)
    ap.add_argument("--sim", action="store_true",
                    help="cluster-scale discrete-event simulation")
    ap.add_argument("--batched", action="store_true",
                    help="live continuous micro-batching "
                         "(registered 'batched' executor)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--batch-wait-ms", type=float, default=2.0)
    ap.add_argument("--page-tokens", type=int, default=0,
                    help=">0 stores psi in a paged HBM pool and ranks "
                         "through the rank_with_pages path")
    ap.add_argument("--segments", action="store_true",
                    help="beyond-prefix reuse: the stream attaches per-"
                         "user candidate-independent seg_lens and the "
                         "side path caches them alongside the prefix "
                         "(implies a paged window; defaults "
                         "--page-tokens to 64 when unset)")
    ap.add_argument("--device-pool", action="store_true",
                    help="keep the paged KV pool device-resident: "
                         "inserts/reloads scatter only fresh pages "
                         "(donated in-place update) and rank launches "
                         "pass the pool by reference — per-launch H2D "
                         "re-ship drops to zero (implies a paged "
                         "window; defaults --page-tokens to 64 when "
                         "unset)")
    ap.add_argument("--devices", type=int, default=1,
                    help="live mode: spread the instances round-robin "
                         "over the first N local devices, one executor "
                         "per device holding its params, inputs and "
                         "page pool (1: JAX's default device)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="stripe the instance pools over N hosts; keyed "
                         "traffic routes owner-map -> per-host ring")
    ap.add_argument("--prefill-hosts", type=int, default=0,
                    help=">0 disaggregates the pre-infer side path onto "
                         "dedicated hosts; psi ships cross-host to its "
                         "owning rank instance over the NIC fabric")
    ap.add_argument("--cold-budget", type=float, default=0.0,
                    help=">0 adds a host-local cold tier (SSD / remote "
                         "psi store) of this many bytes under DRAM: "
                         "evictions demote instead of dropping, and a "
                         "cold-resident user's admission starts an async "
                         "cold->DRAM promotion")
    ap.add_argument("--dram-budget", type=float, default=500e9,
                    help="per-host DRAM expander budget in bytes")
    ap.add_argument("--tenants", type=int, default=1,
                    help=">1 serves N tenants off the one fleet: every "
                         "memory tier is partitioned into per-tenant "
                         "byte/page quotas (a tenant can only evict its "
                         "own entries), admission gets per-tenant token "
                         "buckets, and stats report per-tenant ledgers")
    args = ap.parse_args(argv)
    if (args.segments or args.device_pool) and not args.page_tokens:
        args.page_tokens = 64  # segment spans / device pool need pages
    return args


@dataclasses.dataclass
class LiveService:
    """A built live deployment, ready to replay its arrivals."""
    args: argparse.Namespace
    svc: RelayGRService
    model: Any
    params: Any                  # as initialised, before any placement
    store: UserBehaviorStore
    arrivals: List[tuple]        # (t, UserMeta), the replayed stream
    executors: List[Any]         # one per device on the batched path
    window_bytes: int            # per-instance HBM window budget
    warmed: List[tuple] = dataclasses.field(default_factory=list)


def build_live(args: argparse.Namespace) -> LiveService:
    """Model, params, store, executors and service of the live path —
    everything ``replay`` needs, with the batched jit grid warmed."""
    devices = jax.local_devices()
    if not 1 <= args.devices <= len(devices):
        raise ValueError(f"--devices {args.devices}: {len(devices)} local "
                         f"devices")
    devices = devices[:args.devices]
    # one device keeps JAX's default placement; several bind each
    # executor to its own device
    placed = [None] if args.devices == 1 else devices
    cfg = get_config(args.arch, smoke=args.smoke)
    cost = GRCostModel(get_config(args.arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    store = UserBehaviorStore(WorkloadConfig(
        vocab=cfg.vocab, n_items=64, incr_len=16, len_mu=6.8, len_sigma=0.9,
        max_len=2048))
    arrivals = []
    for i, (t, meta) in enumerate(request_stream(
            store, args.qps, 1e9, refresh_prob=0.2,
            segments=args.segments, tenants=args.tenants)):
        if i >= args.requests:
            break
        arrivals.append((t, meta))

    executors: List[Any] = []
    if args.batched:
        # one shared executor per device -> one jit cache per device
        executors = [get_executor("batched")(
            model, params, store, cost=cost,
            batching=BatchingConfig(max_batch=args.max_batch,
                                    max_wait_ms=args.batch_wait_ms),
            page_tokens=args.page_tokens, segments=args.segments,
            device_pool=args.device_pool, device=d) for d in placed]

    # a paged window preallocates its pool buffer (fixed pages, zero
    # fragmentation), so it is sized from the memory the device reports
    hbm_bytes = (window_bytes(devices, N_INSTANCES) if args.page_tokens
                 else int(16e9))
    relay_cfg = relay_config(
        trigger=TriggerConfig(n_instances=N_INSTANCES, r2=0.5,
                              rank_p99_budget_ms=20.0),
        cluster=ClusterConfig(max_batch=args.max_batch if args.batched
                              else 0,
                              batch_wait_ms=args.batch_wait_ms,
                              page_tokens=args.page_tokens,
                              segments=args.segments,
                              device_pool=args.device_pool,
                              hosts=args.hosts,
                              prefill_hosts=args.prefill_hosts,
                              hbm_cache_bytes=hbm_bytes,
                              dram_budget_bytes=args.dram_budget,
                              cold_budget_bytes=args.cold_budget,
                              tenants=args.tenants))

    warmed: List[tuple] = []
    if executors:
        # pre-warm the (bucket, batch) grid the sampled stream will hit;
        # the executor owns the page geometry, so the pool size derived
        # from ITS layout keeps the warmed rank_with_pages jit key
        # (pool-buffer shape) identical to the serving store's
        pool_pages = (hbm_bytes // executors[0].page_layout.page_bytes
                      if args.page_tokens else 0)

        def warm(ex):
            return ex.warmup([m.prefix_len for _, m in arrivals],
                             batch_sizes=range(1, args.max_batch + 1),
                             incr_len=store.cfg.incr_len,
                             n_items=store.cfg.n_items,
                             pool_pages=pool_pages)

        # one compile per device and program: devices compile in
        # parallel threads (the backend compile releases the GIL)
        with ThreadPoolExecutor(len(executors)) as pool:
            warmed = [k for keys in pool.map(warm, executors) for k in keys]

    order: Dict[str, int] = {}

    def factory(name):
        # instances spread round-robin over the devices in creation order
        i = order.setdefault(name, len(order)) % len(placed)
        if executors:
            return executors[i]
        # per-request path: one executor per instance (its own jit cache)
        return LiveExecutor(model, params, store,
                            page_tokens=args.page_tokens,
                            segments=args.segments,
                            device_pool=args.device_pool, device=placed[i])

    svc = RelayGRService(relay_cfg, cost, executor_factory=factory)
    return LiveService(args, svc, model, params, store, arrivals, executors,
                       hbm_bytes, warmed)


def replay(live: LiveService) -> list:
    """Serve the arrival stream; returns the RankResults in completion
    order (batched) or arrival order (per-request)."""
    results = []
    if live.args.batched:
        rt = live.svc.runtime
        for t, meta in live.arrivals:
            rt.schedule(t, "arrival", meta=meta, sink=results.append)
        rt.drain()
    else:
        for t, meta in live.arrivals:
            results.append(live.svc.submit(meta, now=t))
    return results


def report(results) -> Dict[str, int]:
    hits, lat = {}, []
    for r in results:
        if abs(r.latency_ms - sum(r.components.values())) >= 1e-6:
            raise AssertionError(f"latency {r.latency_ms} != sum of "
                                 f"components {r.components}")
        hits[r.hit.value] = hits.get(r.hit.value, 0) + 1
        lat.append(r.components["rank"])
    print(f"requests={len(results)} hits={hits}")
    print(f"rank compute ms: p50={np.percentile(lat, 50):.1f} "
          f"p99={np.percentile(lat, 99):.1f}")
    return hits


def report_tenants(svc, tenants: int) -> None:
    if tenants <= 1:
        return
    ten = svc.stats()["tenants"]
    print(json.dumps({"tenants": ten}, indent=1))
    # isolation invariants the live smoke leans on: every tenant's
    # admission ledger saw traffic, and no tenant ever evicted another
    # tenant's entry out of any tier
    assert ten["cross_tenant_evictions"] == 0, (
        f"tenant partition violated: "
        f"{ten['cross_tenant_evictions']} cross-tenant evictions")
    assert all(ten["admission"].get(t, {}).get("assessed", 0) > 0
               for t in range(tenants)), (
        f"per-tenant admission ledger not populated: {ten['admission']}")


def report_h2d(svc, args) -> None:
    if not args.page_tokens:
        return
    h2d = svc.stats()["h2d"]
    print(json.dumps({"h2d": h2d}, indent=1))
    if args.device_pool:
        # the whole point of the device-resident pool: rank launches
        # pass the pool by reference, so a single re-ship is a wiring
        # regression
        assert h2d["device_resident"], "device pool not wired"
        assert h2d["launch_reships"] == 0, (
            f"device-pool launch re-shipped the pool "
            f"{h2d['launch_reships']}x")
        assert h2d["bytes_scattered"] > 0


def main(argv=None):
    args = parse_args(argv)
    use_compile_cache()
    if args.sim:
        from repro.serving.simulator import run_sim
        store = UserBehaviorStore()
        arr = request_stream(store, args.qps, args.requests / args.qps,
                             segments=args.segments, tenants=args.tenants)
        s = run_sim(relay_config(
            trigger=TriggerConfig(n_instances=10),
            cluster=ClusterConfig(hosts=args.hosts,
                                  prefill_hosts=args.prefill_hosts,
                                  page_tokens=args.page_tokens,
                                  segments=args.segments,
                                  device_pool=args.device_pool,
                                  dram_budget_bytes=args.dram_budget,
                                  cold_budget_bytes=args.cold_budget,
                                  tenants=args.tenants)),
            GRCostModel(get_config(args.arch)), arr)
        print(json.dumps(s, indent=1))
        return s

    live = build_live(args)
    print(f"model={live.model.cfg.name} devices={args.devices} "
          f"window_bytes={live.window_bytes}/instance")
    if live.warmed:
        print(f"warmed {len(live.warmed)} (bucket, batch) jit entries: "
              f"{sorted({k[:2] for k in live.warmed})}")
    results = replay(live)
    hits = report(results)
    svc = live.svc
    if args.batched:
        batch = {n: i.batcher.stats for n, i in svc.instances.items()
                 if i.batcher is not None and i.batcher.stats["requests"]}
        print(json.dumps({"batch": batch}, indent=1))
    else:
        print(json.dumps(svc.stats()["trigger"], indent=1))
        if args.prefill_hosts:
            print(json.dumps({"shipping": svc.stats()["shipping"]},
                             indent=1))
        if args.cold_budget:
            print(json.dumps({"cold": svc.stats()["cold"]}, indent=1))
    report_tenants(svc, args.tenants)
    report_h2d(svc, args)
    return hits


if __name__ == "__main__":
    main()
