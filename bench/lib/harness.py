"""Builds a cell's deployment through the program's public constructors,
warms it, and serves timed traffic through the unchanged relay runtime
on the wall clock.

The deployment is what ``repro.launch.serve.build_live`` builds for
``--batched --device-pool``: ``RelayGRService`` over ``RelayRuntime``,
the registered ``batched`` executor and the device-resident paged
window of 64-token pages, 2 special + 2 normal instances.  It differs
in what the benchmark must own: the weights (made here from the seed),
the behaviour store's length distribution (the traffic mix's), the
window size (the configuration's share of free device memory) and the
clock (``PacingClock``).
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import spans, spec
from .clock import PacingClock, WindowClosed

# a device without memory statistics (the CPU backend) gets this window
HOST_WINDOW_BYTES = 32e6


@dataclasses.dataclass
class Request:
    uid: int
    prefix_len: int
    due: float                       # rank request due, clock seconds
    done: Optional[float] = None     # scores delivered, clock seconds
    result: Any = None

    def latency_ms(self, closed_at: float) -> float:
        end = self.done if self.done is not None else closed_at
        return (end - self.due) * 1e3


@dataclasses.dataclass
class Deployment:
    svc: Any
    executors: List[Any]
    store: Any
    arch: ModuleType                 # bench/models/<arch>.py
    model_cfg: dict                  # arch.dims of the program's config
    weights: Any
    clock: PacingClock
    log: spans.LaunchLog
    devices: List[Any]
    window_bytes: int
    n_incr: int
    n_items: int
    rank_budget_ms: float
    slack_s: float                   # retrieval + pre-processing
    tracer: Any = None               # the program's Tracer, on; traced runs


def model_module(config: dict) -> ModuleType:
    """The benchmark's module of ``config["model"]["arch"]``:
    ``bench/models/<arch>.py`` with ``-`` as ``_``.  An arch without
    one raises ``FileNotFoundError`` naming the path."""
    arch = config["model"]["arch"]
    return spec.load_file(
        spec.BENCH / "models" / f"{arch.replace('-', '_')}.py",
        "bench_model_", f"benchmark module for model arch {arch!r}")


def model_config(config: dict, rehearse: bool):
    """The program's ModelConfig of ``config["model"]``: every number
    the file gives, set on the arch's own config.  A rehearsal runs the
    arch's reduced smoke model instead."""
    from repro.models import get_config
    m = dict(config["model"])
    arch = m.pop("arch")
    if rehearse:
        return get_config(arch, smoke=True)
    return dataclasses.replace(get_config(arch), **m)


def seed_key(seed: int):
    """A JAX key from any whole number (seeds may exceed 32 bits)."""
    import jax
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(word))


def build(config: dict, traffic: dict, seed: int, rehearse: bool,
          annotate: bool) -> Deployment:
    """The cell's deployment.  ``annotate`` (a traced run) wraps the
    launches in profiler spans and gives the service the program's own
    ``Tracer``, on."""
    arch = model_module(config)
    import jax
    from repro.core import (BatchingConfig, ClusterConfig, GRCostModel,
                            RelayGRService, TriggerConfig, get_executor,
                            relay_config)
    from repro.core.tracing import Tracer
    from repro.data.synthetic import UserBehaviorStore, WorkloadConfig
    from repro.models import build_model, get_config

    dep = config["deployment"]
    cap = config["rehearse"]["history_cap"] if rehearse \
        else config["history_cap"]
    cfg = model_config(config, rehearse)
    dims = arch.dims(cfg)
    model = build_model(cfg)
    weights = arch.make_weights(dims, seed_key(seed))
    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
    if want != got:
        raise ValueError(f"benchmark weights {got} do not match the "
                         f"model's parameters {want}")
    store = UserBehaviorStore(WorkloadConfig(
        vocab=cfg.vocab, n_items=traffic["n_items"],
        incr_len=traffic["incr_len"], len_mu=traffic["history"]["len_mu"],
        len_sigma=traffic["history"]["len_sigma"], max_len=cap))

    n_dev = int(dep.get("chips", 1))
    devices = jax.local_devices()[:n_dev]
    placed = [None] if n_dev == 1 else devices
    cost = GRCostModel(get_config(config["model"]["arch"]))
    # the executor's warm-up keeps its ``max_buckets_live`` most
    # frequent buckets of the lengths it is given; those are the
    # traffic's, so a bound above the grid's size warms all it reaches
    batching = BatchingConfig(max_batch=dep["max_batch"],
                              max_wait_ms=dep["batch_wait_ms"],
                              max_buckets_live=64)
    executors = [get_executor("batched")(
        model, weights, store, cost=cost, batching=batching,
        page_tokens=dep["page_tokens"], device_pool=True, device=d)
        for d in placed]
    log = spans.LaunchLog(annotate)
    for ex in executors:
        log.instrument(ex)
    n_inst = dep["special"] + dep["normal"]
    wbytes = window_bytes(devices, n_inst, dep["window_share"])
    if rehearse:
        wbytes = int(config["rehearse"]["window_bytes"])
    budget = float(dep["rank_budget_ms"])
    relay_cfg = relay_config(
        trigger=TriggerConfig(n_instances=n_inst,
                              r2=dep["special"] / n_inst,
                              rank_p99_budget_ms=budget),
        cluster=ClusterConfig(max_batch=dep["max_batch"],
                              batch_wait_ms=dep["batch_wait_ms"],
                              page_tokens=dep["page_tokens"],
                              device_pool=True, hbm_cache_bytes=wbytes,
                              dram_budget_bytes=float(
                                  dep["dram_bytes_per_instance"])))
    order: Dict[str, int] = {}

    def factory(name):
        return executors[order.setdefault(name, len(order)) % len(executors)]

    clock = PacingClock(annotate)
    tracer = Tracer(on=True) if annotate else None
    svc = RelayGRService(relay_cfg, cost, executor_factory=factory,
                         clock=clock, tracer=tracer)
    pp = svc.runtime.cfg.pipeline
    return Deployment(svc, executors, store, arch, dims, weights, clock,
                      log, devices, wbytes, traffic["incr_len"],
                      traffic["n_items"], budget,
                      (pp.retrieval_ms + pp.preprocess_ms) / 1e3, tracer)


def window_bytes(devices, instances: int, share: float) -> int:
    """Per-instance paged-window budget: ``share`` of the least free
    memory among ``devices``, split over the instances each holds."""
    per_device = -(-instances // len(devices))
    free = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return int(HOST_WINDOW_BYTES)
        free.append(stats["bytes_limit"] - stats["bytes_in_use"])
    return int(min(free) * share) // per_device


def warm(dep: Deployment, prefix_lens: List[int], threads: int = 4
         ) -> Dict[str, int]:
    """Compile (or load from the persistent cache) every program the
    traffic can reach: the rank programs of each bucket and batch size
    and the pool's landing and gather of each prefill grid length (the
    executor's own ``warmup``), and the prefill of each 64-token grid
    length and batch size."""
    from repro.core.types import UserMeta
    from repro.serving.batching import bucket_of, prefill_grid
    ex0 = dep.executors[0]
    max_batch = ex0.batching.max_batch
    sizes = sorted({batch_grid(b, max_batch)
                    for b in range(1, max_batch + 1)})
    pool_pages = dep.window_bytes // ex0.page_layout.page_bytes
    counts = {"buckets": len({bucket_of(n) for n in prefix_lens})}
    for ex in dep.executors:
        ex.warmup(prefix_lens, batch_sizes=range(1, max_batch + 1),
                  incr_len=dep.n_incr, n_items=dep.n_items,
                  pool_pages=pool_pages)
    grids = sorted({prefill_grid(n) for n in prefix_lens})
    counts["prefill_grids"] = len(grids)

    def prefill(job):
        ex, g, b = job
        meta = UserMeta(user_id=0, prefix_len=g, incr_len=dep.n_incr,
                        n_items=dep.n_items)
        ex.pre_infer_group([meta] * b)

    jobs = [(ex, g, b) for ex in dep.executors for g in grids for b in sizes]
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(prefill, jobs))
    return counts


def batch_grid(n: int, max_batch: int) -> int:
    """The batch sizes a launch pads to: powers of two, topped by
    ``max_batch``."""
    b = 1
    while b < n and b < max_batch:
        b *= 2
    return min(b, max_batch)


def serve(dep: Deployment, arrivals, t_start: float,
          deadline: float = float("inf")) -> List[Request]:
    """Schedule ``arrivals`` (offsets from ``t_start``, user ids) and
    drain the runtime until it is empty or the first event due after
    ``deadline``.  Each request's rank stage starts when it falls due
    (arrival + retrieval + pre-processing) and ends when its scores
    reach the sink, both on the wall clock."""
    rt = dep.svc.runtime
    reqs: List[Request] = []
    for a, uid in arrivals:
        meta = dep.store.meta(uid)
        req = Request(uid, meta.prefix_len, t_start + a + dep.slack_s)
        reqs.append(req)
        rt.schedule(t_start + a, "arrival", meta=meta,
                    sink=_sink(dep.clock, req))
    dep.clock.deadline = deadline
    try:
        rt.drain()
    except WindowClosed:
        pass
    dep.clock.deadline = float("inf")
    return reqs


def _sink(clock: PacingClock, req: Request) -> Callable:
    def deliver(result):
        req.done = clock.now()
        req.result = result
    return deliver

