"""Spans, marks and counters of the serving path (repro.core.tracing).

  * off (the default), the live relay path constructs no
    ``TraceAnnotation`` and records nothing;
  * on, spans nest as relay.event -> exec.rank / exec.prefill ->
    exec.prepare / exec.put / exec.wait, the spans and marks of one
    request carry its ids, and self time is duration less children;
  * the page pool's ``h2d`` ledger counts the host side of psi's trip
    (device-to-host pull, host mirror, dense copies) in the shapes'
    bytes, and the executor's counters the padding of a rank launch;
  * the jitted programs of the served path carry stable names.
"""

import sys
import threading

import jax
import numpy as np
import pytest

from repro.core import (OFF, BatchingConfig, ClusterConfig, GRCostModel,
                        PageLayout, PendingRank, RelayGRService, Tracer,
                        TriggerConfig, UserMeta, get_executor, relay_config)
from repro.core.cache import PagedHBMStore, kv_nbytes
from repro.core.expander import DRAMExpander, ExpanderConfig
from repro.core.paging import _gather_jit, _land_jit, _scatter_jit
from repro.models import get_config

PT = 32


@pytest.fixture(scope="module")
def live():
    from repro.data.synthetic import UserBehaviorStore, WorkloadConfig
    from repro.models import build_model
    cfg = get_config("hstu_gr", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    store = UserBehaviorStore(WorkloadConfig(
        vocab=cfg.vocab, n_items=16, incr_len=8, max_len=300))
    return cfg, model, params, store


def _executor(live, max_batch=4):
    cfg, model, params, store = live
    return get_executor("batched")(
        model, params, store, cost=GRCostModel(cfg),
        batching=BatchingConfig(max_batch=max_batch, max_wait_ms=2.0),
        page_tokens=PT, device_pool=True)


def _service(live, tracer=None):
    cfg = live[0]
    layout = PageLayout.from_model_config(cfg, PT)
    budget = 64 * layout.entry_bytes(512)
    ex = _executor(live)
    rcfg = relay_config(
        trigger=TriggerConfig(n_instances=2, r2=0.5, kv_p99_len=512,
                              hbm_bytes=budget / 0.5, r1=0.5,
                              t_life_s=5.0, q_m=1e4),
        cluster=ClusterConfig(hbm_cache_bytes=budget,
                              dram_budget_bytes=8 * budget, max_batch=4,
                              page_tokens=PT, device_pool=True,
                              trigger_policy="admit-all",
                              long_seq_threshold=1))
    svc = RelayGRService(rcfg, GRCostModel(cfg), executor_factory=lambda n: ex,
                         tracer=tracer)
    return svc, ex


def _serve(live, svc, uids=(300, 301, 302, 300)):
    store = live[3]
    out, t = [], 0.0
    for uid in uids:
        meta = UserMeta(user_id=uid,
                        prefix_len=int(store.long_term(uid).shape[0]),
                        incr_len=8, n_items=16)
        out.append(svc.runtime.submit(meta, now=t))
        t += 0.3
    return out


@pytest.fixture
def counted(monkeypatch):
    """Counts TraceAnnotation constructions."""
    made = []
    real = jax.profiler.TraceAnnotation

    def annotation(name, **kw):
        made.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    return made


@pytest.fixture(scope="module")
def traced(live):
    tracer = Tracer(on=True)
    svc, ex = _service(live, tracer)
    results = _serve(live, svc)
    return tracer, svc, ex, results


def test_tracing_off_constructs_no_annotation_and_records_nothing(
        live, counted):
    svc, ex = _service(live)
    results = _serve(live, svc)
    assert len(results) == 4 and all(r.scores is not None for r in results)
    assert svc.runtime.tracer is OFF and ex.tracer is OFF
    assert counted == []
    assert OFF.spans == [] and OFF.marks == {}
    # counters count whether or not tracing is on
    assert ex.counters["rank_rows"] == 4


def test_tracing_on_writes_one_annotation_per_span(live, counted):
    tracer = Tracer(on=True)
    svc, _ = _service(live, tracer)
    _serve(live, svc, uids=(310, 311))
    assert counted == [s.name for s in tracer.spans]
    assert {"relay.event", "exec.prefill", "exec.rank", "exec.wait",
            "window.stage", "window.scatter", "relay.sink"} <= set(counted)


def test_spans_nest_event_exec_prepare_put_wait(traced):
    tracer = traced[0]
    spans = tracer.spans
    names = {s.name for s in spans}
    assert {"relay.event", "exec.rank", "exec.prefill", "exec.prepare",
            "exec.put", "exec.wait", "window.stage", "window.scatter",
            "window.materialize", "dram.spill", "relay.sink"} <= names
    for s in spans:
        parent = spans[s.parent].name if s.parent >= 0 else None
        if s.name == "relay.event":
            assert parent is None
        elif s.name in ("exec.rank", "exec.prefill"):
            assert parent == "relay.event"
        elif s.name in ("exec.prepare", "exec.put", "exec.wait"):
            assert parent in ("exec.rank", "exec.prefill")
        elif s.name == "window.materialize":
            assert parent == "dram.spill"
        else:
            assert parent == "relay.event", (s.name, parent)
        assert s.t0 <= s.t1
        if s.parent >= 0:
            assert spans[s.parent].t0 <= s.t0 and s.t1 <= spans[s.parent].t1


def test_spans_and_marks_of_a_request_share_its_ids(traced):
    tracer, _, _, results = traced
    spans = tracer.spans
    for r in results:
        marks = tracer.marks[r.req_id]
        assert marks["due"] <= marks["launch"] <= marks["launched"] \
            <= marks["sink"]
        sinks = [s for s in spans if s.name == "relay.sink"
                 and s.args["req"] == r.req_id]
        assert len(sinks) == 1 and sinks[0].args["uid"] == r.user_id
        assert any(s.name == "exec.rank" and r.user_id in s.args["uids"]
                   for s in spans)
        assert any(s.name == "relay.event" and s.args.get("req") == r.req_id
                   and s.args["kind"] == "job_start" for s in spans)
    for s in spans:
        if s.name == "exec.rank":
            assert s.args["rows"] == len(s.args["uids"]) \
                == len(s.args["lens"])


def test_self_time_is_duration_less_children(traced):
    tracer = traced[0]
    spans = tracer.spans
    for i, s in enumerate(spans):
        kids = [spans[c] for c in range(len(spans)) if spans[c].parent == i]
        want = s.seconds - sum(k.seconds for k in kids)
        assert tracer.self_seconds(i) == pytest.approx(want, abs=1e-12)
        assert tracer.self_seconds(i) >= -1e-9


def _ones_psi(cfg, tokens, fill=1.0):
    shape = (cfg.n_layers, 1, tokens, cfg.n_heads, cfg.head_dim)
    return (jax.numpy.full(shape, fill, jax.numpy.float32),
            jax.numpy.full(shape, 2 * fill, jax.numpy.float32))


def test_h2d_ledger_counts_insert_spill_and_reload(live):
    cfg = live[0]
    layout = PageLayout.from_model_config(cfg, PT)
    store = PagedHBMStore(16 * layout.entry_bytes(256), layout,
                          device_pool=True)
    dram = DRAMExpander(ExpanderConfig(dram_budget_bytes=1e9))
    L = 100                                    # tokens, page-unaligned
    psi = _ones_psi(cfg, L)
    entry_bytes = layout.entry_bytes(L)
    h2d = store.pool.h2d

    # psi already on the device lands from there: no pull, no mirror
    store.insert(7, psi, kv_nbytes(psi), 0.0, prefix_len=L)
    assert h2d["d2h_bytes"] == 0
    assert h2d["mirror_bytes"] == 0
    assert h2d["bytes_scattered"] == entry_bytes
    assert h2d["device_sourced_bytes"] == h2d["bytes_scattered"]
    assert h2d["materialized_bytes"] == 0
    assert store.buffer is None

    # the spill's dense copy is one pull off the device
    entry = store.consume(7)
    assert dram.spill(entry)
    assert h2d["materialized_bytes"] == entry_bytes
    assert h2d["d2h_bytes"] == entry_bytes
    k, v = dram.entries[7].value
    assert isinstance(k, np.ndarray) and k.nbytes + v.nbytes == entry_bytes
    assert (k[:, :, :L] == 1.0).all() and not k[:, :, L:].any()
    assert (v[:, :, :L] == 2.0).all() and not v[:, :, L:].any()
    entry.dram_backed = True                 # as the runtime marks it
    store.pop(7)                             # so leaving copies nothing
    assert h2d["materialized_bytes"] == entry_bytes

    dram.flight.begin(7)
    dram.complete_reload(7, store, 1.0)      # a host copy: one put
    assert store.resident(7) is not None
    assert h2d["d2h_bytes"] == entry_bytes
    assert h2d["mirror_bytes"] == 0
    assert h2d["bytes_scattered"] == 2 * entry_bytes
    assert h2d["device_sourced_bytes"] == entry_bytes
    assert h2d["materialized_bytes"] == entry_bytes
    back = store.entries[7].value.materialize()
    assert all(a.tobytes() == b.tobytes() for a, b in zip(back, (k, v)))
    assert store.buffer is None


def test_h2d_ledger_of_a_host_pool_counts_its_page_buffer(live):
    """A host-buffer pool stages psi through its page buffer: a device
    value is pulled (``d2h_bytes``) and sliced in (``mirror_bytes``),
    and nothing lands on a device."""
    cfg = live[0]
    layout = PageLayout.from_model_config(cfg, PT)
    store = PagedHBMStore(16 * layout.entry_bytes(256), layout)
    L = 100
    psi = _ones_psi(cfg, L)
    store.insert(7, psi, kv_nbytes(psi), 0.0, prefix_len=L)
    h2d = store.pool.h2d
    assert h2d["d2h_bytes"] == kv_nbytes(psi)
    assert h2d["mirror_bytes"] == layout.entry_bytes(L)
    assert h2d["bytes_scattered"] == h2d["device_sourced_bytes"] == 0
    assert store.buffer is not None


def test_resumed_reload_keeps_the_resident_head_pages(live):
    """A resumed reload lands only the missing tail: the head pages'
    indices point past the pool's end and the update drops them, so
    their device bytes stay as they were even when the reloaded value
    differs there — and the null page stays zero."""
    cfg = live[0]
    layout = PageLayout.from_model_config(cfg, PT)
    L = 4 * PT                                 # 4 pages per slab
    store = PagedHBMStore(layout.entry_bytes(L), layout, device_pool=True)
    psi = _ones_psi(cfg, L, fill=1.0)
    store.insert(1, psi, kv_nbytes(psi), 0.0, prefix_len=L)
    store.consume(1)
    store.entries[1].dram_backed = True
    other = _ones_psi(cfg, PT, fill=5.0)       # pressure: tail-evicts 1
    store.insert(2, other, kv_nbytes(other), 1.0, prefix_len=PT)
    e = store.entries[1]
    assert store.stats["partial_evictions"] == 1
    assert 0 < e.tokens_resident < L
    head = e.page_table[:, :layout.pages_per_slab(e.tokens_resident)]
    before = np.asarray(store.pool.device_buffer)[head.reshape(-1)].copy()
    store.pop(2)
    scattered = store.pool.h2d["pages_scattered"]
    reload = tuple(np.asarray(a) * 3.0 for a in psi)   # differs everywhere
    store.insert(1, reload, kv_nbytes(reload), 2.0, prefix_len=L)
    assert store.stats["resumed_reloads"] == 1
    pool = store.pool
    dev = np.asarray(pool.device_buffer)
    assert dev[head.reshape(-1)].tobytes() == before.tobytes()
    tail = e.page_table[:, head.shape[1]:].reshape(-1)
    assert pool.h2d["pages_scattered"] - scattered == tail.size
    assert set(np.unique(dev[tail])) <= {3.0, 6.0}
    assert not dev[pool.n_pages].any(), "null page must stay zero"


def test_warmup_leaves_served_landing_and_spill_without_compiles(live):
    """After ``warmup`` with the pool's size, inserting a prefill's psi
    of every warmed length, spilling it and reloading the host copy
    compile nothing (JAX's backend-compile event, as the benchmark's
    ``CompileLedger`` counts them)."""
    cfg, _, _, behaviour = live
    ex = _executor(live)
    layout = ex.page_layout
    lens = [40, 100, 130]                      # grids 64, 128, 192
    pool_pages = 3 * layout.entry_pages(192)
    ex.warmup(lens, pool_pages=pool_pages, incr_len=8, n_items=16)
    store = PagedHBMStore(pool_pages * layout.page_bytes, layout,
                          device_pool=True)
    store.device_hooks = ex
    psis = {n: ex.pre_infer(UserMeta(user_id=400 + n, prefix_len=n,
                                     incr_len=8, n_items=16))[0]
            for n in lens}
    store.pool.ensure_device((PT, cfg.n_heads * cfg.head_dim),
                             np.float32, ex.device)
    compiled = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for i, (n, psi) in enumerate(psis.items()):
            store.insert(n, psi, kv_nbytes(psi), float(i), prefix_len=n)
            dense = store.consume(n).value.materialize()
            store.pop(n)
            store.insert(n, dense, kv_nbytes(dense), float(i), prefix_len=n)
            store.pop(n)
    finally:
        jax.monitoring.unregister_event_duration_listener(
            on_event)
    assert store.pool.h2d["scatters"] == 2 * len(lens)
    assert compiled == []


def test_rank_counters_of_a_padded_group(live):
    ex = _executor(live, max_batch=4)
    store = live[3]
    lens = [40, 70, 90]                      # one 128-token bucket
    group = [PendingRank(user_id=u, psi=None, prefix_len=n,
                         incr=store.short_term(u), items=store.candidates(u))
             for u, n in zip((1, 2, 3), lens)]
    scores, _ = ex.rank_group(group)
    assert len(scores) == 3
    c = ex.counters
    assert (c["rank_launches"], c["rank_rows"], c["rank_pad_rows"]) == \
        (1, 3, 1)
    assert c["rank_tokens_launched"] == 4 * 128
    assert c["rank_tokens_real"] == sum(lens)


@pytest.mark.parametrize("attr,name", [
    ("_prefill", "prefill"), ("_rank", "rank_cached"),
    ("_rank_full", "rank_full"), ("_rank_pages", "rank_pages")])
def test_executor_programs_carry_stable_names(live, attr, name):
    ex = _executor(live)
    assert getattr(ex, attr).__name__ == name


def test_pool_scatter_program_is_named():
    buf = jax.numpy.zeros((4, 2, 3))
    text = _scatter_jit().lower(buf, np.zeros(1, np.int32),
                                np.zeros((1, 2, 3), np.float32)).as_text()
    assert "jit_pool_scatter" in text


def test_pool_landing_and_gather_programs_are_named():
    buf = jax.numpy.zeros((5, 2, 6))
    kv = np.zeros((1, 1, 3, 2, 3), np.float32)
    land = _land_jit().lower(buf, np.zeros(4, np.int32), kv, kv).as_text()
    gather = _gather_jit().lower(buf, np.zeros((2, 2), np.int32)).as_text()
    assert "jit_pool_scatter" in land and "jit_pool_gather" in gather


def test_stats_carry_no_slo_tracker(live):
    svc, _ = _service(live)
    _serve(live, svc, uids=(320,))
    stats = svc.stats()
    assert "slo" not in stats
    assert not hasattr(svc, "slo") and not hasattr(svc.runtime, "slo")
    assert {"d2h_bytes", "mirror_bytes", "materialized_bytes"} <= \
        set(stats["h2d"])


def test_use_tracer_wires_a_built_runtime(live):
    svc, ex = _service(live)
    tracer = Tracer(on=True)
    svc.runtime.use_tracer(tracer)
    assert ex.tracer is tracer
    assert all(i.hbm.tracer is tracer for i in svc.instances.values())
    _serve(live, svc, uids=(330,))
    assert any(s.name == "exec.rank" for s in tracer.spans)
    svc.runtime.use_tracer(OFF)
    assert ex.tracer is OFF


def test_spans_of_concurrent_threads_keep_their_parents():
    """Threads that open nested spans at once (the warm-up's prefill
    pool does) each see their own parents, and every span closes."""
    tracer = Tracer(on=True)

    def work(k):
        for _ in range(100):
            with tracer.span("exec.prefill", thread=k):
                with tracer.span("exec.wait", thread=k):
                    pass

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    spans = tracer.spans
    assert len(spans) == 16 * 200
    for s in spans:
        assert s.t1 >= s.t0 > 0
        if s.name == "exec.wait":
            parent = spans[s.parent]
            assert parent.name == "exec.prefill"
            assert parent.args["thread"] == s.args["thread"]
        else:
            assert s.parent == -1
