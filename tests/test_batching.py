"""Correctness contract for continuous micro-batching (the contract
promised by ``repro/serving/batching.py``): batched scores equal
per-request scores, to fp32 rounding — across every ``BUCKETS`` boundary (n, n+1, exact
bucket), with mixed prefix lengths inside one group (padded-key
masking), and through the registered ``batched`` executor end-to-end
under ``RelayRuntime``, not just the raw ``BatchedRankExecutor``.

Also locks the runtime-side semantics: hit classification, the
``latency_ms == sum(components)`` invariant under batching, aggregator
slot scheduling, warmup, and the throughput ordering
relay_batched >= relay at equal hit rates.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import (BatchingConfig, ClusterConfig, Executor,
                        GRCostModel, HitKind, TriggerConfig, UserMeta,
                        get_executor, relay_config)
from repro.core.executors import BatchedLiveExecutor
from repro.data.synthetic import UserBehaviorStore, WorkloadConfig
from repro.models import build_model, get_config
from repro.serving.batching import (BUCKETS, BatchAggregator, PendingRank,
                                    bucket_of, pad_psi)
from repro.serving.simulator import ClusterSim, run_sim

CFG = get_config("hstu_gr", smoke=True)
COST = GRCostModel(CFG)
COST_FULL = GRCostModel(get_config("hstu_gr"))
N_ITEMS, INCR = 16, 8
# A batched launch and a per-request launch are different XLA programs
# (the batch extent differs), and XLA may order their reductions
# differently, so their fp32 scores agree to rounding, not bit for bit:
# at most 5.6e-6 apart on scores of magnitude 1-10 (XLA CPU, JAX 0.9.0).
# The bound below leaves about 4x margin over that.  Where both sides
# run the same program, the tests keep exact equality.
REORDER_TOL = dict(rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def live():
    """(model, params, store, batched executor) — one jit cache for the
    whole module."""
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    store = UserBehaviorStore(WorkloadConfig(
        vocab=CFG.vocab, n_items=N_ITEMS, incr_len=INCR, max_len=512))
    ex = get_executor("batched")(
        model, params, store, cost=COST,
        batching=BatchingConfig(max_batch=4, max_wait_ms=2.0))
    return model, params, store, ex


def _work(meta, psi):
    return PendingRank(user_id=meta.user_id, psi=psi,
                       prefix_len=meta.prefix_len, meta=meta)


def _meta(uid, plen):
    return UserMeta(user_id=uid, prefix_len=plen, incr_len=INCR,
                    n_items=N_ITEMS)


# ---------------------------------------------------------------------------
# registry + protocol
# ---------------------------------------------------------------------------


def test_batched_executor_registered(live):
    assert get_executor("batched") is BatchedLiveExecutor
    _, _, _, ex = live
    assert isinstance(ex, Executor)           # protocol surface intact
    assert ex.batching.max_batch == 4         # runtime batching opt-in
    assert callable(ex.rank_group)


# ---------------------------------------------------------------------------
# batched == per-request, across bucket boundaries
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("boundary", [64, 128])
def test_batched_matches_per_request_at_bucket_boundaries(live, boundary):
    """n just-below, exactly-at, and just-above a BUCKETS edge: batched
    group scores match the per-request rank_cached scores — bit for bit
    for a one-member group (the same program), to fp32 rounding for a
    two-member group (a different program)."""
    _, _, _, ex = live
    for base_uid, plens in ((10, (boundary - 1, boundary)),
                            (20, (boundary + 1,))):
        group, singles = [], []
        for i, plen in enumerate(plens):
            meta = _meta(base_uid + i, plen)
            psi, _, _ = ex.pre_infer(meta)
            s, _ = ex.rank_cached(meta, psi)
            singles.append(np.asarray(s)[0])
            group.append(_work(meta, psi))
        scores, ms = ex.rank_group(group)
        assert ms > 0
        for got, want in zip(scores, singles):
            if len(group) == 1:
                np.testing.assert_array_equal(np.asarray(got), want)
            else:
                np.testing.assert_allclose(np.asarray(got), want,
                                           **REORDER_TOL)


def test_mixed_prefix_lengths_one_group_padded_keys_exact(live):
    """One bucket (256), psi tensors at different 64-grid lengths
    (192/256): zero-padded K rows must contribute exactly nothing."""
    model, params, _, ex = live
    group, singles = [], []
    for uid, plen in ((30, 129), (31, 200), (32, 256)):
        meta = _meta(uid, plen)
        psi, _, _ = ex.pre_infer(meta)
        s, _ = ex.rank_cached(meta, psi)
        singles.append(np.asarray(s)[0])
        group.append(_work(meta, psi))
    lens = {w.psi[0].shape[2] for w in group}
    assert lens == {192, 256}, "group must mix psi lengths to pad"
    scores, _ = ex.rank_group(group)
    for got, want in zip(scores, singles):
        np.testing.assert_allclose(np.asarray(got), want, **REORDER_TOL)
    # padding is explicit and exact: manually padded psi reproduces the
    # batched member through the unjitted model call
    w = group[0]
    kp, vp = pad_psi(jax.numpy, w.psi, 256)
    want = model.rank_with_cache(
        params, (kp, vp),
        jax.numpy.asarray(ex.store.short_term(w.user_id)[None]),
        jax.numpy.asarray(ex.store.candidates(w.user_id)[None]))
    np.testing.assert_allclose(np.asarray(scores[0]), np.asarray(want)[0],
                               atol=1e-5, rtol=1e-5)


def test_batched_full_rank_matches_per_request(live):
    """Miss-fallback members (psi=None) batch through full_rank and
    match the per-request rank_full path to fp32 rounding."""
    _, _, _, ex = live
    group, singles = [], []
    for uid, plen in ((40, 100), (41, 127), (42, 65)):
        meta = _meta(uid, plen)
        s, _ = ex.rank_full(meta)
        singles.append(np.asarray(s)[0])
        group.append(_work(meta, None))
    scores, _ = ex.rank_group(group)
    for got, want in zip(scores, singles):
        np.testing.assert_allclose(np.asarray(got), want, **REORDER_TOL)


def test_batch_axis_padding_is_row_independent(live):
    """A 3-deep group snaps to the 4-row grid by repeating row 0; the
    real members' scores must be unaffected.  Against a 4-deep group
    whose fourth row is another user — the same program — they match
    bit for bit; against the singletons, to fp32 rounding."""
    _, _, _, ex = live
    metas = [_meta(50 + i, 70 + 7 * i) for i in range(4)]
    psis = [ex.pre_infer(m)[0] for m in metas]
    singles = [np.asarray(ex.rank_cached(m, p)[0])[0]
               for m, p in zip(metas, psis)]
    work = [_work(m, p) for m, p in zip(metas, psis)]
    scores, _ = ex.rank_group(work[:3])
    assert len(scores) == 3                   # pad row sliced off
    full, _ = ex.rank_group(work)
    for got, other, want in zip(scores, full, singles):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(other))
        np.testing.assert_allclose(np.asarray(got), want, **REORDER_TOL)


# ---------------------------------------------------------------------------
# aggregator semantics
# ---------------------------------------------------------------------------


def test_live_pre_infer_group_matches_per_request(live):
    """Batched pre-inference (one jitted prefill per prefill-grid
    group): each member's psi slice and byte size bit-match the psi its
    own per-request ``pre_infer`` would produce — so downstream rank
    scores cannot diverge between the batched and per-user side paths."""
    _, _, _, ex = live
    metas = [_meta(50 + i, plen) for i, plen in enumerate((100, 128, 65))]
    outs, ms = ex.pre_infer_group(metas)
    assert ms > 0 and len(outs) == len(metas)
    for meta, (psi, nbytes) in zip(metas, outs):
        want_psi, want_nbytes, _ = ex.pre_infer(meta)
        assert nbytes == want_nbytes
        for got, want in zip(psi, want_psi):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_aggregator_key_separates_kinds_and_buckets():
    agg = BatchAggregator(BatchingConfig(max_batch=8, max_wait_ms=5.0))
    cached = PendingRank(1, ("psi",), 100, incr_len=8, n_items=16)
    full = PendingRank(2, None, 100, incr_len=8, n_items=16)
    other_bucket = PendingRank(3, ("psi",), 200, incr_len=8, n_items=16)
    for p in (cached, full, other_bucket):
        assert agg.add(p, now=0.0) is None
    assert len(agg.queues) == 3               # never co-batched
    assert agg.pending == 3
    g = agg.take_for(cached)
    assert [p.user_id for p in g] == [1]


def test_aggregator_boundary_lengths_group_exactly():
    agg = BatchAggregator(BatchingConfig(max_batch=8, max_wait_ms=5.0))
    for b in BUCKETS[:4]:
        agg.add(PendingRank(b, ("psi",), b, incr_len=8, n_items=16), 0.0)
        agg.add(PendingRank(b + 1, ("psi",), b + 1, incr_len=8,
                            n_items=16), 0.0)
    # n lands in bucket(n); n+1 spills to the next bucket
    assert len(agg.queues) == 5
    g = agg.take_oldest()
    assert [p.user_id for p in g] == [BUCKETS[0]]


def test_aggregator_take_leaves_overflow_queued():
    agg = BatchAggregator(BatchingConfig(max_batch=2, max_wait_ms=5.0))
    got = None
    for uid in range(5):
        r = agg.add(PendingRank(uid, ("psi",), 100, incr_len=8,
                                n_items=16), now=uid * 1e-4)
        got = got or r
    assert [p.user_id for p in got] == [0, 1]
    assert agg.pending == 1                   # 2,3 flushed at max; 4 left
    assert agg.stats["max_seen_batch"] == 2


# ---------------------------------------------------------------------------
# RelayRuntime drives the batched executor end-to-end
# ---------------------------------------------------------------------------


def test_runtime_drives_batched_executor_end_to_end(live):
    """A burst of same-bucket users through the full relay: batches form,
    every admitted request scores as an out-of-band per-request call
    does (to fp32 rounding: a batched launch is another program), and
    the latency invariant survives batching."""
    _, _, _, ex = live
    cfg = relay_config(
        trigger=TriggerConfig(n_instances=2, r2=0.5,
                              rank_p99_budget_ms=50.0),
        cluster=ClusterConfig(m_slots=2))
    svc_cost = GRCostModel(CFG)
    from repro.core import RelayGRService
    svc = RelayGRService(cfg, svc_cost, executor_factory=lambda name: ex)
    rt = svc.runtime
    metas = [_meta(1000 + i, 200 + 8 * i) for i in range(6)]
    results = []
    for i, meta in enumerate(metas):
        rt.schedule(0.001 * i, "arrival", meta=meta, sink=results.append)
    rt.drain()
    assert len(results) == len(metas)
    batch_stats = [i.batcher.stats for i in svc.instances.values()
                   if i.batcher is not None and i.batcher.stats["requests"]]
    assert batch_stats, "no instance batched anything"
    assert sum(s["requests"] for s in batch_stats) == len(metas)
    for r, rec in zip(sorted(results, key=lambda r: r.user_id),
                      sorted(rt.records, key=lambda c: c.user_id)):
        assert r.latency_ms == pytest.approx(sum(r.components.values()),
                                             abs=1e-9)
        assert rec.rank_ms == r.components["rank"] > 0.0
        assert np.isfinite(np.asarray(r.scores, np.float32)).all()
        meta = metas[r.user_id - 1000]
        if r.hit in (HitKind.HBM_HIT, HitKind.DRAM_HIT):
            psi, _, _ = ex.pre_infer(meta)
            want, _ = ex.rank_cached(meta, psi)
        else:
            want, _ = ex.rank_full(meta)
        np.testing.assert_allclose(np.asarray(r.scores),
                                   np.asarray(want)[0], **REORDER_TOL)


def test_batch_grid_never_exceeds_max_batch(live):
    _, _, _, ex = live
    odd = BatchedLiveExecutor(ex.model, ex.params, ex.store, cost=COST,
                              batching=BatchingConfig(max_batch=6))
    assert [odd._batch_grid(n) for n in (1, 2, 3, 5, 6)] == [1, 2, 4, 6, 6]
    assert all(odd._batch_grid(n) <= 6 for n in range(1, 7))


def test_warmup_precompiles_and_dedups(live):
    _, _, _, ex = live
    done = ex.warmup([70, 129], batch_sizes=(1, 3), incr_len=INCR,
                     n_items=N_ITEMS)
    # batch 3 snaps to the 4-row grid; 70 -> bucket 128, 129 -> 256
    assert set(done) == {(128, 1, INCR, N_ITEMS), (128, 4, INCR, N_ITEMS),
                         (256, 1, INCR, N_ITEMS), (256, 4, INCR, N_ITEMS)}
    assert ex.warmup([70, 129], batch_sizes=(1, 3), incr_len=INCR,
                     n_items=N_ITEMS) == []   # already warm


def test_warmup_respects_bucket_guard(live):
    _, _, _, ex = live
    guarded = dataclasses.replace(ex.batching, max_buckets_live=1)
    ex2 = BatchedLiveExecutor(ex.model, ex.params, ex.store, cost=COST,
                              batching=guarded)
    done = ex2.warmup([400, 100, 90, 70], batch_sizes=(1,),
                      incr_len=INCR, n_items=N_ITEMS)
    assert {k[0] for k in done} == {128}      # the traffic-dominant bucket


# ---------------------------------------------------------------------------
# sim mirror: throughput ordering at equal hit rates
# ---------------------------------------------------------------------------


def _sim_cfg(max_batch, m_slots=5):
    return relay_config(
        trigger=TriggerConfig(n_instances=5, r2=0.8, kv_p99_len=2048,
                              hbm_bytes=8e9, r1=0.5, t_life_s=0.5),
        cluster=ClusterConfig(hbm_cache_bytes=4e9, dram_budget_bytes=0.0,
                              max_batch=max_batch, batch_wait_ms=2.0,
                              m_slots=m_slots))


def _stream(qps, dur, seed=0):
    rng = np.random.default_rng(seed)
    t = 0.0
    while t < dur:
        t += rng.exponential(1.0 / qps)
        yield t, UserMeta(user_id=int(rng.integers(0, 10 ** 9)),
                          prefix_len=2048)


def test_relay_batched_throughput_geq_relay_at_equal_hit_rates():
    plain = run_sim(_sim_cfg(0), COST_FULL, _stream(520, 5.0))
    batched = run_sim(_sim_cfg(8), COST_FULL, _stream(520, 5.0))
    assert batched["hbm_hit"] == pytest.approx(plain["hbm_hit"], abs=0.05)
    assert batched["miss"] == pytest.approx(plain["miss"], abs=0.05)
    assert batched["throughput_qps"] >= plain["throughput_qps"]
    assert batched["rank_p99_ms"] <= plain["rank_p99_ms"]


def test_batched_sim_groups_share_launch_cost():
    """Co-batched members report the same rank component — the group
    wall time — and the cost model's batched_rank_ms shape holds.
    One model slot per instance: batching is work-conserving, so depth
    only builds while slots are contended."""
    cfg = _sim_cfg(8, m_slots=1)
    sim = ClusterSim(cfg, COST_FULL)
    meta = [(1e-4 * i, UserMeta(user_id=5000 + i, prefix_len=2048))
            for i in range(12)]
    sim.run(iter(meta))
    assert len(sim.records) == 12
    by_rank = {}
    for r in sim.records:
        by_rank.setdefault(round(r.rank_ms, 9), []).append(r)
        assert r.rank_ms > 0
    deep = max(len(v) for v in by_rank.values())
    mb = max(i.batcher.stats["max_seen_batch"]
             for i in sim.instances.values() if i.batcher is not None)
    assert mb > 1, "burst never formed a batch"
    assert deep > 1, "co-batched members should share one rank latency"
    solo = COST_FULL.rank_on_cache_ms(2048, 64, 512)
    assert COST_FULL.batched_rank_ms([solo] * 4) == pytest.approx(
        solo * (1 + 3 * COST_FULL.batch_factor))
    assert COST_FULL.batched_rank_ms([]) == 0.0
