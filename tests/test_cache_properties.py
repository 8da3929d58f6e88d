"""Property tests for the HBM sliding-window store (invariant I2).

Hypothesis-driven (via the tests/_hyp.py shim — they skip cleanly on
images without the wheel) over arbitrary insert/consume/pop/lookup
interleavings:

  * ``used_bytes`` never exceeds the budget and always equals the sum of
    live entry sizes;
  * ``peak_bytes`` is monotone non-decreasing;
  * eviction accounting is conserved:
    ``inserts == live_count + evictions`` after ANY interleaving
    (budget-pressure evictions, same-user refreshes and explicit pops
    all leave through the same turnstile);
  * ``premature_evictions`` counts exactly the unconsumed
    budget-pressure victims, and stays zero under a correctly sized
    sequence-aware trigger driving the full relay.

Paged-store extensions (``PagedHBMStore`` / ``PagePool``):

  * page conservation — ``pages_allocated == pages_live + pages_freed``
    after any interleaving, pins/zombies included;
  * the free list never double-allocates a page;
  * occupancy under mixed prefix lengths beats the unpaged store at the
    same byte budget (fragmentation is bounded by last-page padding);
  * ``premature_evictions == 0`` end-to-end under a correctly sized
    trigger with the paged window.
"""

import numpy as np
from _hyp import given, settings, st

from repro.core import ClusterConfig, GRCostModel, PageLayout, \
    TriggerConfig, UserMeta, relay_config
from repro.core.cache import HBMCacheStore, PagedHBMStore, kv_nbytes
from repro.core.paging import PagePool
from repro.models import get_config
from repro.serving.simulator import ClusterSim

COST = GRCostModel(get_config("hstu_gr"))

OPS = st.lists(
    st.tuples(st.sampled_from(["insert", "consume", "pop", "lookup"]),
              st.integers(0, 7), st.integers(1, 40)),
    max_size=80)


def _drive(store: HBMCacheStore, ops, check=None):
    """Apply an op sequence, running ``check`` after every step."""
    for t, (op, uid, nbytes) in enumerate(ops):
        if op == "insert":
            store.insert(uid, "psi", nbytes, float(t), prefix_len=uid)
        elif op == "consume":
            store.consume(uid)
        elif op == "pop":
            store.pop(uid)
        else:
            store.lookup(uid)
        if check is not None:
            check(store)
    return store


def _invariants(prev_peak):
    def check(store):
        assert 0 <= store.used_bytes <= store.budget
        assert store.used_bytes == sum(
            e.nbytes for e in store.entries.values())
        assert store.stats["peak_bytes"] >= prev_peak[0]
        prev_peak[0] = store.stats["peak_bytes"]
        assert store.stats["inserts"] == \
            store.live_count + store.stats["evictions"]
        assert store.stats["premature_evictions"] <= store.stats["evictions"]
    return check


@given(OPS, st.integers(20, 120))
@settings(max_examples=60, deadline=None)
def test_budget_peak_and_conservation_under_any_interleaving(ops, budget):
    _drive(HBMCacheStore(budget), ops, _invariants([0]))


@given(OPS)
@settings(max_examples=30, deadline=None)
def test_oversized_inserts_rejected_without_disturbing_window(ops):
    """An entry larger than the whole budget never enters the window —
    and, since the fix, never clears it either: the insert is rejected
    up front, counted in ``rejected_inserts``, and the resident entries
    are left alone (no manufactured premature evictions)."""
    store = _drive(HBMCacheStore(25), ops)
    live_before = store.live_count
    used_before = store.used_bytes
    evicted = store.insert(99, "psi", 26, 1e9)
    assert 99 not in store
    assert evicted == []
    assert store.live_count == live_before
    assert store.used_bytes == used_before
    assert store.stats["rejected_inserts"] >= 1
    assert store.stats["inserts"] == \
        store.live_count + store.stats["evictions"]


def test_conservation_example_paths():
    """Pin the three exit turnstiles without hypothesis: budget
    eviction, same-user refresh, explicit pop."""
    store = HBMCacheStore(10)
    store.insert(1, "a", 6, 0.0)
    store.insert(1, "a2", 6, 1.0)          # refresh: 1 eviction
    assert store.stats["evictions"] == 1
    assert store.stats["premature_evictions"] == 0
    store.insert(2, "b", 6, 2.0)           # pressure: evicts unconsumed 1
    assert store.stats["evictions"] == 2
    assert store.stats["premature_evictions"] == 1
    store.consume(2)
    store.pop(2)                           # explicit exit, not premature
    assert store.stats["evictions"] == 3
    assert store.stats["premature_evictions"] == 1
    assert store.stats["inserts"] == 3 == \
        store.live_count + store.stats["evictions"]
    assert store.used_bytes == 0


def test_kv_nbytes_sizes_pytrees():
    kv = (np.zeros((2, 1, 64, 2, 32), np.float32),
          np.zeros((2, 1, 64, 2, 32), np.float32))
    assert kv_nbytes(kv) == 2 * 2 * 64 * 2 * 32 * 4
    assert kv_nbytes({"k": kv, "v": [kv]}) == 2 * kv_nbytes(kv)
    assert kv_nbytes(("psi", 7, 2048)) == 0   # sim executor stub


# ---------------------------------------------------------------------------
# paged store (PagedHBMStore / PagePool)
# ---------------------------------------------------------------------------

# small geometry so hypothesis explores pressure quickly: 4 slabs
# (2 layers x K/V), 8-token pages, 1 byte per token per slab
LAYOUT = PageLayout(page_tokens=8, slabs=4, token_bytes=1)


def _paged_store(pool_pages: int) -> PagedHBMStore:
    return PagedHBMStore(pool_pages * LAYOUT.page_bytes, LAYOUT)


def _paged_invariants(store: PagedHBMStore):
    pool = store.pool
    # page conservation: every page ever allocated is live or freed
    assert pool.stats["pages_allocated"] == \
        pool.pages_live + pool.stats["pages_freed"]
    # entry bytes are whole pages and sum to used_bytes
    assert store.used_bytes == sum(e.nbytes for e in store.entries.values())
    assert all(e.nbytes % LAYOUT.page_bytes == 0
               for e in store.entries.values())
    # entry accounting stays conserved under paging
    assert store.stats["inserts"] == \
        store.live_count + store.stats["evictions"]
    # live tables reference live pages only, with no page shared
    seen = set()
    for e in store.entries.values():
        pps = LAYOUT.pages_per_slab(e.tokens_resident) \
            if e.tokens_resident else 0
        for p in e.page_table[:, :pps].reshape(-1):
            assert int(p) not in seen, "page double-allocated"
            seen.add(int(p))


PAGED_OPS = st.lists(
    st.tuples(st.sampled_from(["insert", "consume", "pop", "lookup"]),
              st.integers(0, 7), st.integers(1, 80)),
    max_size=80)


@given(PAGED_OPS, st.integers(6, 40))
@settings(max_examples=60, deadline=None)
def test_paged_conservation_under_any_interleaving(ops, pool_pages):
    store = _paged_store(pool_pages)
    for t, (op, uid, tokens) in enumerate(ops):
        if op == "insert":
            store.insert(uid, "psi", LAYOUT.entry_bytes(tokens), float(t),
                         prefix_len=tokens)
        elif op == "consume":
            store.consume(uid)
        elif op == "pop":
            store.pop(uid)
        else:
            store.lookup(uid)
        _paged_invariants(store)


@given(st.lists(st.tuples(st.integers(1, 6), st.booleans()), max_size=60),
       st.integers(4, 24))
@settings(max_examples=60, deadline=None)
def test_free_list_never_double_allocates(plan, pool_pages):
    """Drive alloc/free (with pins interleaved) directly on the pool:
    outstanding allocations never overlap and conservation holds."""
    pool = PagePool(pool_pages, page_bytes=8)
    outstanding = []
    for n, pin in plan:
        pages = pool.alloc(n)
        if pages is not None:
            assert len(set(pages)) == len(pages)
            flat = {p for ps, _ in outstanding for p in ps}
            assert not flat & set(pages), "double allocation"
            if pin:
                pool.pin(pages)
            outstanding.append((pages, pin))
        elif outstanding:
            pages_, pinned = outstanding.pop(0)
            pool.free(pages_)
            if pinned:
                # zombie until unpinned: still counted live
                assert pool.stats["pages_allocated"] == \
                    pool.pages_live + pool.stats["pages_freed"]
                pool.unpin(pages_)
        assert pool.stats["pages_allocated"] == \
            pool.pages_live + pool.stats["pages_freed"]
        assert 0 <= pool.free_pages <= pool.n_pages


@given(st.lists(st.integers(1, 100), min_size=4, max_size=30),
       st.integers(0, 3))
@settings(max_examples=40, deadline=None)
def test_paged_occupancy_beats_dense_under_mixed_lengths(lens, seed):
    """The headline fragmentation claim: with mixed prefix lengths under
    one byte budget, the paged window keeps AT LEAST as many psi
    resident as the dense store (its only waste is last-page padding,
    the dense store fragments on whole-entry granularity)."""
    budget = 40 * LAYOUT.page_bytes
    dense = HBMCacheStore(budget)
    paged = _paged_store(40)
    rng = np.random.default_rng(seed)
    for i, tokens in enumerate(lens):
        uid = int(rng.integers(0, 10 ** 6))
        # the dense store ships the 64-grid padded pytree; charge the
        # paged store its page-rounded footprint for the same psi
        dense.insert(uid, "psi", LAYOUT.slabs * LAYOUT.token_bytes
                     * (-(-tokens // 64) * 64), float(i),
                     prefix_len=tokens)
        paged.insert(uid, "psi", LAYOUT.entry_bytes(tokens), float(i),
                     prefix_len=tokens)
    assert paged.live_count >= dense.live_count
    _paged_invariants(paged)


def test_paged_partial_eviction_and_resume_pinned_example():
    """Pin the partial-eviction -> resumed-reload path without
    hypothesis: tail pages of the oldest consumed DRAM-backed entry go
    first, the head stays resident, and the resume streams only the
    missing tokens."""
    store = _paged_store(10 * LAYOUT.slabs)   # 10 pages per slab
    e8 = LAYOUT.entry_bytes(8 * LAYOUT.page_tokens)
    store.insert(1, "psi", e8, 0.0, prefix_len=8 * LAYOUT.page_tokens)
    store.consume(1)
    store.entries[1].dram_backed = True
    store.insert(2, "psi", LAYOUT.entry_bytes(4 * LAYOUT.page_tokens), 1.0,
                 prefix_len=4 * LAYOUT.page_tokens)
    assert store.stats["partial_evictions"] == 1
    assert store.stats["evictions"] == 0
    e = store.entries[1]
    assert 0 < e.tokens_resident < e.prefix_len
    assert store.lookup(1) is None            # partial != servable
    missing = store.missing_tokens(1, e.prefix_len)
    assert missing == e.prefix_len - e.tokens_resident
    store.insert(1, "psi", e8, 2.0, prefix_len=e.prefix_len)
    assert store.stats["resumed_reloads"] == 1
    assert store.entries[1].tokens_resident == e.prefix_len
    assert store.lookup(1) is not None
    _paged_invariants(store)


def test_paged_pinned_pages_survive_eviction():
    """A page pinned by an in-flight launch is freed only after release
    (zombie defer) — and is never handed to a new allocation first."""
    store = _paged_store(2 * LAYOUT.slabs)
    t8 = LAYOUT.page_tokens * 2               # 2 pages per slab
    store.insert(1, "psi", LAYOUT.entry_bytes(t8), 0.0, prefix_len=t8)
    psi = store.acquire_value(store.entries[1])
    pinned = {int(p) for p in store.entries[1].page_table.reshape(-1)}
    store.insert(2, "psi", LAYOUT.entry_bytes(t8), 1.0, prefix_len=t8)
    # user 1 evicted under pressure, but its pages are pinned: user 2's
    # insert must have been rejected rather than reuse them
    assert 1 not in store
    assert store.pool.zombie_pages == len(pinned)
    assert 2 not in store
    assert store.stats["rejected_inserts"] == 1
    store.release_value(psi)
    assert store.pool.zombie_pages == 0
    store.insert(2, "psi", LAYOUT.entry_bytes(t8), 2.0, prefix_len=t8)
    assert 2 in store
    pool = store.pool
    assert pool.stats["pages_allocated"] == \
        pool.pages_live + pool.stats["pages_freed"]


@given(st.integers(1500, 3500), st.integers(0, 3))
@settings(max_examples=4, deadline=None)
def test_premature_evictions_zero_under_trigger_paged(L, seed):
    """The end-to-end I2 guarantee survives paging: a correctly sized
    sequence-aware trigger over the PAGED window never lets an admitted
    cache die unconsumed."""
    hbm = 2e9
    cfg = relay_config(
        trigger=TriggerConfig(n_instances=5, r2=0.8, t_life_s=0.5,
                              kv_p99_len=max(L, 4096),
                              hbm_bytes=hbm / 0.5, r1=0.5,
                              q_m=1e3 / COST.pre_infer_ms(L),
                              slack_budget_ms=65.0),
        cluster=ClusterConfig(hbm_cache_bytes=hbm, dram_budget_bytes=0.0,
                              page_tokens=64))
    rng = np.random.default_rng(seed)
    t, arr = 0.0, []
    for _ in range(200):
        t += rng.exponential(1.0 / 80.0)
        arr.append((t, UserMeta(user_id=int(rng.integers(0, 10 ** 9)),
                                prefix_len=L)))
    sim = ClusterSim(cfg, COST)
    sim.run(iter(arr))
    assert any(i.hbm.stats["inserts"] > 0
               for i in sim.instances.values()), "vacuous: nothing admitted"
    for inst in sim.instances.values():
        assert inst.hbm.stats["premature_evictions"] == 0
        assert inst.hbm.stats["inserts"] == \
            inst.hbm.live_count + inst.hbm.stats["evictions"]
        pool = inst.hbm.pool
        assert pool.stats["pages_allocated"] == \
            pool.pages_live + pool.stats["pages_freed"]


@given(st.integers(1500, 3500), st.integers(0, 3))
@settings(max_examples=6, deadline=None)
def test_premature_evictions_zero_under_sequence_aware_trigger(L, seed):
    """I2 end-to-end: a *correctly sized* sequence-aware trigger —
    kv_p99_len covering the workload, hbm_bytes matching the store
    budget, q_m derived from the actual pre-infer cost, and slack-aware
    admission so psi always lands before its ranking — never lets an
    admitted cache die unconsumed, for any sequence length in the
    admitting regime and any arrival seed."""
    hbm = 2e9
    cfg = relay_config(
        trigger=TriggerConfig(n_instances=5, r2=0.8, t_life_s=0.5,
                              kv_p99_len=max(L, 4096),
                              hbm_bytes=hbm / 0.5, r1=0.5,
                              q_m=1e3 / COST.pre_infer_ms(L),
                              slack_budget_ms=65.0),
        cluster=ClusterConfig(hbm_cache_bytes=hbm, dram_budget_bytes=0.0))
    rng = np.random.default_rng(seed)
    t, arr = 0.0, []
    for _ in range(200):
        t += rng.exponential(1.0 / 80.0)
        arr.append((t, UserMeta(user_id=int(rng.integers(0, 10 ** 9)),
                                prefix_len=L)))
    sim = ClusterSim(cfg, COST)
    sim.run(iter(arr))
    assert any(i.hbm.stats["inserts"] > 0
               for i in sim.instances.values()), "vacuous: nothing admitted"
    for inst in sim.instances.values():
        assert inst.hbm.stats["premature_evictions"] == 0
        assert inst.hbm.stats["inserts"] == \
            inst.hbm.live_count + inst.hbm.stats["evictions"]
