"""Batched execution + stateful property tests for
the cache/expander interplay (hypothesis rule-based state machine)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from _hyp import (RuleBasedStateMachine, given, initialize, invariant, rule,
                  settings, st)

from repro.core.cache import HBMCacheStore
from repro.core.expander import DRAMExpander, ExpanderConfig
from repro.models import get_model
from repro.serving.batching import (BatchAggregator, BatchedRankExecutor,
                                    BatchingConfig, PendingRank, bucket_of)

RNG = np.random.default_rng(21)


# ---------------------------------------------------------------------------
# Batched rank execution == per-request execution
# ---------------------------------------------------------------------------


def test_batched_rank_matches_per_request():
    model = get_model("hstu_gr", smoke=True)
    params = model.init(jax.random.PRNGKey(0))
    ex = BatchedRankExecutor(model, params)
    batch = []
    singles = []
    for i, plen in enumerate((48, 64, 57)):  # mixed lengths, one bucket
        prefix = jnp.asarray(RNG.integers(0, 500, (1, plen)), jnp.int32)
        incr = RNG.integers(0, 500, 8).astype(np.int32)
        items = RNG.integers(0, 500, 16).astype(np.int32)
        _, psi = model.prefill(params, {"tokens": prefix})
        batch.append(PendingRank(user_id=i, psi=psi, prefix_len=plen,
                                 incr=incr, items=items))
        # per-request reference: same bucket-padded psi + normalizer
        k, v = psi
        pad = bucket_of(plen) - plen
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        singles.append(model.rank_with_cache(
            params, (kp, vp), jnp.asarray(incr[None]),
            jnp.asarray(items[None]))[0])
    outs = ex.run(batch)
    for got, want in zip(outs, singles):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=1e-4, rtol=1e-4)


def test_aggregator_batches_and_expiry():
    agg = BatchAggregator(BatchingConfig(max_batch=3, max_wait_ms=5.0))
    mk = lambda uid, plen: PendingRank(uid, None, plen,
                                       np.zeros(8, np.int32),
                                       np.zeros(16, np.int32))
    assert agg.add(mk(1, 100), now=0.0) is None
    assert agg.add(mk(2, 120), now=0.001) is None
    full = agg.add(mk(3, 90), now=0.002)
    assert full is not None and len(full) == 3           # same bucket (128)
    assert agg.add(mk(4, 5000), now=0.003) is None       # different bucket
    assert agg.expired(now=0.0031) == []
    exp = agg.expired(now=0.010)
    assert len(exp) == 1 and exp[0][0].user_id == 4


@given(st.integers(1, 40000))
def test_bucketing_monotone(n):
    b = bucket_of(n)
    assert b >= min(n, 32768)
    assert bucket_of(b) == b


# ---------------------------------------------------------------------------
# Stateful property test: HBM window + DRAM expander interplay
# ---------------------------------------------------------------------------


class CacheLifecycleMachine(RuleBasedStateMachine):
    """Random interleavings of insert/consume/spill/reload/evict must
    never violate: budget bounds, single-flight at-most-one, and
    no-user-in-two-tiers-simultaneously."""

    @initialize()
    def setup(self):
        self.hbm = HBMCacheStore(budget_bytes=50)
        self.exp = DRAMExpander(ExpanderConfig(dram_budget_bytes=100))
        self.clock = 0.0

    def _tick(self):
        self.clock += 0.01
        return self.clock

    @rule(uid=st.integers(0, 9), nbytes=st.integers(1, 20))
    def pre_infer(self, uid, nbytes):
        evicted = self.hbm.insert(uid, "psi", nbytes, self._tick(),
                                  prefix_len=uid)
        for e in evicted:
            if e.consumed:
                self.exp.spill(e)

    @rule(uid=st.integers(0, 9))
    def rank(self, uid):
        now = self._tick()
        action, entry = self.exp.pseudo_pre_infer(uid, self.hbm, now)
        if action == "hbm":
            self.hbm.consume(uid)
        elif action == "reload":
            self.exp.complete_reload(uid, self.hbm, now)
            self.exp.finish(uid)
            self.hbm.consume(uid)
        elif action in ("wait", "miss"):
            self.exp.finish(uid)

    @rule(uid=st.integers(0, 9))
    def spill_consumed(self, uid):
        e = self.hbm.entries.get(uid)
        if e is not None and e.consumed:
            import dataclasses as dc
            self.exp.spill(dc.replace(e))

    @invariant()
    def budgets_hold(self):
        assert 0 <= self.hbm.used_bytes <= 50
        assert 0 <= self.exp.used_bytes <= 100

    @invariant()
    def no_dangling_flight(self):
        # outside of a rule, no single-flight op should be left open
        assert all(v >= 0 for v in self.exp.flight._inflight.values())

    @invariant()
    def bytes_match_entries(self):
        assert self.hbm.used_bytes == sum(
            e.nbytes for e in self.hbm.entries.values())
        assert self.exp.used_bytes == sum(
            e.nbytes for e in self.exp.entries.values())


TestCacheLifecycle = CacheLifecycleMachine.TestCase
