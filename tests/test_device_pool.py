"""Device-resident page pool — parity, ledger, and launch contracts.

The ``DevicePagePool`` keeps the page-pool data plane on device, as
the only copy, and mutates it in place (donated landing at
insert/resume); correctness is defined relative to a host-buffer pool
driven through the same operations:

  * after ANY interleaving of insert / partial tail-evict /
    resume-reload / extract-handoff, the device buffer is byte-equal to
    the host twin's buffer on every page a launch could reference (live
    or pinned), ``materialize()`` gives the twin's dense copy bit for
    bit, page accounting is conserved, no host page mirror exists, and
    the null page stays zero — so gathered K/V, and therefore scores,
    bit-match the host-buffer path (hypothesis-driven via
    ``tests/_hyp``, plus a deterministic interleaving that always
    runs);
  * end to end through ``RelayRuntime``, the device-pool deployment
    scores bit-identically to the host-buffer deployment while its
    ``h2d`` ledger reads ``launch_reships == 0`` and
    ``bytes_scattered`` == the freshly inserted page bytes (the
    host-buffer deployment re-ships the pool once per launch);
  * ``_page_launch_args`` REFUSES to truncate a page table wider than
    the launch bucket (the silent-drop bugfix), and ``rank_group``
    widens its bucket to the largest member so an entry whose
    whole-page span padding overhangs the prefix bucket still gathers
    every cached page.
"""

import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core import (BatchingConfig, ClusterConfig, DevicePagePool,
                        GRCostModel, HitKind, PageLayout, TriggerConfig,
                        UserMeta, get_executor, relay_config)
from repro.core.cache import PagedHBMStore, kv_nbytes
from repro.core.paging import PagedPsi
from repro.core.runtime import RelayRuntime
from repro.models import get_config

N_LAYERS = 2
H, D = 2, 3
PT = 8
LAYOUT = PageLayout(page_tokens=PT, slabs=2 * N_LAYERS,
                    token_bytes=H * D * 4)
POOL_PAGES = 40


def _tokens_of(uid: int) -> int:
    # fixed per user (so a re-insert is a refresh/resume, never a
    # resize) and deliberately page-unaligned
    return 2 * PT * (1 + uid % 3) - 3


def _kv(uid: int, tokens: int):
    rng = np.random.default_rng(uid * 1009 + tokens)
    shape = (N_LAYERS, 1, tokens, H, D)
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


def _store(device: bool) -> PagedHBMStore:
    return PagedHBMStore(POOL_PAGES * LAYOUT.page_bytes, LAYOUT,
                         device_pool=device)


def _resident_pages(store: PagedHBMStore, entry) -> np.ndarray:
    pps = store.layout.pages_per_slab(entry.tokens_resident) \
        if entry.tokens_resident else 0
    return entry.page_table[:, :pps].reshape(-1)


def _check_mirror_and_conservation(store: PagedHBMStore, pinned,
                                   twin: PagedHBMStore, twin_pinned) -> None:
    """The device pool against its host-pool twin, driven through the
    same ops: conserved pages, no host page mirror, and the device
    buffer byte-equal to the twin's host buffer on every page a launch
    could reference, and on every dense copy ``materialize`` gives."""
    pool = store.pool
    assert pool.stats["pages_allocated"] == \
        pool.pages_live + pool.stats["pages_freed"]
    assert pool.h2d["launch_reships"] == 0
    assert pool.h2d["bytes_scattered"] == \
        pool.h2d["pages_scattered"] * pool.page_bytes
    assert store.buffer is None, "a device pool keeps no host mirror"
    assert pool.h2d["mirror_bytes"] == 0
    if pool.device_buffer is None:
        return
    dev = np.asarray(pool.device_buffer)
    assert not dev[pool.n_pages].any(), "null page must stay zero"
    for uid, e in store.entries.items():
        if e.page_table is None:
            continue
        te = twin.entries[uid]
        assert dev[_resident_pages(store, e)].tobytes() == \
            twin.buffer[_resident_pages(twin, te)].tobytes()
        if isinstance(e.value, PagedPsi) \
                and e.tokens_resident >= e.prefix_len:
            for a, b in zip(e.value.materialize(), te.value.materialize()):
                assert a.shape == b.shape and a.dtype == b.dtype
                assert a.tobytes() == b.tobytes()
    for psi, tpsi in zip(pinned, twin_pinned):
        # an in-flight launch's pinned snapshot stays readable and
        # byte-stable even after the window freed/recycled around it
        assert dev[psi.table.reshape(-1)].tobytes() == \
            twin.buffer[tpsi.table.reshape(-1)].tobytes()


def _drive_pair(ops):
    """Apply one op sequence to a host-buffer store and a device-pool
    store; after every step both stores must agree entry-for-entry and
    the device pool must bit-match the host store's data plane."""
    host, dev = _store(False), _store(True)
    pinned = {id(host): [], id(dev): []}
    now = 0.0
    for op, uid in ops:
        now += 1.0
        tokens = _tokens_of(uid)
        for s in (host, dev):
            if op == "insert":
                v = _kv(uid, tokens)
                s.insert(uid, v, kv_nbytes(v), now, prefix_len=tokens)
            elif op == "consume":
                s.consume(uid)
            elif op == "back":
                e = s.entries.get(uid)
                if e is not None and e.consumed:
                    e.dram_backed = True   # runtime spilled a DRAM copy
            elif op == "extract":
                s.extract(uid)
            elif op == "pop":
                s.pop(uid)
            elif op == "pin":
                e = s.resident(uid)
                if e is not None:
                    pinned[id(s)].append(s.acquire_value(e))
            elif op == "release" and pinned[id(s)]:
                s.release_value(pinned[id(s)].pop(0))
        # identical window decisions on both flavours...
        assert sorted(host.entries) == sorted(dev.entries)
        assert host.stats == dev.stats
        for uid_, he in host.entries.items():
            assert he.tokens_resident == dev.entries[uid_].tokens_resident
        # ...and identical page data (the score-determining input)
        _check_mirror_and_conservation(dev, pinned[id(dev)],
                                       host, pinned[id(host)])
    return host, dev


# deterministic interleaving covering every path: fills the window,
# partial tail-evicts a consumed DRAM-backed victim, resumes it,
# hands one entry off, and recycles freed pages under a live pin
DETERMINISTIC_OPS = [
    ("insert", 2), ("consume", 2), ("back", 2),
    ("insert", 0), ("insert", 1),          # pressure -> partial tail evict
    ("insert", 2),                         # resume-reload of user 2's tail
    ("pin", 1), ("extract", 1),            # handoff under an active launch
    ("insert", 3), ("insert", 4),          # realloc over recycled pages
    ("release", 1), ("insert", 5), ("pop", 0), ("insert", 0),
]


def test_device_pool_interleaving_parity_deterministic():
    host, dev = _drive_pair(DETERMINISTIC_OPS)
    assert dev.stats["partial_evictions"] >= 1, "tail evict not exercised"
    assert dev.stats["resumed_reloads"] >= 1, "resume not exercised"
    assert dev.stats["handoffs"] >= 1, "extract-handoff not exercised"
    assert dev.pool.stats["pages_freed"] > 0
    assert dev.pool.h2d["scatters"] > 0


def test_device_pool_resume_scatters_only_missing_tail():
    """A resumed partial reload lands only the missing tail pages on
    the device — the resident head never re-crosses the link."""
    _, dev = _drive_pair(DETERMINISTIC_OPS[:5])   # user 2 partially evicted
    e = dev.entries[2]
    assert e.tokens_resident < e.prefix_len
    before = dict(dev.pool.h2d)
    v = _kv(2, _tokens_of(2))
    dev.insert(2, v, kv_nbytes(v), 99.0, prefix_len=_tokens_of(2))
    assert dev.stats["resumed_reloads"] == 1
    moved = dev.pool.h2d["pages_scattered"] - before["pages_scattered"]
    assert 0 < moved < LAYOUT.entry_pages(_tokens_of(2))
    assert dev.pool.h2d["bytes_scattered"] - before["bytes_scattered"] == \
        moved * LAYOUT.page_bytes


OPS = st.lists(
    st.tuples(st.sampled_from(["insert", "consume", "back", "extract",
                               "pop", "pin", "release"]),
              st.integers(0, 5)),
    max_size=60)


@given(OPS)
@settings(max_examples=40, deadline=None)
def test_device_pool_interleaving_parity_property(ops):
    _drive_pair(ops)


@given(st.lists(st.integers(0, 5), min_size=8, max_size=40))
@settings(max_examples=40, deadline=None)
def test_device_pool_free_list_reuse_never_aliases(uids):
    """Churn a window smaller than the working set so freed pages are
    constantly reallocated to OTHER users: if a recycled page ever
    served stale bytes, the page/materialize comparison would catch
    the alias on the very step it appears."""
    host, dev = _drive_pair([("insert", u) for u in uids])
    assert dev.pool.stats["pages_freed"] > 0, "no reuse pressure"
    for uid, he in host.entries.items():
        hv, dv = he.value, dev.entries[uid].value
        if hasattr(hv, "materialize"):
            hk, hvv = hv.materialize()
            dk, dvv = dv.materialize()
            assert hk.tobytes() == dk.tobytes()
            assert hvv.tobytes() == dvv.tobytes()


# ---------------------------------------------------------------------------
# launch-bucket truncation bugfix (_page_launch_args / rank_group)
# ---------------------------------------------------------------------------


def test_page_launch_args_refuses_truncation():
    """The boundary case that used to truncate silently: a table wider
    than the launch bucket must raise, not drop cached pages."""
    import jax.numpy as jnp
    from repro.core.executors import _page_launch_args
    buf = np.zeros((9, PT, H, D), np.float32)
    table = np.arange(8, dtype=np.int32).reshape(4, 2)  # 2 pages/slab
    psi = PagedPsi(table, 2 * PT, LAYOUT, buf)
    with pytest.raises(ValueError, match="truncation"):
        _page_launch_args(jnp.asarray, [psi], 1)
    # the boundary itself (n == bucket) is fine
    _page_launch_args(jnp.asarray, [psi], 2)


# ---------------------------------------------------------------------------
# live end-to-end: device pool == host pool, zero launch re-ships
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def live():
    import jax
    from repro.data.synthetic import UserBehaviorStore, WorkloadConfig
    from repro.models import build_model
    cfg = get_config("hstu_gr", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    store = UserBehaviorStore(WorkloadConfig(
        vocab=cfg.vocab, n_items=16, incr_len=8, max_len=512))
    return cfg, model, params, store


def _live_runtime(live, device_pool):
    cfg, model, params, store = live
    cost = GRCostModel(cfg)
    layout = PageLayout.from_model_config(cfg, 32)
    budget = 64 * layout.entry_bytes(512)
    ex = get_executor("batched")(
        model, params, store, cost=cost,
        batching=BatchingConfig(max_batch=4, max_wait_ms=2.0),
        page_tokens=32, device_pool=device_pool)
    rcfg = relay_config(
        trigger=TriggerConfig(n_instances=2, r2=0.5,
                              kv_p99_len=512, hbm_bytes=budget / 0.5,
                              r1=0.5, t_life_s=5.0, q_m=1e4),
        cluster=ClusterConfig(hbm_cache_bytes=budget,
                              dram_budget_bytes=0.0, max_batch=4,
                              page_tokens=32, device_pool=device_pool,
                              trigger_policy="admit-all",
                              long_seq_threshold=1))
    return RelayRuntime(rcfg, cost, executor_factory=lambda name: ex)


def test_live_device_pool_matches_host_pool_scores(live):
    """THE acceptance: same stream, host-buffer vs device-resident
    deployment — bit-identical scores, and per-launch H2D traffic drops
    from O(pool bytes) to zero."""
    _, _, _, store = live
    metas = [UserMeta(user_id=200 + i,
                      prefix_len=int(store.long_term(200 + i).shape[0]),
                      incr_len=8, n_items=16)
             for i in range(6)]
    results, stats = {}, {}
    for device in (False, True):
        rt = _live_runtime(live, device)
        out = []
        t = 0.0
        for m in metas:
            out.append(rt.submit(m, now=t))
            t += 0.3
        results[device] = out
        stats[device] = rt.stats()["h2d"]
    for hostr, devr in zip(results[False], results[True]):
        assert hostr.hit == devr.hit
        assert hostr.hit == HitKind.HBM_HIT
        assert np.asarray(hostr.scores).tobytes() == \
            np.asarray(devr.scores).tobytes()
    # host-buffer path re-ships the pool once per rank launch...
    assert stats[False]["launch_reships"] >= len(metas)
    assert stats[False]["bytes_scattered"] == 0
    assert not stats[False]["device_resident"]
    # ...the device pool never re-ships, and scatters exactly the
    # freshly inserted page bytes
    h2d = stats[True]
    assert h2d["device_resident"]
    assert h2d["launch_reships"] == 0
    assert h2d["reshipped_bytes"] == 0
    assert h2d["bytes_scattered"] > 0
    layout = PageLayout.from_model_config(live[0], 32)
    # pre_infer pads the prefix to the 64-token prefill grid before the
    # store sizes the entry, so that's the page count that crossed H2D
    inserted = sum(layout.entry_pages(-(-m.prefix_len // 64) * 64)
                   for m in metas)
    assert h2d["pages_scattered"] == inserted
    assert h2d["bytes_scattered"] == inserted * layout.page_bytes


def test_live_rank_group_widens_bucket_past_prefix(live):
    """Regression for the silent truncation: a member whose page table
    overhangs the prefix-derived bucket (whole-page span padding does
    this in segments mode) must gather ALL its pages — the grouped
    launch now scores bit-identically to the per-request launch
    instead of silently dropping the overhanging pages."""
    from repro.serving.batching import PendingRank, bucket_of
    cfg, model, params, store = live
    cost = GRCostModel(cfg)
    ex = get_executor("batched")(
        model, params, store, cost=cost,
        batching=BatchingConfig(max_batch=4), page_tokens=32,
        device_pool=True)
    layout = ex.page_layout
    hbm = PagedHBMStore(64 * layout.entry_bytes(512), layout,
                        device_pool=True)
    hbm.device_hooks = ex
    uid = 7
    meta = UserMeta(user_id=uid, prefix_len=64, incr_len=8, n_items=16)
    kv, _, _ = ex.pre_infer(meta)
    kv = tuple(np.concatenate(
        [np.asarray(a), np.zeros_like(np.asarray(a))], axis=2)
        for a in kv)                       # 128 tokens: 2x the bucket
    hbm.insert(uid, kv, kv_nbytes(kv), 0.0, prefix_len=kv[0].shape[2])
    psi = hbm.acquire_value(hbm.entries[uid])
    assert psi.table.shape[1] > bucket_of(meta.prefix_len) \
        // layout.page_tokens, "fixture must overhang the prefix bucket"
    solo, _ = ex.rank_cached(meta, psi)
    group = [PendingRank(user_id=uid, psi=psi, prefix_len=meta.prefix_len,
                         meta=meta)]
    scores, _ = ex.rank_group(group)
    assert np.asarray(solo).tobytes() == np.asarray(scores[0]).tobytes()
    hbm.release_value(psi)


# ---------------------------------------------------------------------------
# placement on a device other than the default one
# ---------------------------------------------------------------------------

_PLACEMENT_PROBE = r"""
import sys
sys.path.insert(0, {src!r})
import jax
import numpy as np
from repro.core import DevicePagePool
from repro.core.paging import device_zeros

dev = jax.devices()[3]
host = np.arange(5 * 8 * 2 * 3, dtype=np.float32).reshape(5, 8, 2, 3)
with jax.transfer_guard_device_to_device("disallow"):
    z = device_zeros((4, 8, 6), np.float32, dev)
    pool = DevicePagePool(4, host[0].nbytes)
    pool.scatter([1, 2], host, device=dev)
assert z.devices() == {{dev}} and not np.asarray(z).any()
assert pool.device_buffer.devices() == {{dev}}
got = np.asarray(pool.device_buffer)
assert (got[1:3] == host[1:3].reshape(2, 8, 6)).all() and not got[0].any()
print("ok")
"""


def test_pool_fills_on_its_own_device():
    """A pool bound to a non-default device is created and scattered
    there without passing through the default device: ``jnp.zeros(...,
    device=d)`` fills on the default device and copies, which for a
    chip-sized pool ran the default chip out of memory.  Runs in a
    child with four host devices (this process keeps one)."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_PROBE.format(src=src)],
        capture_output=True, text=True, env=env, timeout=120)
    assert res.returncode == 0, res.stderr
    assert res.stdout.strip() == "ok"
