"""Executor protocol + registry: how a ranking instance computes.

The relay-race state machine never touches tensors directly — every
compute step goes through an ``Executor``:

  * ``SimExecutor``  — analytic cost-model latencies, no real compute
    (cluster-scale simulation, capacity planning, paper figures);
  * ``LiveExecutor`` — jitted JAX HSTU prefill / rank-with-cache /
    full-rank on the local device, latencies measured.

Both satisfy the same ``typing.Protocol``, so the runtime drives the
identical state machine in either mode; new backends register under a
name and are selected per deployment via ``get_executor``:

  * ``BatchedLiveExecutor`` (name ``batched``) — ``LiveExecutor`` plus
    continuous micro-batching: compatible rank requests grouped by the
    per-instance ``BatchAggregator`` execute as ONE jitted call on
    bucketed shapes (``rank_group``), and per-request shapes snap to
    the same bucket grid so batched and per-request scores agree to
    fp32 rounding (tests/test_batching.py).

An executor opts into runtime-driven batching by carrying a
``batching: BatchingConfig`` attribute and a ``rank_group(group)``
method; ``RelayRuntime`` then parks rank work in a ``BatchAggregator``
and flushes groups through one model slot each.  ``SimExecutor``
mirrors the same surface via ``GRCostModel.batched_rank_ms`` so the
cluster simulator stays trace-comparable with the live engine.

Both executors also serve the *disaggregated-prefill* split
(``ClusterConfig.prefill_hosts > 0``): a dedicated prefill engine
drives only the side-path surface — ``pre_infer`` and the batched
``pre_infer_group`` — while its produced psi is shipped cross-host by
the runtime; the rank surface of the same executor runs on the owning
rank instances.  No prefill-specific executor subclass exists on
purpose: the compute is identical, only the placement (and the NIC
hop) differs.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Protocol, \
    Sequence, Tuple, runtime_checkable

import numpy as np

from repro.serving.batching import (BatchingConfig, PendingRank, bucket_of,
                                    pad_psi, prefill_grid, stack_psi)

from .cache import kv_nbytes
from .costmodel import GRCostModel
from .paging import (DevicePagePool, PageLayout, PagedPsi, ceil_div,
                     device_pages, device_zeros)
from .tracing import OFF
from .types import UserMeta


@runtime_checkable
class Executor(Protocol):
    """Compute backend for one ranking instance."""

    def pre_infer(self, meta: UserMeta) -> Tuple[Any, int, float]:
        """Pre-infer psi for the user's long-term prefix.
        Returns (psi, nbytes, latency_ms)."""
        ...

    def rank_cached(self, meta: UserMeta, psi: Any) -> Tuple[Any, float]:
        """Rank candidates reusing cached psi. Returns (scores, ms)."""
        ...

    def rank_full(self, meta: UserMeta) -> Tuple[Any, float]:
        """Full inference on the critical path (miss fallback)."""
        ...

    def reload_ms(self, meta: UserMeta, tokens: Optional[int] = None
                  ) -> float:
        """DRAM -> HBM reload cost for this user's psi.  ``tokens``
        narrows the transfer to the missing suffix (paged stores resume
        partial reloads); None means the whole prefix."""
        ...


# --- paged psi launch helpers -------------------------------------------------


def page_bucket(tokens: int, page_tokens: int) -> int:
    """Page count a launch pads its tables to: the shared ``BUCKETS``
    token grid expressed in pages — THE first key component of the
    paged ``rank_with_pages`` jit cache (page-count bucket, batch)."""
    return ceil_div(bucket_of(int(tokens)), int(page_tokens))


def _pages_of(tokens: int, psi: PagedPsi) -> int:
    return page_bucket(tokens, psi.layout.page_tokens)


def _page_launch_args(put, psis: Sequence[PagedPsi], np_bucket: int):
    """Stack per-member page tables — (slabs, n) int32 — into the
    (B, L, 2, np_bucket) launch table, padding with the pool's null
    (all-zero) page so padded tokens contribute silu(0) = 0 exactly,
    matching the dense bucketed path's zero-padded psi.

    The pool buffer: a ``DevicePagePool`` passes its device-resident
    array by REFERENCE (zero host->device traffic per launch); a
    host-buffer pool re-ships its whole page buffer, counted in the
    owning pool's ``h2d`` ledger.  A member whose table exceeds ``np_bucket``
    is an error — truncating would silently drop cached pages from the
    gather (callers widen the launch bucket to the group's largest
    member instead).  ``put`` moves a host array to the launching
    executor's device."""
    pool = psis[0].pool
    on_device = isinstance(pool, DevicePagePool)
    buf = pool.device_buffer if on_device else psis[0].buffer
    null = buf.shape[0] - 1
    rows = []
    for psi in psis:
        slabs, n = psi.table.shape
        if n > np_bucket:
            raise ValueError(
                f"page table has {n} pages/slab but the launch bucket "
                f"is {np_bucket}: truncation would silently drop cached "
                f"pages — widen the bucket to the group's largest member")
        t = np.full((slabs, np_bucket), null, np.int32)
        t[:, :n] = psi.table
        rows.append(t.reshape(slabs // 2, 2, np_bucket))
    if on_device:
        launch_buf = buf
    else:
        launch_buf = put(device_pages(buf))    # O(pool bytes) per launch
        if pool is not None:
            pool.h2d["launch_reships"] += 1
            pool.h2d["reshipped_bytes"] += int(buf.nbytes)
    return launch_buf, put(np.stack(rows))


def _table_bytes(psis: Sequence[PagedPsi], np_bucket: int) -> int:
    """Bytes of the launch page table ``_page_launch_args`` puts."""
    return len(psis) * psis[0].layout.slabs * np_bucket * 4


def _gather_psi(jnp, buf, tables, heads: int):
    """Inside-jit gather: pool buffer in its device layout (N + 1, pt,
    H * D) + launch tables (B, L, 2, np) -> the (K, V) pytree of stacked
    (L, B, np * pt, H, D) that ``rank_with_cache`` consumes.  The Pallas
    kernel (``repro.kernels.paged_prefix_attn``) reads the pool through
    the page-table BlockSpec index map instead."""
    g = jnp.take(buf, tables, axis=0)      # (B, L, 2, np, pt, H * D)
    B, L, _, npg, pt, hd = g.shape
    g = g.reshape(B, L, 2, npg * pt, heads, hd // heads)
    k = jnp.transpose(g[:, :, 0], (1, 0, 2, 3, 4))
    v = jnp.transpose(g[:, :, 1], (1, 0, 2, 3, 4))
    return (k, v)


# --- registry ----------------------------------------------------------------

EXECUTORS: Dict[str, Callable[..., Executor]] = {}


def register_executor(name: str):
    def deco(cls):
        EXECUTORS[name] = cls
        return cls

    return deco


def get_executor(name: str) -> Callable[..., Executor]:
    try:
        return EXECUTORS[name]
    except KeyError:
        raise KeyError(f"unknown executor {name!r}; "
                       f"registered: {sorted(EXECUTORS)}") from None


def executor_names():
    return sorted(EXECUTORS)


# --- built-in executors --------------------------------------------------------


@register_executor("sim")
class SimExecutor:
    """Latency-only executor driven by the analytic cost model.

    Passing a ``BatchingConfig`` opts the executor into runtime-driven
    micro-batching: group launch cost comes from
    ``GRCostModel.batched_rank_ms`` — the sim-side mirror of the live
    ``batched`` executor, keeping ``ClusterSim`` trace-comparable."""

    def __init__(self, cost: GRCostModel,
                 batching: Optional[BatchingConfig] = None,
                 page_tokens: int = 0, segments: bool = False):
        self.cost = cost
        self.batching = batching
        self.page_tokens = int(page_tokens)
        # beyond-prefix segment reuse: the side path also computes the
        # candidate-independent interior segments (UserMeta.seg_lens),
        # and a cache hit ranks only the truly fresh tokens.  Disabled
        # (or with empty seg_lens) every cost is unchanged.
        self.segments = bool(segments)

    def _seg_tokens(self, meta: UserMeta) -> int:
        if not self.segments:
            return 0
        return int(sum(getattr(meta, "seg_lens", ()) or ()))

    def pre_infer(self, meta: UserMeta) -> Tuple[Any, int, float]:
        reuse = meta.prefix_len + self._seg_tokens(meta)
        nbytes = self.cost.kv_bytes(reuse)
        ms = self.cost.pre_infer_ms(reuse)
        return ("psi", meta.user_id, reuse), nbytes, ms

    def rank_cached(self, meta: UserMeta, psi) -> Tuple[Any, float]:
        segs = self._seg_tokens(meta)
        return None, self.cost.rank_on_cache_ms(
            meta.prefix_len + segs, meta.incr_len - segs, meta.n_items)

    def rank_full(self, meta: UserMeta) -> Tuple[Any, float]:
        return None, self.cost.full_rank_ms(
            meta.prefix_len, meta.incr_len, meta.n_items)

    def reload_ms(self, meta: UserMeta, tokens: Optional[int] = None
                  ) -> float:
        t = meta.prefix_len if tokens is None else tokens
        if self.page_tokens:
            # page-granular streaming: resumed reloads pay only for the
            # missing pages — the sim mirror of the paged live store
            return self.cost.paged_load_ms(t, self.page_tokens)
        return self.cost.dram_load_ms(t)

    def rank_group(self, group: Sequence[PendingRank]
                   ) -> Tuple[List[Any], float]:
        """Rank a compatible group in one modelled launch.
        Returns (per-member scores, group wall ms)."""
        per = []
        for w in group:
            m = w.meta
            plen = m.prefix_len if m is not None else w.prefix_len
            if w.psi is not None:
                segs = self._seg_tokens(m) if m is not None else 0
                per.append(self.cost.rank_on_cache_ms(
                    plen + segs, w.incr_len - segs, w.n_items))
            else:
                per.append(self.cost.full_rank_ms(
                    plen, w.incr_len, w.n_items))
        bucket = bucket_of(max(w.prefix_len for w in group))
        return ([None] * len(group),
                self.cost.batched_rank_ms(per, bucket=bucket))

    def pre_infer_group(self, metas: Sequence[UserMeta]
                        ) -> Tuple[List[Tuple[Any, int]], float]:
        """Pre-infer a prefill-grid-compatible group as one modelled
        launch (the batched side path).  Returns
        ([(psi, nbytes), ...], group wall ms) — single-member groups
        cost exactly the per-request ``pre_infer``, keeping uncontended
        traces bit-identical to the unbatched side path."""
        outs, per = [], []
        for m in metas:
            psi, nbytes, ms = self.pre_infer(m)
            outs.append((psi, nbytes))
            per.append(ms)
        bucket = prefill_grid(max(m.prefix_len for m in metas))
        return outs, self.cost.batched_rank_ms(per, bucket=bucket)


RANK_COUNTERS = ("rank_launches", "rank_rows", "rank_pad_rows",
                 "rank_tokens_launched", "rank_tokens_real")


def _launch_args(kind: str, rows: Sequence, pad_rows: int, width: int
                 ) -> dict:
    """A launch span's arguments; ``rows`` are the real rows' metas or
    pending ranks."""
    return {"kind": kind, "rows": len(rows), "pad_rows": pad_rows,
            "bucket": width, "lens": [int(r.prefix_len) for r in rows],
            "uids": [int(r.user_id) for r in rows]}


@register_executor("live")
class LiveExecutor:
    """Runs the real HSTU backbone with jitted prefill / rank steps.

    ``counters`` ledgers the rank launches: real and padding rows, and
    prefix tokens launched (rows x the launch's prefix width) against
    the real prefix lengths.  ``tracer`` (set by the owning runtime)
    spans each launch and its input building, puts and wait."""

    tracer = OFF

    def __init__(self, model, params, store,
                 cost: Optional[GRCostModel] = None, page_tokens: int = 0,
                 segments: bool = False, device_pool: bool = False,
                 device=None):
        import jax
        self._jax = jax
        self.model = model
        # ``device`` binds the executor to one accelerator: params,
        # launch inputs and the device page pool all live there, so the
        # psi an instance produces is ranked where it rests.  None keeps
        # JAX's default device.
        self.device = device
        self.params = (params if device is None
                       else jax.device_put(params, device))
        self.store = store
        self.cost = cost or GRCostModel(model.cfg)
        self.page_tokens = int(page_tokens)
        self.segments = bool(segments)
        # device-resident page pool: the serving window allocates a
        # DevicePagePool and routes page writes through the
        # insert_pages/free_pages hooks below, so rank_with_pages
        # launches pass the pool by reference instead of re-shipping
        # the host buffer (InstanceRuntime wires store <-> executor)
        self.device_pool = bool(device_pool) and self.page_tokens > 0
        # the executor owns compute geometry: a paged window must page
        # THIS model's psi, not the (possibly full-scale) cost model's
        self.page_layout = (PageLayout.from_model_config(
            model.cfg, page_tokens) if page_tokens else None)
        self.counters = dict.fromkeys(RANK_COUNTERS, 0)

        # named, so the device operations in a trace name their program
        def prefill(p, toks):
            return model.prefill(p, {"tokens": toks})

        def rank_cached(p, kv, incr, items):
            return model.rank_with_cache(p, kv, incr, items)

        def rank_full(p, pref, incr, items):
            return model.full_rank(p, pref, incr, items)

        # paged consumption: psi gathered from the page pool inside the
        # jitted launch (device-side gather; no host re-materialization)
        def rank_pages(p, buf, tables, incr, items):
            return model.rank_with_cache(
                p, _gather_psi(jax.numpy, buf, tables, model.cfg.n_heads),
                incr, items)

        self._prefill = jax.jit(prefill)
        self._rank = jax.jit(rank_cached)
        self._rank_full = jax.jit(rank_full)
        self._rank_pages = jax.jit(rank_pages)

    def _round(self, n: int, m: int = 64) -> int:
        return max(m, (n + m - 1) // m * m)  # bucketed shapes: few recompiles

    def _put(self, x):
        """Host array -> this executor's device."""
        return self._jax.device_put(x, self.device)

    def _count_rank(self, rows: Sequence, pad_rows: int, width: int
                    ) -> None:
        c = self.counters
        c["rank_launches"] += 1
        c["rank_rows"] += len(rows)
        c["rank_pad_rows"] += pad_rows
        c["rank_tokens_launched"] += (len(rows) + pad_rows) * width
        c["rank_tokens_real"] += sum(int(r.prefix_len) for r in rows)

    def _pad_segments(self, kv, meta: UserMeta):
        """Append the segmented entry's span slots to live psi: one
        whole-page run of ZERO K/V per interior segment, matching the
        page grid ``PagedHBMStore.insert`` sizes a span-carrying entry
        to.  Zero keys are exact under silu attention (they contribute
        silu(0)·v = 0), so live scores equal the prefix-only launch
        while the span storage/gather machinery runs end-to-end; real
        interior-segment compute rides the Pallas segment kernel
        (``repro.kernels.paged_prefix_attn.segment_rank_attn``)."""
        segs = tuple(getattr(meta, "seg_lens", ()) or ())
        if not (self.segments and self.page_layout is not None and segs):
            return kv
        jnp = self._jax.numpy
        pt = self.page_layout.page_tokens
        extra = sum(pt * ceil_div(int(s), pt) for s in segs)

        def pad(a):
            z = jnp.zeros(a.shape[:2] + (extra,) + a.shape[3:], a.dtype)
            return jnp.concatenate([a, z], axis=2)

        return tuple(pad(a) for a in kv)

    def pre_infer(self, meta: UserMeta) -> Tuple[Any, int, float]:
        tr = self.tracer
        n = self._round(meta.prefix_len)
        with tr.span("exec.prefill",
                     **_launch_args("prefill", [meta], 0, n)):
            with tr.span("exec.prepare"):
                toks = np.resize(self.store.long_term(meta.user_id),
                                 n)[None, :]
            with tr.span("exec.put", bytes=toks.nbytes):
                toks = self._put(toks)
            t0 = time.perf_counter()
            with tr.span("exec.wait"):
                _, kv = self._prefill(self.params, toks)
                kv = self._jax.block_until_ready(kv)
            ms = (time.perf_counter() - t0) * 1e3
            kv = self._pad_segments(kv, meta)
        return kv, kv_nbytes(kv), ms

    def _launch_psi(self, psi):
        """The cached psi a per-request launch ranks with."""
        return psi

    def rank_cached(self, meta: UserMeta, psi) -> Tuple[Any, float]:
        tr = self.tracer
        psi = self._launch_psi(psi)
        paged = isinstance(psi, PagedPsi)
        width = (_pages_of(psi.n_tokens, psi) * psi.layout.page_tokens
                 if paged else int(psi[0].shape[2]))
        self._count_rank([meta], 0, width)
        with tr.span("exec.rank", **_launch_args("cached", [meta], 0,
                                                 width)):
            incr, items = self._request_inputs(meta)
            t0 = time.perf_counter()
            if paged:
                npb = _pages_of(psi.n_tokens, psi)
                with tr.span("exec.put", bytes=_table_bytes([psi], npb)):
                    buf, tables = _page_launch_args(self._put, [psi], npb)
            with tr.span("exec.wait"):
                if paged:
                    scores = self._rank_pages(self.params, buf, tables,
                                              incr, items)
                else:
                    scores = self._rank(self.params, psi, incr, items)
                scores.block_until_ready()
        return scores, (time.perf_counter() - t0) * 1e3

    def rank_full(self, meta: UserMeta) -> Tuple[Any, float]:
        tr = self.tracer
        n = self._full_pad(meta.prefix_len)
        self._count_rank([meta], 0, n)
        with tr.span("exec.rank", **_launch_args("full", [meta], 0, n)):
            with tr.span("exec.prepare"):
                pref = np.resize(self.store.long_term(meta.user_id),
                                 n)[None, :]
            with tr.span("exec.put", bytes=pref.nbytes):
                pref = self._put(pref)
            incr, items = self._request_inputs(meta)
            t0 = time.perf_counter()
            with tr.span("exec.wait"):
                scores = self._rank_full(self.params, pref, incr, items)
                scores.block_until_ready()
        return scores, (time.perf_counter() - t0) * 1e3

    def _request_inputs(self, meta: UserMeta):
        """One request's incremental tokens and candidates, on the
        device."""
        tr = self.tracer
        with tr.span("exec.prepare"):
            incr = self.store.short_term(meta.user_id)[None, :]
            items = self.store.candidates(meta.user_id)[None, :]
        with tr.span("exec.put", bytes=incr.nbytes + items.nbytes):
            return self._put(incr), self._put(items)

    def _full_pad(self, n: int) -> int:
        """Padded prefix length for the full-inference fallback."""
        return self._round(n)

    def reload_ms(self, meta: UserMeta, tokens: Optional[int] = None
                  ) -> float:
        t = meta.prefix_len if tokens is None else tokens
        if self.page_tokens:
            return self.cost.paged_load_ms(t, self.page_tokens)
        return self.cost.dram_load_ms(t)

    # --- device-pool hooks ---------------------------------------------------
    # The paged window routes its page-data movement through the
    # executor (the owner of the jax device), so every path that writes
    # pages — fresh insert, resumed partial reload, handoff re-insert,
    # cold-promotion landing — lands them in the device-resident pool
    # with ONE donated update on this executor's device, and every free
    # goes back through the same conserved free-list accounting.

    def insert_pages(self, pool: DevicePagePool, pages: Sequence[int],
                     src: Any, table: Optional[np.ndarray] = None,
                     first: int = 0) -> int:
        """Land ``pages`` in the device-resident pool.  With ``table``,
        ``src`` is the dense psi ``(K, V)`` and the pages of ``table``
        from page column ``first`` on are written from it
        (``DevicePagePool.land``); without, ``src`` is a host page
        buffer that already holds ``pages`` (``DevicePagePool.scatter``).
        Returns the bytes landed (== len(pages) * page_bytes)."""
        if table is None:
            return pool.scatter(pages, src, device=self.device)
        return pool.land(pages, table, src, first=first,
                         device=self.device)

    def free_pages(self, pool, pages: Sequence[int]) -> None:
        """Return pages to the pool's free list (pin/zombie protection
        applies unchanged).  No device write: a freed page is
        unreachable until realloc lands it again."""
        pool.free(pages)


@register_executor("batched")
class BatchedLiveExecutor(LiveExecutor):
    """LiveExecutor + continuous micro-batching on bucketed shapes.

    Shape discipline is what makes batching correct AND cheap:

      * pre-inference keeps the 64-token grid (psi stays compact);
      * every rank launch — per-request or grouped — snaps the prefix
        axis to the shared ``BUCKETS`` grid (psi zero-padded, which is
        exact for HSTU's silu attention; full-rank prefix tokens tiled,
        matching what the per-request call does after bucketing), so
        batched scores equal per-request scores up to the reduction
        order of the differently batched program;
      * the batch axis snaps to a power-of-two grid by repeating the
        first member (row-independent compute, sliced off afterwards),
        bounding the jit cache to #buckets x log2(max_batch) entries —
        all pre-compiled by ``warmup`` so compiles leave the P99 path;
      * over a paged HBM window (``page_tokens > 0``) the group path
        becomes ``rank_with_pages``: members carry ``PagedPsi`` handles,
        their page tables pad to the page-count bucket with the pool's
        null page, and K/V are gathered from the pool INSIDE the one
        jitted launch — same (bucket, batch) key discipline, scores
        bit-identical to the dense path (tests/test_paging.py).
    """

    def __init__(self, model, params, store,
                 cost: Optional[GRCostModel] = None,
                 batching: Optional[BatchingConfig] = None,
                 page_tokens: int = 0, segments: bool = False,
                 device_pool: bool = False, device=None):
        super().__init__(model, params, store, cost,
                         page_tokens=page_tokens, segments=segments,
                         device_pool=device_pool, device=device)
        self.batching = batching or BatchingConfig()
        self._warmed: set = set()
        self._pool_warmed: set = set()      # (prefill grid, pool pages)

    # --- per-request paths on the bucket grid -------------------------------

    def _launch_psi(self, psi):
        if isinstance(psi, PagedPsi):
            # page tables already pad to the page-count bucket at launch
            return psi
        return pad_psi(self._jax.numpy, psi, bucket_of(psi[0].shape[2]))

    def _full_pad(self, n: int) -> int:
        return bucket_of(n)

    # --- group path ---------------------------------------------------------

    def _batch_grid(self, n: int) -> int:
        """Smallest power-of-two >= n, clamped to max_batch (so a
        non-power-of-two max_batch tops the grid itself)."""
        b = 1
        while b < n and b < self.batching.max_batch:
            b *= 2
        return min(b, self.batching.max_batch)

    def rank_group(self, group: Sequence[PendingRank]
                   ) -> Tuple[List[Any], float]:
        """Execute a compatible group as ONE jitted call.
        Returns (per-member scores, measured group wall ms)."""
        jnp = self._jax.numpy
        tr = self.tracer
        n = len(group)
        bucket = bucket_of(max(w.prefix_len for w in group))
        pad_rows = self._batch_grid(n) - n
        rows = list(group) + [group[0]] * pad_rows
        paged = isinstance(group[0].psi, PagedPsi)
        width = bucket
        if paged:
            # rank_with_pages: ONE launch keyed (page-count bucket,
            # batch grid); K/V stay in the page pool and are gathered
            # through the stacked page tables inside the jit.  The
            # bucket widens to the group's largest member: a segmented
            # entry's whole-page span padding can push its table past
            # the prefix-derived bucket, and truncating it would drop
            # cached pages from the gather (prefix-only members never
            # exceed the prefix bucket, so this is exact for them)
            pt = group[0].psi.layout.page_tokens
            npb = max([page_bucket(bucket, pt)]
                      + [_pages_of(w.psi.n_tokens, w.psi) for w in rows])
            width = npb * pt
        kind = "full" if group[0].psi is None else "cached"
        self._count_rank(group, pad_rows, width)
        with tr.span("exec.rank",
                     **_launch_args(kind, group, pad_rows, width)):
            with tr.span("exec.prepare"):
                incr = np.stack([w.incr if w.incr is not None
                                 else self.store.short_term(w.user_id)
                                 for w in rows])
                items = np.stack([w.items if w.items is not None
                                  else self.store.candidates(w.user_id)
                                  for w in rows])
            t0 = time.perf_counter()
            with tr.span("exec.put", bytes=incr.nbytes + items.nbytes):
                incr, items = self._put(incr), self._put(items)
            if paged:
                with tr.span("exec.put",
                             bytes=_table_bytes([w.psi for w in rows], npb)):
                    buf, tables = _page_launch_args(
                        self._put, [w.psi for w in rows], npb)
            elif group[0].psi is not None:   # homogeneous by aggregator key
                with tr.span("exec.prepare"):
                    kv = stack_psi(jnp, [w.psi for w in rows], bucket)
            else:
                with tr.span("exec.prepare"):
                    pref = np.stack([
                        np.resize(self.store.long_term(w.user_id), bucket)
                        for w in rows])
                with tr.span("exec.put", bytes=pref.nbytes):
                    pref = self._put(pref)
            with tr.span("exec.wait"):
                if paged:
                    scores = self._rank_pages(self.params, buf, tables,
                                              incr, items)
                elif group[0].psi is not None:
                    scores = self._rank(self.params, kv, incr, items)
                else:
                    scores = self._rank_full(self.params, pref, incr,
                                             items)
                scores.block_until_ready()
            ms = (time.perf_counter() - t0) * 1e3
        return [scores[i] for i in range(n)], ms

    def pre_infer_group(self, metas: Sequence[UserMeta]
                        ) -> Tuple[List[Tuple[Any, int]], float]:
        """Batched pre-inference: ONE jitted prefill for a group sharing
        the 64-token prefill grid (the aggregator keys pre work by
        ``prefill_grid``, so every member's padded length is identical).
        The batch axis snaps to the power-of-two grid by repeating the
        first member, and each member's psi slice — rows are
        independent under batched compute — is bit-identical to the psi
        its own per-request ``pre_infer`` call would have produced."""
        tr = self.tracer
        n = self._round(max(m.prefix_len for m in metas))
        pad_rows = self._batch_grid(len(metas)) - len(metas)
        rows = list(metas) + [metas[0]] * pad_rows
        with tr.span("exec.prefill",
                     **_launch_args("prefill", metas, pad_rows, n)):
            with tr.span("exec.prepare"):
                toks = np.stack([np.resize(self.store.long_term(m.user_id),
                                           n) for m in rows])
            t0 = time.perf_counter()
            with tr.span("exec.put", bytes=toks.nbytes):
                toks = self._put(toks)
            with tr.span("exec.wait"):
                _, kv = self._prefill(self.params, toks)
                kv = self._jax.block_until_ready(kv)
            ms = (time.perf_counter() - t0) * 1e3
            outs = []
            for i in range(len(metas)):
                psi = tuple(a[:, i:i + 1] for a in kv)   # (L, 1, n, H, D)
                psi = self._pad_segments(psi, metas[i])
                outs.append((psi, kv_nbytes(psi)))
        return outs, ms

    # --- startup pre-warming -------------------------------------------------

    def warmup(self, prefix_lens: Sequence[int],
               batch_sizes: Sequence[int] = (1,),
               incr_len: int = 64, n_items: int = 512,
               pool_pages: int = 0) -> List[Tuple]:
        """Compile the bucketed rank entry points ahead of traffic.

        ``prefix_lens`` is the expected workload (e.g. the sampled
        arrival stream); the jit-cache guard keeps the
        ``batching.max_buckets_live`` *most frequent* buckets, so the
        traffic-dominant shapes are the warm ones — any dropped bucket
        still compiles lazily on first hit.  Returns the freshly
        compiled (bucket, batch) keys (already-warm keys are skipped).

        With ``page_tokens`` set, also pre-compiles the
        ``rank_with_pages`` entries keyed (page-count bucket, batch) —
        ``pool_pages`` must match the serving store's pool size (the
        pool buffer shape is part of the jit key).  On a device pool it
        also compiles the pool's landing and gather programs for the
        64-token prefill grid of every length (``_warm_pool``)."""
        from collections import Counter
        jax, jnp = self._jax, self._jax.numpy
        cfg = self.model.cfg
        dt = jnp.dtype(cfg.dtype)
        zeros = lambda shape, dtype=jnp.int32: device_zeros(
            shape, dtype, self.device)
        freq = Counter(bucket_of(int(n)) for n in prefix_lens)
        buckets = sorted(b for b, _ in
                         freq.most_common(self.batching.max_buckets_live))
        sizes = sorted({self._batch_grid(int(b)) for b in batch_sizes})
        buf = None
        if self.page_tokens and pool_pages:
            buf = zeros((pool_pages + 1, self.page_tokens,
                         cfg.n_heads * cfg.head_dim), dt)
        done = []
        for bucket in buckets:
            for nb in sizes:
                key = (bucket, nb, incr_len, n_items)
                if key in self._warmed:
                    continue
                z = zeros((cfg.n_layers, nb, bucket, cfg.n_heads,
                           cfg.head_dim), dt)
                incr = zeros((nb, incr_len))
                items = zeros((nb, n_items))
                jax.block_until_ready(
                    self._rank(self.params, (z, z), incr, items))
                pref = zeros((nb, bucket))
                jax.block_until_ready(
                    self._rank_full(self.params, pref, incr, items))
                if buf is not None:
                    npb = page_bucket(bucket, self.page_tokens)
                    tables = zeros((nb, cfg.n_layers, 2, npb))
                    jax.block_until_ready(self._rank_pages(
                        self.params, buf, tables, incr, items))
                self._warmed.add(key)
                done.append(key)
        if self.device_pool and pool_pages:
            self._warm_pool(prefix_lens, pool_pages)
        return done

    def _warm_pool(self, prefix_lens: Sequence[int], pool_pages: int
                   ) -> None:
        """Compile ``DevicePagePool``'s landing (keyed by the psi's
        length) and gather (keyed by its page count) for the prefill
        grid of each of ``prefix_lens``: the lengths a prefill, a DRAM
        reload or a handoff lands and a spill reads back.  Compiled
        ahead of time from shapes, on 8 threads; the
        placement matches the served arrays' (committed to ``device``
        where the executor has one), so the served calls hit."""
        from concurrent.futures import ThreadPoolExecutor
        from jax.sharding import SingleDeviceSharding
        from .paging import _gather_jit, _land_jit
        jax, cfg = self._jax, self.model.cfg
        dt = jax.numpy.dtype(cfg.dtype)
        pt, slabs = self.page_tokens, 2 * cfg.n_layers
        where = (None if self.device is None
                 else SingleDeviceSharding(self.device))
        spec = lambda shape, dtype=dt, sharding=where: jax.ShapeDtypeStruct(
            shape, dtype, sharding=sharding)
        buf = spec((pool_pages + 1, pt, cfg.n_heads * cfg.head_dim))
        grids = sorted(g for g in {prefill_grid(int(n)) for n in prefix_lens}
                       if (g, pool_pages) not in self._pool_warmed)

        def compile_both(g):
            n = ceil_div(g, pt)
            kv = spec((cfg.n_layers, 1, g, cfg.n_heads, cfg.head_dim))
            _land_jit().lower(buf, spec((slabs * n,), np.int32, None),
                              kv, kv).compile()
            _gather_jit().lower(buf, spec((slabs, n), np.int32,
                                          None)).compile()

        with ThreadPoolExecutor(8) as pool:
            list(pool.map(compile_both, grids))
        self._pool_warmed.update((g, pool_pages) for g in grids)
