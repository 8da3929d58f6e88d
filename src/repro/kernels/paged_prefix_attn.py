"""Pallas TPU kernel: paged ranking-with-cache HSTU attention.

The paged consumption path of the RelayGR HBM window: the cached user
prefix psi lives in a fixed-size page pool (``repro.core.paging``) and
the kernel gathers K/V page-by-page through a *page-table BlockSpec
index map* (scalar-prefetch grid), so ranking reads psi straight from
pages — no dense re-materialization of the prefix ever exists in HBM.

Mask semantics are identical to ``prefix_rank_attn``:

  * incremental tokens attend causally over prefix + earlier incr;
  * candidate items attend to prefix + incr + themselves ONLY.

Because HSTU attention is pointwise (silu, fixed 1/n normalizer — no
softmax running max/denominator), the aggregation splits exactly into
a prefix part and a new-token part.  The kernel runs two phases that
share one f32 accumulation chain:

  phase 1  grid (B, nq, n_pages): K/V pages — all heads, in the device
           pool's ``(page_tokens, H * D)`` layout — fetched via
           ``table[b, ip]``, heads looped in-kernel; every query sees the
           whole prefix, so the only mask is per-row residency
           (``ip * page_tokens + j < prefix_len[b]``).  Emits the f32
           partial sums.
  phase 2  grid (B, H, nq, nk): the dense incr+item K/V with the
           n_prefix = 0 rank mask, accumulator INITIALIZED from the
           phase-1 partial — the accumulation order is therefore
           identical to the dense kernel's, so for page-aligned
           prefixes the scores match ``prefix_rank_attn`` (called with
           ``bk = page_tokens``) bit for bit.

Page tables are padded to the launch's page-count bucket with a *null
page* (an always-zero pool row): zero keys contribute silu(0) = 0 —
exactly nothing — so padding is mask-free, matching the dense bucketed
path's zero-padded psi.  Mixed prefix lengths ride in one launch via
the per-row ``prefix_lens`` scalars; the shared ``n_total`` normalizer
is the bucket's padded length, exactly what the dense bucketed caller
uses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _page_heads(q_ref, k_ref, v_ref, acc_ref, keep, scale, inv_n):
    """Accumulate one page into every head's partial sum.  The page
    block is ``(1, page_tokens, H * D)``: the device pool's layout
    (``repro.core.paging.DevicePagePool``), which meets the TPU's
    (8, 128) tiling where a one-head ``(page_tokens, 1, D)`` block does
    not.  Each page is fetched once per (row, query block) and the head
    loop runs inside the kernel."""
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32)        # (bq, D)
        hd = pl.ds(h * q.shape[1], q.shape[1])
        k = k_ref[0, :, hd].astype(jnp.float32)    # (page_tokens, D)
        v = v_ref[0, :, hd].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        a = jnp.where(keep, jax.nn.silu(logits) * inv_n, 0.0)
        acc_ref[h] += jax.lax.dot_general(
            a, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _prefix_pages_kernel(table_ref, plen_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, *, scale, inv_n, page_tokens, n_pages):
    """Phase 1: accumulate the prefix contribution, one page per step."""
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = pl.program_id(0)
    bq = q_ref.shape[2]
    ki = ip * page_tokens + jax.lax.broadcasted_iota(
        jnp.int32, (bq, page_tokens), 1)
    keep = ki < plen_ref[b]                    # residency / padding mask
    _page_heads(q_ref, k_ref, v_ref, acc_ref, keep, scale, inv_n)

    @pl.when(ip == n_pages - 1)
    def _done():
        o_ref[0] = acc_ref[...]


def _new_tokens_kernel(q_ref, k_ref, v_ref, part_ref, o_ref, acc_ref, *,
                       scale, inv_n, bq, bk, n_incr, n_kv_blocks):
    """Phase 2: the incr+item tokens with the n_prefix = 0 rank mask,
    chained onto the phase-1 partial sums."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = part_ref[0, 0]

    # prune: keys strictly after the latest query this block can see
    @pl.when(ik * bk <= iq * bq + (bq - 1))
    def _work():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        a = jax.nn.silu(logits) * inv_n
        qi = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        ki = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        causal = ki <= qi
        is_item_q = qi >= n_incr
        is_item_k = ki >= n_incr
        self_key = ki == qi
        # pure logical ops (see prefix_rank_attn: a boolean select lowers
        # to a truncation the TPU compiler refuses)
        items_ok = ~is_item_q | ~is_item_k | self_key
        a = jnp.where(causal & items_ok, a, 0.0)
        acc_ref[...] += jax.lax.dot_general(
            a, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_kv_blocks - 1)
    def _done():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_incr", "bq", "bk", "n_total", "interpret"))
def paged_prefix_rank_attn(q, k_pages, v_pages, page_table, prefix_lens,
                           k_new, v_new, *, n_incr: int, bq: int = 128,
                           bk: int = 0, n_total: float = None,
                           interpret: bool = False):
    """Rank with psi gathered from the page pool.

    q:                (B, H, Sq, D)   incr + item queries
    k_pages, v_pages: (N + 1, page_tokens, H * D) pool buffers in the
                      device layout — row N is the all-zero null page
                      used to pad tables
    page_table:       (B, n_pages) int32 page ids for each row's prefix
                      (pad with the null page up to the bucket)
    prefix_lens:      (B,) int32 true prefix tokens per row
    k_new, v_new:     (B, H, Sq, D)   incr + item keys/values

    ``n_total`` defaults to the bucket's padded context,
    ``n_pages * page_tokens + Sq`` — the same normalizer the dense
    bucketed caller uses on zero-padded psi.  ``bk`` defaults to
    ``page_tokens`` so the phase-2 block decomposition continues the
    phase-1 page decomposition (bit-for-bit with the dense kernel).
    """
    B, H, Sq, D = q.shape
    page_tokens = k_pages.shape[1]
    n_pages = page_table.shape[1]
    bq = min(bq, Sq)
    bk = min(bk or page_tokens, Sq)
    assert Sq % bq == 0 and Sq % bk == 0, (Sq, bq, bk)
    nq, nk = Sq // bq, Sq // bk
    scale = 1.0 / np.sqrt(D)
    inv_n = 1.0 / (n_total or (n_pages * page_tokens + Sq))

    # --- phase 1: prefix pages via the page-table index map ---------------
    grid1 = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # page_table, prefix_lens
        grid=(B, nq, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, bq, D),
                         lambda b, iq, ip, tr, lr: (b, 0, iq, 0)),
            pl.BlockSpec((1, page_tokens, H * D),
                         lambda b, iq, ip, tr, lr: (tr[b, ip], 0, 0)),
            pl.BlockSpec((1, page_tokens, H * D),
                         lambda b, iq, ip, tr, lr: (tr[b, ip], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, bq, D),
                               lambda b, iq, ip, tr, lr: (b, 0, iq, 0)),
        scratch_shapes=[pltpu.VMEM((H, bq, D), jnp.float32)],
    )
    kernel1 = functools.partial(
        _prefix_pages_kernel, scale=scale, inv_n=inv_n,
        page_tokens=page_tokens, n_pages=n_pages)
    partial = pl.pallas_call(
        kernel1, grid_spec=grid1,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), jnp.float32),
        interpret=interpret,
    )(page_table, prefix_lens, q, k_pages, v_pages)

    # --- phase 2: dense incr+items, accumulator chained from phase 1 ------
    kernel2 = functools.partial(
        _new_tokens_kernel, scale=scale, inv_n=inv_n, bq=bq, bk=bk,
        n_incr=n_incr, n_kv_blocks=nk)
    return pl.pallas_call(
        kernel2,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k_new, v_new, partial)


def _segment_pages_kernel(table_ref, pos_ref, valid_ref, q_ref, qpos_ref,
                          k_ref, v_ref, o_ref, acc_ref, *, scale, inv_n,
                          page_tokens, n_pages):
    """Segment phase 1: accumulate the CACHED-SPAN contribution, one
    page per step.  Unlike the prefix kernel, pages carry arbitrary
    token spans: ``pos_ref[b, ip]`` is the page's global position base
    and ``valid_ref[b, ip]`` its resident token count, so the mask is
    per-(query, key) — residency AND global-position causality (a
    fresh token between two cached segments must not see the later
    segment; items' positions exceed every cached position, so the
    same causal test covers them)."""
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = pl.program_id(0)
    bq = q_ref.shape[2]
    j = jax.lax.broadcasted_iota(jnp.int32, (bq, page_tokens), 1)
    qp = qpos_ref[0]                           # (bq, 1) global positions
    resident = j < valid_ref[b, ip]
    causal = pos_ref[b, ip] + j <= qp
    _page_heads(q_ref, k_ref, v_ref, acc_ref, resident & causal, scale,
                inv_n)

    @pl.when(ip == n_pages - 1)
    def _done():
        o_ref[0] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "n_items", "bq", "bk", "n_total", "interpret"))
def segment_rank_attn(q, k_pages, v_pages, page_table, page_pos,
                      page_valid, q_pos, k_new, v_new, *, n_items: int,
                      bq: int = 128, bk: int = 0, n_total: float = None,
                      interpret: bool = False):
    """Rank with psi gathered from an ordered list of cached SPANS
    (beyond-prefix segment reuse, RcLLM-style): the prefix plus any
    candidate-independent interior segments live in the page pool; the
    fresh tokens interleave between them at their global positions.

    q:                (B, H, Sq, D) FRESH tokens (fresh incr + items)
    k_pages, v_pages: (N + 1, page_tokens, H * D) pool buffers in the
                      device layout — row N is the all-zero null page
                      used to pad tables
    page_table:       (B, n_pages) int32 page ids over the row's cached
                      spans, in span order (pad with the null page)
    page_pos:         (B, n_pages) int32 global position of each page's
                      first token (0 for null-padded slots)
    page_valid:       (B, n_pages) int32 resident tokens per page
                      (0 for null-padded slots)
    q_pos:            (B, Sq) int32 global positions of the fresh
                      tokens, strictly increasing per row; the last
                      ``n_items`` are the candidate items
    k_new, v_new:     (B, H, Sq, D) fresh keys/values (same positions)

    Phase 1 walks the span pages with the residency + global-position
    causal mask; phase 2 is the UNCHANGED dense new-token kernel (the
    fresh tokens share one position array, so local causality equals
    global causality), chained onto the phase-1 partials.  With a
    single span at positions [0, prefix_len) and
    ``q_pos = prefix_len + arange(Sq)`` every mask bit equals the
    prefix kernel's, so the degenerate call is bit-identical to
    ``paged_prefix_rank_attn`` (tests/test_kernels.py).
    """
    B, H, Sq, D = q.shape
    page_tokens = k_pages.shape[1]
    n_pages = page_table.shape[1]
    bq = min(bq, Sq)
    bk = min(bk or page_tokens, Sq)
    assert Sq % bq == 0 and Sq % bk == 0, (Sq, bq, bk)
    nq, nk = Sq // bq, Sq // bk
    scale = 1.0 / np.sqrt(D)
    inv_n = 1.0 / (n_total or (n_pages * page_tokens + Sq))

    # --- phase 1: cached spans via the segment-table index map ------------
    # q_pos is handed over as a (B, Sq, 1) column view: a (1, bq) block
    # over (B, Sq) has a second-minor block dim of 1, which the TPU
    # tiling refuses for B > 1, and the column arrives in the (query,
    # key) mask orientation without an in-kernel relayout
    grid1 = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # page_table, page_pos, page_valid
        grid=(B, nq, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, bq, D),
                         lambda b, iq, ip, tr, pr, vr: (b, 0, iq, 0)),
            pl.BlockSpec((1, bq, 1),
                         lambda b, iq, ip, tr, pr, vr: (b, iq, 0)),
            pl.BlockSpec((1, page_tokens, H * D),
                         lambda b, iq, ip, tr, pr, vr: (tr[b, ip], 0, 0)),
            pl.BlockSpec((1, page_tokens, H * D),
                         lambda b, iq, ip, tr, pr, vr: (tr[b, ip], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, bq, D),
                               lambda b, iq, ip, tr, pr, vr: (b, 0, iq, 0)),
        scratch_shapes=[pltpu.VMEM((H, bq, D), jnp.float32)],
    )
    kernel1 = functools.partial(
        _segment_pages_kernel, scale=scale, inv_n=inv_n,
        page_tokens=page_tokens, n_pages=n_pages)
    partial = pl.pallas_call(
        kernel1, grid_spec=grid1,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), jnp.float32),
        interpret=interpret,
    )(page_table, page_pos, page_valid, q, q_pos[:, :, None], k_pages,
      v_pages)

    # --- phase 2: dense fresh tokens, identical to the prefix path --------
    kernel2 = functools.partial(
        _new_tokens_kernel, scale=scale, inv_n=inv_n, bq=bq, bk=bk,
        n_incr=Sq - n_items, n_kv_blocks=nk)
    return pl.pallas_call(
        kernel2,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k_new, v_new, partial)


def pack_segments(k_cached, v_cached, spans, page_tokens: int,
                  n_pages: int = None):
    """Test/reference helper: slice per-row cached tokens into span-
    aware pool buffers, mimicking what the span-aware paged store does
    at insert.  ``k_cached``/``v_cached`` are (B, H, C, D) with row
    ``b``'s cached tokens packed contiguously in span order;
    ``spans[b]`` is an ordered list of (global_start, length) pairs.
    Every span pads to whole pages (the store's residency unit).
    Returns (k_pages, v_pages, table, page_pos, page_valid) with the
    pool in the device layout (N + 1, page_tokens, H * D) and the
    all-zero null page as its last row."""
    k_cached, v_cached = np.asarray(k_cached), np.asarray(v_cached)
    B, H, C, D = k_cached.shape
    per_row = [sum(-(-int(ln) // page_tokens) for _, ln in row)
               for row in spans]
    n_pages = n_pages or max(per_row)
    total = sum(per_row)
    kp = np.zeros((total + 1, page_tokens, H, D), k_cached.dtype)
    vp = np.zeros_like(kp)
    table = np.full((B, n_pages), total, np.int32)     # pad = null page
    page_pos = np.zeros((B, n_pages), np.int32)
    page_valid = np.zeros((B, n_pages), np.int32)
    pid = 0
    for b, row in enumerate(spans):
        off = 0           # consumed cached tokens within this row
        slot = 0
        for start, ln in row:
            for j in range(-(-int(ln) // page_tokens)):
                lo, hi = j * page_tokens, min((j + 1) * page_tokens,
                                              int(ln))
                kp[pid, :hi - lo] = np.moveaxis(
                    k_cached[b, :, off + lo:off + hi], 0, 1)
                vp[pid, :hi - lo] = np.moveaxis(
                    v_cached[b, :, off + lo:off + hi], 0, 1)
                table[b, slot] = pid
                page_pos[b, slot] = int(start) + lo
                page_valid[b, slot] = hi - lo
                pid += 1
                slot += 1
            off += int(ln)
    return (kp.reshape(total + 1, page_tokens, H * D),
            vp.reshape(total + 1, page_tokens, H * D), table, page_pos,
            page_valid)


def pack_pages(k_dense, v_dense, prefix_lens, page_tokens: int,
               n_pages: int = None):
    """Test/reference helper: slice dense per-row prefixes — (B, H, P,
    D) — into pool buffers + page tables, mimicking what the paged HBM
    store does at insert.  Returns (k_pages, v_pages, table (B, np),
    prefix_lens i32) with the pool in the device layout (N + 1,
    page_tokens, H * D); its last row is the all-zero null page."""
    k_dense, v_dense = np.asarray(k_dense), np.asarray(v_dense)
    B, H, P, D = k_dense.shape
    plens = np.asarray(prefix_lens, np.int32)
    per_row = [-(-int(p) // page_tokens) for p in plens]
    n_pages = n_pages or max(per_row)
    total = sum(per_row)
    kp = np.zeros((total + 1, page_tokens, H, D), k_dense.dtype)
    vp = np.zeros_like(kp)
    table = np.full((B, n_pages), total, np.int32)     # pad = null page
    pid = 0
    for b in range(B):
        for j in range(per_row[b]):
            lo, hi = j * page_tokens, min((j + 1) * page_tokens, int(plens[b]))
            kp[pid, :hi - lo] = np.moveaxis(k_dense[b, :, lo:hi], 0, 1)
            vp[pid, :hi - lo] = np.moveaxis(v_dense[b, :, lo:hi], 0, 1)
            table[b, j] = pid
            pid += 1
    return (kp.reshape(total + 1, page_tokens, H * D),
            vp.reshape(total + 1, page_tokens, H * D), table, plens)
