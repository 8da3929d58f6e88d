"""Block-granular psi storage: the fixed-size HBM page pool.

The unpaged window stores each admitted psi(u) as one monolithic pytree,
so mixed prefix lengths fragment the ``r1 * HBM`` budget (invariant I2)
and every spill/reload moves a whole prefix.  Paging fixes both: the
budget is carved into fixed-size pages of ``page_tokens`` tokens each,
an entry owns a *page table* instead of a dense buffer, and the only
waste is the zero padding of each slab's last page.

Layout.  psi(u) is the per-layer (K, V) pytree of shape
``(L, B, P, H, D)``; paging slices the token axis P.  Each of the
``2 * L`` K/V planes — called *slabs* here — is paged independently, so
one page holds ``page_tokens`` tokens of ONE slab, shaped
``(page_tokens, H, D)``.  A ``PagedPsi`` handle carries the
``(slabs, n_pages)`` page table; the paged Pallas kernel
(``repro.kernels.paged_prefix_attn``) and the live executor's
``rank_with_pages`` path gather K/V directly from the pool through it.

Accounting is conserved at page granularity, mirroring the entry-level
turnstile of the HBM window:

    stats["pages_allocated"] == pages_live + stats["pages_freed"]

after any interleaving, and the free list never double-allocates
(tests/test_cache_properties.py).  Pages referenced by an in-flight
rank launch are *pinned*: freeing a pinned page parks it in a zombie
set (still occupying the pool, still "live") and the release after the
launch returns it to the free list — so a batched group can never read
a page the window recycled under it.

``DevicePagePool`` keeps the same bookkeeping but makes the data plane
a device-resident jax array mutated in place, and the only copy of the
pages it holds: dense psi lands in it with one donated
``.at[pages].set(...)`` update on the device (``DevicePagePool.land``),
rank launches pass the pool by reference (zero per-launch re-ship), and
``PagedPsi.materialize`` gathers pages back on the device and pulls the
dense copy with one device-to-host transfer.  The ``h2d`` ledger on
every pool accounts the traffic either way.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .tracing import OFF


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class PageLayout:
    """Static geometry of the page pool for one model family."""
    page_tokens: int
    slabs: int                  # independently paged K/V planes: 2 * L
    token_bytes: int            # bytes per token per slab: H * D * itemsize

    @property
    def page_bytes(self) -> int:
        return self.page_tokens * self.token_bytes

    def pages_per_slab(self, tokens: int) -> int:
        return ceil_div(max(int(tokens), 1), self.page_tokens)

    def entry_pages(self, tokens: int) -> int:
        """Pool pages held by a fully resident psi of ``tokens`` tokens."""
        return self.slabs * self.pages_per_slab(tokens)

    def entry_bytes(self, tokens: int) -> int:
        return self.entry_pages(tokens) * self.page_bytes

    @classmethod
    def from_model_config(cls, cfg, page_tokens: int) -> "PageLayout":
        # pages must tile the 64-token shape-bucket grid exactly, or the
        # paged launch pads to a different context length than the dense
        # bucketed path and the 1/n_total normalizer silently diverges —
        # fail at config time instead of producing wrong scores
        if page_tokens <= 0 or 64 % int(page_tokens) != 0:
            raise ValueError(
                f"page_tokens={page_tokens} must divide the 64-token "
                f"bucket grid (1, 2, 4, 8, 16, 32 or 64) so paged and "
                f"dense launches share shape buckets and normalizers")
        itemsize = 4 if cfg.dtype == "float32" else 2
        return cls(page_tokens=int(page_tokens),
                   slabs=2 * cfg.n_layers,
                   token_bytes=cfg.n_heads * cfg.head_dim * itemsize)


H2D_KEYS = ("bytes_scattered", "pages_scattered", "scatters",
            "launch_reships", "reshipped_bytes", "d2h_bytes",
            "mirror_bytes", "materialized_bytes", "device_sourced_bytes")


class PagePool:
    """Free-list allocator over a fixed number of pages.

    Pure bookkeeping — data lives in the owner's (optional) page buffer,
    indexed by the ids handed out here.  Conservation invariant:
    ``stats["pages_allocated"] == pages_live + stats["pages_freed"]``
    where a page stays *live* from alloc until it actually returns to
    the free list (a freed-but-pinned zombie is still live: it occupies
    pool capacity until the pinning launch releases it).
    """

    tracer = OFF            # the owning window's (``PagedHBMStore.tracer``)

    def __init__(self, n_pages: int, page_bytes: int):
        self.n_pages = int(n_pages)
        self.page_bytes = int(page_bytes)
        self._free: List[int] = list(range(self.n_pages - 1, -1, -1))
        self._pins: Dict[int, int] = {}     # page id -> in-flight refs
        self._zombies: set = set()          # freed while pinned
        self.stats = {"pages_allocated": 0, "pages_freed": 0,
                      "alloc_failures": 0, "peak_pages": 0}
        # host->device traffic ledger.  On a DevicePagePool the landing
        # side counts every page written into the device-resident buffer
        # (``bytes_scattered`` == bytes of freshly written pages, of
        # which ``device_sourced_bytes`` came from psi already on the
        # device) and ``launch_reships`` stays 0; on a host-buffer pool
        # the launch path counts each whole-pool re-ship instead.  The
        # host side of psi's trip is counted too: ``d2h_bytes`` pulled
        # off the device (a host pool's staging pull, a device pool's
        # materialize), ``mirror_bytes`` of pages written into a host
        # page buffer, ``materialized_bytes`` of dense host copies
        # gathered out of the pool (spill, evict, handoff).
        self.h2d = dict.fromkeys(H2D_KEYS, 0)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def zombie_pages(self) -> int:
        return len(self._zombies)

    @property
    def pages_live(self) -> int:
        return self.n_pages - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` page ids, or None (and a counted failure) if the
        free list is short — the caller evicts and retries."""
        if n > len(self._free):
            self.stats["alloc_failures"] += 1
            return None
        pages = [self._free.pop() for _ in range(n)]
        self.stats["pages_allocated"] += n
        self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                       self.pages_live)
        return pages

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            if self._pins.get(p, 0) > 0:
                self._zombies.add(p)        # still live until unpinned
            else:
                self._free.append(p)
                self.stats["pages_freed"] += 1

    def pin(self, pages: Sequence[int]) -> None:
        for p in pages:
            self._pins[p] = self._pins.get(p, 0) + 1

    def unpin(self, pages: Sequence[int]) -> None:
        for p in pages:
            n = self._pins.get(p, 0) - 1
            if n <= 0:
                self._pins.pop(p, None)
                if p in self._zombies:      # deferred free fires now
                    self._zombies.discard(p)
                    self._free.append(p)
                    self.stats["pages_freed"] += 1
            else:
                self._pins[p] = n


def device_pages(pages: np.ndarray) -> np.ndarray:
    """A page buffer ``(n, page_tokens, H, D)`` as the device stores
    it, ``(n, page_tokens, H * D)`` — a view, no copy."""
    return pages.reshape(pages.shape[0], pages.shape[1], -1)


def device_zeros(shape, dtype, device=None):
    """Zeros filled on ``device`` itself and committed there (None:
    JAX's default device).  ``jnp.zeros(..., device=d)`` fills on the
    default device and then copies to ``d``, which for a pool-sized
    buffer costs the pool's bytes a second time on the default device."""
    import jax
    import jax.numpy as jnp
    if device is None:
        return jnp.zeros(shape, dtype)
    with jax.default_device(device):
        return jax.device_put(jnp.zeros(shape, dtype), device)


@functools.lru_cache(maxsize=None)
def _scatter_jit():
    """Jitted donated page scatter, shared by every DevicePagePool so
    the compile cache is per-(pool shape, batch grid), not per-pool.
    Donating the pool argument lets XLA update the buffer in place —
    the pool is never copied on insert."""
    import jax

    def pool_scatter(buf, idx, vals):
        return buf.at[idx].set(vals)

    return jax.jit(pool_scatter, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _land_jit():
    """Jitted donated landing of dense psi ``(K, V)``, each ``(L, 1, P,
    H, D)``, into the pool rows ``idx`` (the page table flattened, one
    row of ``page_tokens`` tokens per page, in slab order: slab ``2l``
    is layer ``l``'s K, ``2l + 1`` its V).  The token axis zero-pads to
    whole pages, so padded tokens read silu(0) = 0.  Rows past the
    buffer's end are dropped: that is how a resumed reload leaves its
    resident head pages unwritten.  One program per (pool shape, psi
    length), named ``pool_scatter`` as the host-buffer form is: both
    write landed pages into the pool."""
    import jax
    import jax.numpy as jnp

    def pool_scatter(buf, idx, k, v):
        _, pt, hd = buf.shape
        L, _, P = k.shape[:3]
        n = idx.shape[0] // (2 * L)
        idx = idx.reshape(L, 2, n)
        # K and V write separately: stacking them first would cost the
        # v5e compiler a temporary of twice the psi
        for plane, a in enumerate((k, v)):
            rows = jnp.pad(a[:, 0].reshape(L, P, hd),
                           ((0, 0), (0, max(n * pt - P, 0)), (0, 0)))
            buf = buf.at[idx[:, plane].reshape(-1)].set(
                rows[:, :n * pt].reshape(-1, pt, hd), mode="drop")
        return buf

    return jax.jit(pool_scatter, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _gather_jit():
    """Jitted gather of a page table ``(slabs, n)`` out of the pool into
    one dense ``(2, L, 1, n * page_tokens, H * D)`` array: K planes,
    then V planes.  One program per (pool shape, page count)."""
    import jax

    def pool_gather(buf, table):
        slabs, n = table.shape
        _, pt, hd = buf.shape
        # order the (small) table K-first, so the gather lands in the
        # output's layout and needs no transpose of the pages
        planes = table.reshape(slabs // 2, 2, n).transpose(1, 0, 2)
        return buf[planes].reshape(2, slabs // 2, 1, n * pt, hd)

    return jax.jit(pool_gather)


class DevicePagePool(PagePool):
    """Page pool whose data plane is a device-resident array mutated in
    place, and the only copy of the pages it holds.  Dense psi lands
    with ``land``: one donated ``.at[pages].set(...)`` update on the
    device writes the entry's pages, so psi that is already on the
    device never crosses the link and a host value crosses it once.
    Rank launches pass the buffer by reference (zero per-launch
    host->device re-ship), and ``gather`` reads an entry's pages back
    into a dense host copy with one device-to-host pull.

    Bookkeeping (free list, pins, zombies, conservation) is inherited
    unchanged, so stale-page reuse is impossible by construction: a
    freed page cannot re-enter a table until the allocator hands it out
    again, and every allocation is rewritten before any launch can
    reference it — the stale device bytes of a recycled page are
    unreadable in between.  The buffer starts as device-side zeros, so
    the null page (row ``n_pages``) reads zero and
    ``h2d["bytes_scattered"]`` counts exactly the landed page bytes.

    On the device a page is stored as ``(page_tokens, H * D)`` rows
    (``device_pages``): the same bytes as a host ``(page_tokens, H, D)``
    page.  With (H, D) = (4, 64) as the two minor axes a page does not
    fill the TPU's (8, 128) tile, and the compiler then lays the pool
    out page-minor and relays ALL of it on every scatter and gather (2x
    and 1x the pool in temporaries for the v5e compiler); flat rows tile
    exactly, so the donated update is truly in place."""

    def __init__(self, n_pages: int, page_bytes: int):
        super().__init__(n_pages, page_bytes)
        self.device_buffer = None           # lazily shaped, jax array
        self.device = None                  # where the buffer lives
        self.head_shape: Optional[Tuple[int, int]] = None   # (H, D)

    def ensure_device(self, row_shape: Tuple[int, int], dtype, device=None):
        """Create the resident buffer, ``(n_pages + 1, *row_shape)``, on
        first use — device-side zeros, so creation itself moves no bytes
        over the link.  ``device`` is the owning executor's (None: JAX's
        default device); the buffer stays there."""
        if self.device_buffer is None:
            self.device_buffer = device_zeros(
                (self.n_pages + 1,) + tuple(row_shape), dtype, device)
            self.device = device
        return self.device_buffer

    def scatter(self, pages: Sequence[int], host_buffer: np.ndarray,
                device=None) -> int:
        """Land ``pages`` already written into a host page buffer
        ``(n_pages + 1, page_tokens, H, D)``.  The page-id axis pads to
        a power-of-two grid by repeating the first page (same index,
        same value — set() is idempotent), bounding the jit cache to
        log2(n_pages) entries.  Returns the logical bytes moved."""
        pages = [int(p) for p in pages]
        if not pages:
            return 0
        import jax
        buf = self.ensure_device(device_pages(host_buffer).shape[1:],
                                 host_buffer.dtype, device)
        self.head_shape = tuple(host_buffer.shape[2:])
        grid = 1
        while grid < len(pages):
            grid *= 2
        idx = np.asarray(pages + [pages[0]] * (grid - len(pages)), np.int32)
        # donation invalidates ``buf`` on an accelerator: only the
        # returned array may be read after this call
        self.device_buffer = _scatter_jit()(
            buf, jax.device_put(idx, device),
            jax.device_put(device_pages(host_buffer[idx]), device))
        return self._count_landed(len(pages), device_sourced=False)

    def land(self, pages: Sequence[int], table: np.ndarray, value: Any,
             first: int = 0, device=None) -> int:
        """Write dense psi ``value`` — ``(K, V)``, each ``(L, 1, P, H,
        D)`` — into the pages of ``table`` ``(slabs, n)`` from page
        column ``first`` on (nonzero for a resumed reload, whose head
        pages stay as they are); ``pages`` are the pages written.  A
        value on the device is read where it is; a host value takes one
        put of the dense psi.  Returns the bytes landed."""
        import jax
        k, v = value
        on_device = isinstance(k, jax.Array)
        hd = k.shape[3] * k.shape[4]
        with self.tracer.span("window.stage", pages=len(pages),
                              put_bytes=0 if on_device
                              else k.nbytes + v.nbytes):
            page_tokens = self.page_bytes // (hd * k.dtype.itemsize)
            buf = self.ensure_device((page_tokens, hd), k.dtype, device)
            self.head_shape = tuple(k.shape[3:])
            idx = np.array(table, np.int32)
            idx[:, :first] = self.n_pages + 1       # past the end: dropped
            if not on_device or (self.device is not None
                                 and k.devices() != {self.device}):
                k, v = jax.device_put((k, v), self.device)
        nbytes = len(pages) * self.page_bytes
        with self.tracer.span("window.scatter", pages=len(pages),
                              bytes=nbytes):
            # donation invalidates ``buf`` on an accelerator: only the
            # returned array may be read after this call
            self.device_buffer = _land_jit()(buf, idx.reshape(-1), k, v)
        return self._count_landed(len(pages), device_sourced=on_device)

    def _count_landed(self, n_pages: int, device_sourced: bool) -> int:
        nbytes = n_pages * self.page_bytes
        self.h2d["bytes_scattered"] += nbytes
        self.h2d["pages_scattered"] += n_pages
        self.h2d["scatters"] += 1
        if device_sourced:
            self.h2d["device_sourced_bytes"] += nbytes
        return nbytes

    def gather(self, table: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """The dense host ``(K, V)``, each ``(L, 1, n * page_tokens, H,
        D)``, of the pages of ``table`` ``(slabs, n)``: gathered on the
        device, pulled with one device-to-host copy, no host copy after
        it."""
        assert self.device_buffer is not None, "no page data landed"
        out = np.asarray(_gather_jit()(self.device_buffer, table))
        self.h2d["d2h_bytes"] += out.nbytes
        out = out.reshape(out.shape[:4] + self.head_shape)
        return out[0], out[1]


class PagedPsi:
    """Handle to a paged psi: the page table plus the pool it lives in.

    This is what a paged ``CacheEntry.value`` holds in live mode and
    what ``classify_rank`` snapshots for a (possibly deferred) batched
    launch.  ``table`` is ``(slabs, n_pages)`` int32 — row ``2*l`` is
    layer ``l``'s K plane, row ``2*l + 1`` its V plane.  ``buffer`` is
    a host pool's page buffer; it is None on a ``DevicePagePool``,
    whose device buffer holds the only copy.  ``materialize`` gathers
    back to the dense host ``(L, 1, P, H, D)`` (K, V) pytree — used
    when psi leaves the pool (DRAM spill, eviction, handoff) — with P
    padded to the page grid (zero tail, exact for HSTU's silu
    attention); on a device pool the gather runs on the device.
    """

    def __init__(self, table: np.ndarray, n_tokens: int, layout: PageLayout,
                 buffer: Optional[np.ndarray], spans=None,
                 pool: Optional[PagePool] = None):
        self.table = np.asarray(table, np.int32)
        self.n_tokens = int(n_tokens)
        self.layout = layout
        self.buffer = buffer
        # owning pool (when handed out by a PagedHBMStore): lets the
        # launch path pass a DevicePagePool's resident buffer by
        # reference instead of re-shipping the host pool per launch
        self.pool = pool
        # beyond-prefix reuse: ordered (global_start, valid_len) cached
        # spans; None for prefix-only psi.  Each span occupies whole
        # pages (``n_tokens`` is the padded total), so the consumer can
        # derive the kernel's page_pos/page_valid tables from it.
        self.spans = tuple(spans) if spans else None

    @property
    def pages(self) -> List[int]:
        return [int(p) for p in self.table.reshape(-1)]

    def materialize(self) -> Any:
        slabs, np_ = self.table.shape
        nbytes = slabs * np_ * self.layout.page_bytes
        pool = self.pool
        tracer = pool.tracer if pool is not None else OFF
        with tracer.span("window.materialize", bytes=nbytes):
            if self.buffer is None and isinstance(pool, DevicePagePool):
                out = pool.gather(self.table)
            else:
                assert self.buffer is not None, \
                    "sim-mode psi has no page data"
                # (slabs, n_pages, pt, H, D) -> (slabs, P_padded, H, D)
                flat = self.buffer[self.table].reshape(
                    slabs, np_ * self.layout.page_tokens,
                    *self.buffer.shape[2:])
                k = flat[0::2][:, None]             # (L, 1, P, H, D)
                v = flat[1::2][:, None]
                out = (k.copy(), v.copy())
        if pool is not None:
            pool.h2d["materialized_bytes"] += nbytes
        return out


def slice_into_pages(buffer: np.ndarray, table: np.ndarray, value: Any,
                     page_tokens: int, t0: int = 0) -> None:
    """Write the dense psi pytree ``value`` — per-layer (K, V) arrays of
    shape (L, B, P, H, D) — into pool ``buffer`` pages named by
    ``table`` (slabs, n_pages), starting at token ``t0`` (page-aligned;
    nonzero for partial-reload resume).  The tail of the last page is
    zeroed so padded tokens contribute silu(0) = 0 exactly."""
    k, v = value
    k, v = np.asarray(k), np.asarray(v)
    P = k.shape[2]
    assert t0 % page_tokens == 0, (t0, page_tokens)
    for slab in range(table.shape[0]):
        src = (k if slab % 2 == 0 else v)[slab // 2, 0]   # (P, H, D)
        for j in range(t0 // page_tokens, table.shape[1]):
            pid = int(table[slab, j])
            lo = j * page_tokens
            hi = min(lo + page_tokens, P)
            n = max(hi - lo, 0)
            if n > 0:
                buffer[pid, :n] = src[lo:hi]
            buffer[pid, n:] = 0.0
