"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attn import decode_attn
from repro.kernels.hstu_attn import hstu_attn
from repro.kernels.paged_prefix_attn import (pack_pages, pack_segments,
                                             paged_prefix_rank_attn,
                                             segment_rank_attn)
from repro.kernels.prefix_rank_attn import prefix_rank_attn

RNG = np.random.default_rng(7)


def _mk(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: dict(atol=3e-4, rtol=3e-4),
       jnp.bfloat16: dict(atol=6e-2, rtol=6e-2)}


@pytest.mark.parametrize("S,bq,bk", [(128, 128, 128), (256, 128, 64),
                                     (512, 256, 256), (1024, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("D", [64, 128])
def test_hstu_attn_sweep(S, bq, bk, dtype, D):
    B, H = 2, 2
    q, k, v = (_mk((B, H, S, D), dtype) for _ in range(3))
    out = hstu_attn(q, k, v, bq=bq, bk=bk, interpret=True)
    want = ref.hstu_attn_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("n_prefix,n_incr,n_items",
                         [(128, 64, 64), (256, 64, 192), (512, 128, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefix_rank_attn_sweep(n_prefix, n_incr, n_items, dtype):
    B, H, D = 2, 2, 64
    Sq, Sk = n_incr + n_items, n_prefix + n_incr + n_items
    q = _mk((B, H, Sq, D), dtype)
    k = _mk((B, H, Sk, D), dtype)
    v = _mk((B, H, Sk, D), dtype)
    out = prefix_rank_attn(q, k, v, n_prefix=n_prefix, n_incr=n_incr,
                           bq=64, bk=64, interpret=True)
    want = ref.prefix_rank_attn_ref(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), n_prefix=n_prefix, n_incr=n_incr)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def _paged_case(plens, bucket, pt, n_incr, n_items, dtype, seed=3):
    """Build matched dense/paged inputs: dense psi zero-padded to the
    bucket (what the bucketed batched path feeds prefix_rank_attn) and
    the same prefixes sliced into pool pages + page tables."""
    rng = np.random.default_rng(seed)
    B, H, D = len(plens), 2, 64
    Sq = n_incr + n_items
    mk = lambda *s: rng.normal(size=s).astype(np.float32)
    q, kn, vn = (jnp.asarray(mk(B, H, Sq, D), dtype) for _ in range(3))
    kp = np.zeros((B, H, bucket, D), np.float32)
    vp = np.zeros_like(kp)
    for b, p in enumerate(plens):
        kp[b, :, :p], vp[b, :, :p] = mk(H, p, D), mk(H, p, D)
    kp, vp = jnp.asarray(kp, dtype), jnp.asarray(vp, dtype)
    kpg, vpg, table, pl_ = pack_pages(kp, vp, plens, pt,
                                      n_pages=bucket // pt)
    return q, kp, vp, kn, vn, (jnp.asarray(kpg), jnp.asarray(vpg),
                               jnp.asarray(table), jnp.asarray(pl_))


@pytest.mark.parametrize("n_prefix,pt,n_incr,n_items",
                         [(128, 64, 32, 32), (256, 64, 32, 32),
                          (256, 128, 64, 64)])
def test_paged_rank_attn_bitwise_aligned(n_prefix, pt, n_incr, n_items):
    """Page-aligned prefixes: the paged kernel's two-phase accumulation
    chain reproduces the dense kernel (bk = page_tokens) BIT FOR BIT."""
    q, kp, vp, kn, vn, paged = _paged_case(
        [n_prefix, n_prefix], n_prefix, pt, n_incr, n_items, jnp.float32)
    k = jnp.concatenate([kp, kn], axis=2)
    v = jnp.concatenate([vp, vn], axis=2)
    want = prefix_rank_attn(q, k, v, n_prefix=n_prefix, n_incr=n_incr,
                            bq=32, bk=pt, interpret=True)
    got = paged_prefix_rank_attn(q, *paged, kn, vn, n_incr=n_incr,
                                 bq=32, bk=pt, interpret=True)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("plens,bucket", [([100, 37, 128], 128),
                                          ([1, 200, 64], 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_rank_attn_mixed_lengths(plens, bucket, dtype):
    """Mixed per-row prefix lengths in ONE launch — the occupancy win
    paging buys — match the dense kernel on zero-padded psi to fp32
    tolerance (and still bitwise for f32: silu(0) pad keys contribute
    exactly nothing on both sides)."""
    pt, n_incr, n_items = 64, 32, 32
    Sq = n_incr + n_items
    q, kp, vp, kn, vn, paged = _paged_case(
        plens, bucket, pt, n_incr, n_items, dtype)
    k = jnp.concatenate([kp, kn], axis=2)
    v = jnp.concatenate([vp, vn], axis=2)
    want = prefix_rank_attn(q, k, v, n_prefix=bucket, n_incr=n_incr,
                            bq=32, bk=pt, n_total=bucket + Sq,
                            interpret=True)
    got = paged_prefix_rank_attn(q, *paged, kn, vn, n_incr=n_incr,
                                 bq=32, bk=pt, n_total=bucket + Sq,
                                 interpret=True)
    if dtype == jnp.float32:
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_paged_rank_attn_matches_oracle():
    """Independent of the dense kernel: gather pages back to dense and
    check against the pure-numpy reference oracle."""
    pt, n_incr, n_items = 64, 16, 48
    plens, bucket = [90, 128], 128
    q, kp, vp, kn, vn, paged = _paged_case(
        plens, bucket, pt, n_incr, n_items, jnp.float32)
    Sq = n_incr + n_items
    k = jnp.concatenate([kp, kn], axis=2)
    v = jnp.concatenate([vp, vn], axis=2)
    want = ref.prefix_rank_attn_ref(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), n_prefix=bucket, n_incr=n_incr)
    got = paged_prefix_rank_attn(q, *paged, kn, vn, n_incr=n_incr,
                                 bq=32, bk=pt, n_total=bucket + Sq,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **TOL[jnp.float32])


def _segment_case(patterns, n_items, pt, dtype, seed=11, n_pages=None):
    """Build matched interleaved inputs from per-row chunk patterns.

    ``patterns[b]`` is an ordered list of ('c', ln) cached-span /
    ('f', ln) fresh-token chunks; every row must carry the same total
    fresh count Sq and end with at least ``n_items`` fresh tokens (the
    candidate items occupy the sequence tail).  Returns the fresh-token
    q/k/v, the span-aware pool pack, the FULL dense interleaved
    sequence (positions 0..S_b-1 per row, padded rows masked by a
    sentinel position) and the position arrays — everything both the
    kernel and the dense interleaved oracle need."""
    rng = np.random.default_rng(seed)
    B, H, D = len(patterns), 2, 64
    SENTINEL = 1 << 20
    Sq = sum(ln for kind, ln in patterns[0] if kind == "f")
    spans, fpos, totals = [], [], []
    for row in patterns:
        assert sum(ln for kind, ln in row if kind == "f") == Sq
        assert row[-1][0] == "f" and row[-1][1] >= n_items
        pos, sp, fp = 0, [], []
        for kind, ln in row:
            if kind == "c":
                sp.append((pos, ln))
            else:
                fp.extend(range(pos, pos + ln))
            pos += ln
        spans.append(sp)
        fpos.append(fp)
        totals.append(pos)
    S_max = max(totals)
    k_full = rng.normal(size=(B, H, S_max, D)).astype(np.float32)
    v_full = rng.normal(size=(B, H, S_max, D)).astype(np.float32)
    k_pos = np.full((B, S_max), SENTINEL, np.int32)
    for b, S_b in enumerate(totals):
        k_pos[b, :S_b] = np.arange(S_b)
    q = rng.normal(size=(B, H, Sq, D)).astype(np.float32)
    q_pos = np.asarray(fpos, np.int32)
    idx = q_pos[:, None, :, None]
    kn = np.take_along_axis(k_full, np.broadcast_to(
        idx, (B, H, Sq, D)), axis=2)
    vn = np.take_along_axis(v_full, np.broadcast_to(
        idx, (B, H, Sq, D)), axis=2)
    C_max = max(sum(ln for _, ln in sp) for sp in spans)
    kc = np.zeros((B, H, C_max, D), np.float32)
    vc = np.zeros_like(kc)
    for b, sp in enumerate(spans):
        off = 0
        for start, ln in sp:
            kc[b, :, off:off + ln] = k_full[b, :, start:start + ln]
            vc[b, :, off:off + ln] = v_full[b, :, start:start + ln]
            off += ln
    paged = pack_segments(kc, vc, spans, pt, n_pages=n_pages)
    to = lambda x: jnp.asarray(x, dtype)
    return (to(q), to(kn), to(vn),
            tuple(jnp.asarray(p) for p in paged), jnp.asarray(q_pos),
            to(k_full), to(v_full), jnp.asarray(k_pos))


@pytest.mark.parametrize("plens,bucket", [([128, 128], 128),
                                          ([100, 37, 128], 128)])
def test_segment_rank_attn_prefix_only_bitwise(plens, bucket):
    """Degenerate interleaving (one span at [0, prefix_len), fresh
    tokens after it): the segment kernel's masks reduce to the prefix
    kernel's, so it reproduces ``paged_prefix_rank_attn`` — and through
    it the dense reference chain — BIT FOR BIT.  This is the
    segments-disabled parity discipline at the kernel level."""
    pt, n_incr, n_items = 64, 32, 32
    Sq = n_incr + n_items
    q, kp, vp, kn, vn, paged = _paged_case(
        plens, bucket, pt, n_incr, n_items, jnp.float32)
    want = paged_prefix_rank_attn(q, *paged, kn, vn, n_incr=n_incr,
                                  bq=32, bk=pt, n_total=bucket + Sq,
                                  interpret=True)
    # same prefixes as single spans in the segment layout
    spans = [[(0, int(p))] for p in plens]
    kc = np.zeros((len(plens), 2, bucket, 64), np.float32)
    vc = np.zeros_like(kc)
    for b, p in enumerate(plens):
        kc[b, :, :p] = np.asarray(kp, np.float32)[b, :, :p]
        vc[b, :, :p] = np.asarray(vp, np.float32)[b, :, :p]
    seg = tuple(jnp.asarray(x) for x in
                pack_segments(kc, vc, spans, pt, n_pages=bucket // pt))
    q_pos = jnp.asarray(np.asarray(plens, np.int32)[:, None]
                        + np.arange(Sq, dtype=np.int32)[None])
    got = segment_rank_attn(q, *seg, q_pos, kn, vn, n_items=n_items,
                            bq=32, bk=pt, n_total=bucket + Sq,
                            interpret=True)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_rank_attn_matches_interleaved_oracle(dtype):
    """Beyond-prefix reuse: cached interior segments interleaved with
    fresh tokens (different layouts per row, one launch) match the
    dense reference built from the same interleaving — fresh tokens
    between two cached segments must NOT see the later segment."""
    pt, n_items = 64, 32
    patterns = [
        [("c", 64), ("f", 32), ("c", 64), ("f", 32)],
        [("c", 30), ("f", 10), ("c", 50), ("f", 22), ("c", 17),
         ("f", 32)],
    ]
    q, kn, vn, seg, q_pos, k_full, v_full, k_pos = _segment_case(
        patterns, n_items, pt, dtype)
    Sq = q.shape[2]
    n_pages = seg[2].shape[1]
    nt = n_pages * pt + Sq
    got = segment_rank_attn(q, *seg, q_pos, kn, vn, n_items=n_items,
                            bq=32, bk=pt, n_total=nt, interpret=True)
    want = ref.segment_rank_attn_ref(
        q.astype(jnp.float32), k_full.astype(jnp.float32),
        v_full.astype(jnp.float32), q_pos=q_pos, k_pos=k_pos,
        n_items=n_items, n_total=nt)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_segment_ref_degenerates_to_prefix_ref():
    """The interleaved oracle itself: one span at [0, P) + fresh tokens
    after it equals the prefix oracle exactly (same mask bits)."""
    P, n_incr, n_items = 96, 16, 48
    B, H, D = 2, 2, 64
    Sq = n_incr + n_items
    rng = np.random.default_rng(23)
    mk = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    q, k, v = mk(B, H, Sq, D), mk(B, H, P + Sq, D), mk(B, H, P + Sq, D)
    want = ref.prefix_rank_attn_ref(q, k, v, n_prefix=P, n_incr=n_incr)
    q_pos = np.broadcast_to(P + np.arange(Sq, dtype=np.int32), (B, Sq))
    k_pos = np.broadcast_to(np.arange(P + Sq, dtype=np.int32),
                            (B, P + Sq))
    got = ref.segment_rank_attn_ref(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                    n_items=n_items)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_rank_mask_matches_model():
    """Kernel mask semantics == model-level rank_mask (candidate
    independence is the correctness-critical property)."""
    from repro.models.hstu import rank_mask
    m_model = np.asarray(rank_mask(8, 4, 6)[0, 0])
    m_ref = np.asarray(ref.rank_mask_ref(8, 4, 6))
    np.testing.assert_array_equal(m_model, m_ref)
    # items never attend to other items
    qi = np.arange(10)[:, None]
    ki = np.arange(18)[None, :]
    item_q, item_k = qi >= 4, ki >= 12
    cross_item = m_ref & item_q & item_k & (ki != qi + 8)
    assert not cross_item.any()


@pytest.mark.parametrize("S,KV,H", [(1024, 2, 8), (2048, 4, 4),
                                    (4096, 1, 8), (512, 8, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attn_sweep(S, KV, H, dtype):
    B, D = 2, 64
    q = _mk((B, H, D), dtype)
    k = _mk((B, KV, S, D), dtype)
    v = _mk((B, KV, S, D), dtype)
    out = decode_attn(q, k, v, bk=256, interpret=True)
    want = ref.decode_attn_ref(q.astype(jnp.float32),
                               k.astype(jnp.float32),
                               v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_ops_wrappers_model_layout():
    B, S, H, D = 2, 256, 2, 64
    q, k, v = (_mk((B, S, H, D), jnp.float32) for _ in range(3))
    out = ops.hstu_attention(q, k, v)
    want = jnp.swapaxes(ref.hstu_attn_ref(*(jnp.swapaxes(t, 1, 2)
                                            for t in (q, k, v))), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-4, rtol=3e-4)
    # a shape the tiling cannot serve raises: no silent oracle fallback
    qo, ko, vo = (_mk((B, 300, H, D), jnp.float32) for _ in range(3))
    with pytest.raises(ValueError, match="no kernel tiling"):
        ops.hstu_attention(qo, ko, vo)
    with pytest.raises(ValueError, match="no kernel tiling"):
        ops.rank_attention(qo[:, :64], ko, vo, n_prefix=236, n_incr=32)
    kd = _mk((B, 600, 1, D), jnp.float32)      # 600 % 512 != 0
    with pytest.raises(ValueError, match="no kernel tiling"):
        ops.cache_decode_attention(qo[:, :1], kd, kd)


@pytest.mark.parametrize("H,P,N", [(4, 64, 64), (2, 128, 32), (8, 64, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk_kernel_sweep(H, P, N, dtype):
    from repro.kernels.ssd_chunk import ssd_chunk_intra, ssd_chunk_intra_ref
    B, nc, Q = 2, 2, 128
    Cc = _mk((B, nc, Q, N), dtype)
    Bc = _mk((B, nc, Q, N), dtype)
    xc = _mk((B, nc, Q, H, P), dtype)
    cum = jnp.asarray(-np.abs(RNG.normal(size=(B, nc, Q, H))).cumsum(2),
                      jnp.float32)
    dtc = jnp.asarray(np.abs(RNG.normal(size=(B, nc, Q, H))), jnp.float32)
    out = ssd_chunk_intra(Cc, Bc, xc, cum, dtc, interpret=True)
    ref = ssd_chunk_intra_ref(Cc.astype(jnp.float32),
                              Bc.astype(jnp.float32),
                              xc.astype(jnp.float32), cum, dtc)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("H,P,N", [(4, 64, 64), (2, 128, 32)])
def test_ssd_chunk_state_kernel(H, P, N):
    from repro.kernels.ssd_chunk import ssd_chunk_state, ssd_chunk_state_ref
    B, nc, Q = 2, 2, 128
    Bc = _mk((B, nc, Q, N), jnp.float32)
    xc = _mk((B, nc, Q, H, P), jnp.float32)
    cum = jnp.asarray(-np.abs(RNG.normal(size=(B, nc, Q, H))).cumsum(2),
                      jnp.float32)
    dtc = jnp.asarray(np.abs(RNG.normal(size=(B, nc, Q, H))), jnp.float32)
    out = ssd_chunk_state(Bc, xc, cum, dtc, interpret=True)
    ref = ssd_chunk_state_ref(Bc, xc, cum, dtc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-4, rtol=3e-4)
