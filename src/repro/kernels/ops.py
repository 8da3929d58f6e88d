"""jit'd public wrappers around the Pallas kernels.

These adapt the model layout (B, S, H, D) to the kernel-native layout
(B, H, S, D) and choose the execution mode: the compiled kernel on an
accelerator, ``interpret=True`` on the CPU backend (the kernel body
then runs as a Python/XLA interpretation — the correctness path used by
CI) or wherever the caller passes ``interpret`` explicitly.

A shape the tiling cannot serve raises ``ValueError``.  There is no
silent fallback to the pure-jnp oracle: a run on the chip that looks
fine while no kernel ran would measure the wrong program.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .decode_attn import decode_attn as _decode_attn
from .hstu_attn import hstu_attn as _hstu_attn
from .prefix_rank_attn import prefix_rank_attn as _prefix_rank_attn


def _interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def _check_tiles(what: str, **dims) -> None:
    """``dims`` maps a name to (length, block); each length must be a
    whole number of blocks."""
    bad = {k: v for k, v in dims.items() if v[0] % v[1]}
    if bad:
        raise ValueError(
            f"{what}: no kernel tiling serves "
            + ", ".join(f"{k}={n} (block {b})" for k, (n, b) in bad.items())
            + "; pad the sequence to a multiple of the block")


def _bsh_to_bhs(x):
    return jnp.swapaxes(x, 1, 2)


def hstu_attention(q, k, v, *, n_total=None, block_q=256, block_k=256,
                   interpret: Optional[bool] = None):
    """q,k,v: (B, S, H, D) model layout. Causal HSTU attention."""
    S = q.shape[1]
    bq, bk = min(block_q, S), min(block_k, S)
    _check_tiles("hstu_attention", S_q=(S, bq), S_k=(S, bk))
    qt, kt, vt = map(_bsh_to_bhs, (q, k, v))
    out = _hstu_attn(qt, kt, vt, bq=bq, bk=bk, n_total=n_total,
                     interpret=_interpret(interpret))
    return _bsh_to_bhs(out)


def rank_attention(q, k, v, *, n_prefix, n_incr, n_total=None,
                   block_q=128, block_k=256,
                   interpret: Optional[bool] = None):
    """Ranking-with-cache attention, model layout (B, S, H, D)."""
    Sq, Sk = q.shape[1], k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    _check_tiles("rank_attention", S_q=(Sq, bq), S_k=(Sk, bk))
    qt, kt, vt = map(_bsh_to_bhs, (q, k, v))
    out = _prefix_rank_attn(qt, kt, vt, n_prefix=n_prefix, n_incr=n_incr,
                            bq=bq, bk=bk, n_total=n_total,
                            interpret=_interpret(interpret))
    return _bsh_to_bhs(out)


def cache_decode_attention(q, k, v, *, block_k=512,
                           interpret: Optional[bool] = None):
    """Flash-decode: q (B, 1, H, D); cache k, v (B, S, KV, D)."""
    S = k.shape[1]
    bk = min(block_k, S)
    _check_tiles("cache_decode_attention", S_k=(S, bk))
    kt = jnp.swapaxes(k, 1, 2)  # (B, KV, S, D)
    vt = jnp.swapaxes(v, 1, 2)
    out = _decode_attn(q[:, 0], kt, vt, bk=bk,
                       interpret=_interpret(interpret))
    return out[:, None]  # (B, 1, H, D)
