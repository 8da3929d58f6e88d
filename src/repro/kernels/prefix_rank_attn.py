"""Pallas TPU kernel: ranking-with-cache HSTU attention.

This is the RelayGR consumption path: queries are the incremental tokens
(short-term behaviours + cross features) followed by the candidate
items; keys/values are the cached user prefix psi concatenated with the
new tokens.  The mask encodes the ranking semantics:

  * incremental tokens attend causally over prefix + earlier incr;
  * candidate items attend to prefix + incr + themselves ONLY
    (candidate independence — items never see each other).

Grid/BlockSpec structure matches hstu_attn (kv axis innermost, f32 VMEM
accumulator, MXU-aligned tiles); the mask is computed from global
indices in-kernel, so no (Sq, Sk) mask tensor ever exists in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, *, scale, inv_n, bq, bk,
            n_prefix, n_incr, n_kv_blocks):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # prune: keys strictly after the latest query this block can see
    @pl.when(ik * bk <= iq * bq + (bq - 1) + n_prefix)
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
        causal = ki <= qi + n_prefix
        is_item_q = qi >= n_incr
        is_item_k = ki >= n_prefix + n_incr
        self_key = ki == qi + n_prefix
        # pure logical ops: a boolean select here lowers to an i8 -> i1
        # truncation that the TPU compiler refuses
        items_ok = ~is_item_q | ~is_item_k | self_key
        a = jnp.where(causal & items_ok, a, 0.0)
        acc_ref[...] += jax.lax.dot_general(
            a, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_kv_blocks - 1)
    def _done():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_prefix", "n_incr", "bq", "bk", "n_total", "interpret"))
def prefix_rank_attn(q, k, v, *, n_prefix: int, n_incr: int,
                     bq: int = 128, bk: int = 256, n_total: float = None,
                     interpret: bool = False):
    """q: (B, H, Sq, D); k, v: (B, H, Sk, D), Sk = n_prefix + Sq."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    assert Sk == n_prefix + Sq, (Sk, n_prefix, Sq)
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / np.sqrt(D)
    inv_n = 1.0 / (n_total or Sk)

    kernel = functools.partial(
        _kernel, scale=scale, inv_n=inv_n, bq=bq, bk=bk,
        n_prefix=n_prefix, n_incr=n_incr, n_kv_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v)
