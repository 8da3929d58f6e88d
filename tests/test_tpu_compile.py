"""Ahead-of-time compiles for a described TPU v5e, no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes off
the (8, 128) tiling, boolean selects it cannot truncate, programs that
do not fit the device.  These cases compile each kernel of the HSTU
path at real widths (H=4, D=64, fp32, a 2048-token prefix, 512 query
tokens, 64-token pages, B up to 8) and the full-width
``rank_with_cache`` program for one chip of a ``v5e:2x2`` topology.

The topology is described inside the module fixture — never at import —
because only one process at a time may load the TPU library; under
pytest-xdist the worker given this file loads it and the rest never
try.  The persistent compilation cache stays off around the compiles:
an entry written for a described chip cannot be read back here.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attn import decode_attn
from repro.kernels.hstu_attn import hstu_attn
from repro.kernels.paged_prefix_attn import (paged_prefix_rank_attn,
                                             segment_rank_attn)
from repro.kernels.prefix_rank_attn import prefix_rank_attn
from repro.models import build_model, get_config

H, D, P, SQ, N_INCR, PT, B = 4, 64, 2048, 512, 64, 64, 8
N_PAGES = P // PT
POOL = B * N_PAGES + 1                 # every row's pages + the null page
HBM_BYTES = 16 * 2 ** 30               # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                         # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# name -> (kernel call, argument shapes); dtype int32 marks index inputs
KERNELS = {
    "hstu_attn": (
        functools.partial(hstu_attn, bq=256, bk=256),
        [(B, H, P, D)] * 3),
    "prefix_rank_attn": (
        functools.partial(prefix_rank_attn, n_prefix=P, n_incr=N_INCR,
                          bq=128, bk=256),
        [(B, H, SQ, D), (B, H, P + SQ, D), (B, H, P + SQ, D)]),
    "paged_prefix_rank_attn": (
        functools.partial(paged_prefix_rank_attn, n_incr=N_INCR, bq=128),
        [(B, H, SQ, D), (POOL, PT, H * D), (POOL, PT, H * D),
         ((B, N_PAGES), jnp.int32), ((B,), jnp.int32),
         (B, H, SQ, D), (B, H, SQ, D)]),
    "segment_rank_attn": (
        functools.partial(segment_rank_attn, n_items=SQ - N_INCR, bq=128),
        [(B, H, SQ, D), (POOL, PT, H * D), (POOL, PT, H * D),
         ((B, N_PAGES), jnp.int32), ((B, N_PAGES), jnp.int32),
         ((B, N_PAGES), jnp.int32), ((B, SQ), jnp.int32),
         (B, H, SQ, D), (B, H, SQ, D)]),
    "decode_attn": (
        functools.partial(decode_attn, bk=512),
        [(B, H, D), (B, H, P, D), (B, H, P, D)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [_spec(one_chip, *s) if isinstance(s[0], tuple)
            else _spec(one_chip, s) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the Mosaic kernel itself is in the program, not an XLA stand-in
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_rank_with_cache_compiles_for_v5e(one_chip):
    """The served rank-on-cache program at hstu_gr's published widths:
    B rows of a 2048-token cached prefix, 512 incr + item tokens."""
    cfg = get_config("hstu_gr")
    model = build_model(cfg)
    params = jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    kv = _spec(one_chip, (cfg.n_layers, B, P, cfg.n_heads, cfg.head_dim))
    incr = _spec(one_chip, (B, N_INCR), jnp.int32)
    items = _spec(one_chip, (B, SQ - N_INCR), jnp.int32)
    compiled = jax.jit(model.rank_with_cache).lower(
        params, (kv, kv), incr, items).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES
