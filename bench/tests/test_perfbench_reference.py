"""The plain reference of ``bench/models/hstu_gr.py`` against the
program's HSTU at the smoke size, and the lower-precision control
against each configuration's limit."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.lib import check, harness  # noqa: E402
from bench.models import hstu_gr  # noqa: E402


def _request(rng, vocab, plen, n_incr, n_items):
    return (rng.integers(0, vocab, plen), rng.integers(0, vocab, n_incr),
            rng.integers(0, vocab, n_items))


@pytest.mark.parametrize("grid,bucket", [(192, 256), (256, 256)])
def test_reference_matches_the_model_at_highest_precision(grid, bucket):
    from repro.models import build_model, get_config
    cfg = get_config("hstu-gr", smoke=True)
    dims = hstu_gr.dims(cfg)
    model = build_model(cfg)
    w = hstu_gr.make_weights(dims, harness.seed_key(2**40 + 3))
    hist, incr, items = _request(np.random.default_rng(grid), cfg.vocab,
                                 grid, 16, 32)
    incr_, items_ = jnp.asarray(incr)[None], jnp.asarray(items)[None]
    with jax.default_matmul_precision("highest"):
        _, psi = model.prefill(w, {"tokens": jnp.asarray(hist)[None]})
        pad = ((0, 0), (0, 0), (0, bucket - grid), (0, 0), (0, 0))
        hit = model.rank_with_cache(
            w, tuple(jnp.pad(a, pad) for a in psi), incr_, items_)[0]
        tiled = np.resize(hist, bucket)
        miss = model.full_rank(w, jnp.asarray(tiled)[None], incr_,
                               items_)[0]
    toks = np.zeros(bucket, np.int32)
    toks[:grid] = hist
    assert check.gap(hit, hstu_gr.rank_scores(
        dims, w, toks, grid, incr, items)) < 1e-5
    assert check.gap(miss, hstu_gr.rank_scores(
        dims, w, tiled, bucket, incr, items)) < 1e-5


@pytest.mark.parametrize("config", ["hstu_gr-L8k", "hstu_gr-L2k"])
def test_lower_precision_control_fails_the_limit(config):
    """The control (the reference at three bfloat16 passes, the next
    precision below the float32 the configuration states) at the
    configuration's published widths, over a short history a CPU test
    can hold, against the float32 reference."""
    cfg = json.loads((ROOT / "bench" / "configs"
                      / f"{config}.json").read_text())
    dims = {k: v for k, v in cfg["model"].items() if k != "arch"}
    limit = cfg["check"]["score_gap_limit"]
    w = hstu_gr.make_weights(dims, harness.seed_key(11))
    gaps = []
    for plen in (200, 256):
        hist, incr, items = _request(np.random.default_rng(plen),
                                     dims["vocab"], plen, 64, 512)
        toks = np.zeros(256, np.int32)
        grid = -(-plen // 64) * 64
        toks[:grid] = np.resize(hist, grid)
        want = hstu_gr.rank_scores(dims, w, toks, grid, incr, items, "f32")
        got = hstu_gr.rank_scores(dims, w, toks, grid, incr, items, "high")
        gaps.append(check.gap(got, want))
    assert max(gaps) > limit
