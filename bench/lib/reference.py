"""Plain HSTU reference and the benchmark's own weights.

Straight ``jax.numpy``, independent of the program's model code and
kernels: the HSTU block of Zhai et al. (arXiv:2402.17152, Eq. 1-3)

    U, V, Q, K = split(SiLU(norm(x) W1))        rotary on Q and K
    A          = SiLU(Q K^T / sqrt(d_head)) / n   masked, no softmax
    y          = x + (norm(A V) * U) W2

and a task tower ``SiLU(h T1) T2`` over each candidate's position.  A
rank scores ``n_items`` candidates after ``n_incr`` incremental tokens
over a cached prefix: incremental tokens attend causally, each
candidate sees the prefix, the incremental tokens and itself.

It takes the sequence a served path actually computed.  The prefix is
``tokens[:n_fed]``, inferred causally with the normaliser ``n = n_fed``;
the rank then sees ``n_slots`` prefix positions (``n_fed`` computed
ones, the rest zero keys, which contribute nothing), its own tokens at
positions ``n_slots + i`` and the normaliser ``n_slots + n_incr +
n_items``.  Attention runs in blocks of queries, so an 8K prefix fits.
Three precisions (``mode``):

* ``"f32"``: float32 throughout, matmuls at the highest precision (the
  configurations state float32);
* ``"high"``: the control, float32 storage with each matmul computed
  as three bfloat16 passes (``hi*hi + hi*lo + lo*hi``, summed in
  float32), which is what ``Precision.HIGH`` does on a TPU, written out
  so that it computes the same on any platform;
* ``"bf16"``: the same equations with weights and activations in
  bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def weight_shapes(cfg: dict) -> dict:
    """The weight tree the served HSTU takes, by name and shape."""
    L, d, H, hd = (cfg["n_layers"], cfg["d_model"], cfg["n_heads"],
                   cfg["head_dim"])
    vp, T = _round_up(cfg["vocab"], 256), cfg.get("n_tasks", 1)
    return {"tok": (vp, d), "final_norm": (d,), "unembed": (d, vp),
            "layers": {"ln": (L, d), "uvqk": (L, d, 4, H, hd),
                       "ln_attn": (L, H * hd), "wo": (L, H, hd, d)},
            "task_tower": {"w1": (d, 4 * d), "w2": (4 * d, T)}}


# fan-in of each matrix: the size of what its input contracts over
_FAN_IN = {"unembed": lambda s: s[0], "uvqk": lambda s: s[1],
           "wo": lambda s: s[1] * s[2], "w1": lambda s: s[0],
           "w2": lambda s: s[0]}


def make_weights(cfg: dict, key) -> dict:
    """Random weights from ``key``, in one jitted call on the default
    device, in the served dtype: fan-in scaled normals for matrices,
    unit-scale embeddings, and norm gains ``1 + 0.1 N(0, 1)`` so the
    comparison exercises them."""
    shapes = weight_shapes(cfg)
    dt = jnp.dtype(cfg.get("dtype", "float32"))
    flat, tree = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    names = [path[-1].key for path, _ in flat]

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, name, (_, shape) in zip(keys, names, flat):
            x = jax.random.normal(k, shape, jnp.float32)
            if name in ("ln", "ln_attn", "final_norm"):
                x = 1.0 + 0.1 * x
            elif name in _FAN_IN:
                x = x / np.sqrt(_FAN_IN[name](shape))
            out.append(x.astype(dt))
        return jax.tree.unflatten(tree, out)

    return jax.jit(make)(key)


def _rms_norm(x, w, dt):
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    return (x * w.astype(jnp.float32)).astype(dt)


def _rope(x, positions, theta):
    """Rotary embedding on interleaved pairs; x (S, H, D)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., ::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


MODES = ("f32", "high", "bf16")


def _split(x):
    """float32 -> (hi, lo), each a float32 holding a bfloat16 value, with
    hi + lo ~ x to 16 bits.  ``reduce_precision`` rather than a round
    trip through bfloat16, which XLA may fold away as excess precision."""
    x = x.astype(jnp.float32)
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


class _Ops:
    def __init__(self, cfg, mode):
        self.mode = mode
        self.dt = jnp.dtype(jnp.bfloat16 if mode == "bf16" else jnp.float32)
        self.H, self.hd = cfg["n_heads"], cfg["head_dim"]
        self.theta = cfg.get("rope_theta") or 0.0

    def ein(self, spec, a, b):
        if self.mode == "f32":
            return jnp.einsum(spec, a, b, precision=HIGHEST)
        if self.mode == "high":
            a_hi, a_lo = _split(a)
            b_hi, b_lo = _split(b)
            return sum(jnp.einsum(spec, x, y, precision=HIGHEST)
                       for x, y in ((a_hi, b_hi), (a_hi, b_lo),
                                    (a_lo, b_hi)))
        return jnp.einsum(spec, a, b)

    def qkvu(self, lp, x, positions):
        """x (S, d) -> u, v, q, k, each (S, H, hd)."""
        xn = _rms_norm(x, lp["ln"], self.dt)
        uvqk = jax.nn.silu(self.ein("sd,dfhk->sfhk", xn,
                                    lp["uvqk"].astype(self.dt)))
        u, v, q, k = (uvqk[:, i] for i in range(4))
        if self.theta:
            q, k = _rope(q, positions, self.theta), _rope(k, positions,
                                                          self.theta)
        return u, v, q, k

    def attend(self, q, k, v, keep, n_total):
        """q (Sq, H, hd), k/v (Sk, H, hd), keep (Sq, Sk) bool."""
        logits = self.ein("qhd,khd->hqk", q, k).astype(jnp.float32)
        a = jax.nn.silu(logits / np.sqrt(self.hd)) / n_total
        a = jnp.where(keep[None], a, 0.0)
        return self.ein("hqk,khd->qhd", a.astype(v.dtype), v)

    def out(self, lp, x, av, u):
        S = av.shape[0]
        av = _rms_norm(av.reshape(S, self.H * self.hd), lp["ln_attn"],
                       self.dt).reshape(S, self.H, self.hd)
        y = self.ein("shk,hkd->sd", av * u, lp["wo"].astype(self.dt))
        return x + y


def _prefill(ops, w, tokens, n_fed):
    """Per-layer (K, V) of the causal prefill of ``tokens[:n_fed]``
    (positions past ``n_fed`` are computed but masked out)."""
    P = tokens.shape[0]
    qb = min(Q_BLOCK, P)
    pos = jnp.arange(P)
    x = w["tok"].astype(ops.dt)[tokens]
    n_total = n_fed.astype(jnp.float32)

    def layer(x, lp):
        u, v, q, k = ops.qkvu(lp, x, pos)

        def block(i):
            rows = jax.lax.dynamic_slice_in_dim(pos, i * qb, qb)
            qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
            keep = (pos[None, :] <= rows[:, None]) & (pos[None, :] < n_fed)
            return ops.attend(qi, k, v, keep, n_total)

        av = jax.lax.map(block, jnp.arange(P // qb))
        av = av.reshape(P, ops.H, ops.hd)
        return ops.out(lp, x, av, u), (k, v)

    _, kv = jax.lax.scan(layer, x, w["layers"])
    return kv


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _scores(w, tokens, n_fed, incr, items, cfg_items, mode):
    cfg = dict(cfg_items)
    ops = _Ops(cfg, mode)
    n_slots = tokens.shape[0]
    ks, vs = _prefill(ops, w, tokens, n_fed)       # (L, P, H, hd)
    n_incr, n_items = incr.shape[0], items.shape[0]
    Q = n_incr + n_items
    qpos = n_slots + jnp.arange(Q)
    x = w["tok"].astype(ops.dt)[jnp.concatenate([incr, items])]
    qi = jnp.arange(Q)[:, None]
    kp = jnp.arange(n_slots)[None, :]
    ko = jnp.arange(Q)[None, :]
    keep_prefix = jnp.broadcast_to(kp < n_fed, (Q, n_slots))
    is_item = qi >= n_incr
    keep_own = jnp.where(is_item, (ko < n_incr) | (ko == qi), ko <= qi)
    keep = jnp.concatenate([keep_prefix, keep_own], axis=1)
    n_total = float(n_slots + Q)

    def layer(x, per):
        lp, pk, pv = per
        u, v, q, k = ops.qkvu(lp, x, qpos)
        av = ops.attend(q, jnp.concatenate([pk, k]),
                        jnp.concatenate([pv, v]), keep, n_total)
        return ops.out(lp, x, av, u), None

    x, _ = jax.lax.scan(layer, x, (w["layers"], ks, vs))
    tw = w["task_tower"]
    h = jax.nn.silu(ops.ein("sd,df->sf", x[n_incr:],
                            tw["w1"].astype(ops.dt)))
    return ops.ein("sf,ft->st", h, tw["w2"].astype(ops.dt)).astype(
        jnp.float32)


def rank_scores(cfg: dict, w, tokens, n_fed: int, incr, items,
                mode: str = "f32"):
    """Scores ``(n_items, n_tasks)`` of one request: ``tokens`` is the
    served prefix, ``n_slots = len(tokens)`` long, of which the first
    ``n_fed`` were inferred; ``mode`` is one of ``MODES``."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    keys = ("n_layers", "d_model", "n_heads", "head_dim", "n_tasks",
            "rope_theta", "vocab")
    cfg_items = tuple((k, cfg[k]) for k in keys if k in cfg)
    return _scores(w, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(n_fed, jnp.int32),
                   jnp.asarray(incr, jnp.int32),
                   jnp.asarray(items, jnp.int32), cfg_items, mode)
