"""Operations and bytes that an HSTU launch has to do, from its shapes.

The count follows the HSTU equations (``U, V, Q, K = split(SiLU(f1(x)))``,
``A = SiLU(Q K^T / sqrt(d)) / n``, ``y = x + f2(norm(A V) * U)``) and
counts matrix-multiply operations only, over real rows and real tokens:
no batch-padding row, no bucket or grid padding, and, where attention
is causal or masked, only the query-key pairs that the mask keeps.  So
a launch reads the same work whatever implements it, and a kernel that
skips masked blocks raises its roofline share honestly.

Bytes are what the algorithm must move at the least: the weights it
uses once per launch, the embedding rows of its tokens, psi where it is
written (prefill) or read (a cached rank), the token ids in and the
scores out.  Intermediate activations and attention logits are not
counted.
"""

from __future__ import annotations

from typing import Sequence, Tuple


def _dims(cfg: dict):
    return (cfg["n_layers"], cfg["d_model"], cfg["n_heads"],
            cfg["head_dim"], cfg.get("n_tasks", 1),
            4 if cfg.get("dtype", "float32") == "float32" else 2)


def layer_param_bytes(cfg: dict) -> int:
    L, d, H, hd, _, b = _dims(cfg)
    return L * (d * 4 * H * hd + H * hd * d + d + H * hd) * b


def tower_param_bytes(cfg: dict) -> int:
    _, d, _, _, T, b = _dims(cfg)
    return (d * 4 * d + 4 * d * T) * b


def _proj_per_token(cfg: dict) -> float:
    L, d, H, hd, _, _ = _dims(cfg)
    return L * (2.0 * d * 4 * H * hd + 2.0 * H * hd * d)


def _attn_per_pair(cfg: dict) -> float:
    L, _, H, hd, _, _ = _dims(cfg)
    return L * 4.0 * H * hd          # Q.K and A.V, every head and layer


def prefill_row_flops(cfg: dict, n: int) -> float:
    """Causal prefill of ``n`` real tokens."""
    return _proj_per_token(cfg) * n + _attn_per_pair(cfg) * n * (n + 1) / 2


def rank_row_flops(cfg: dict, n_prefix: int, n_incr: int,
                   n_items: int) -> float:
    """Rank of ``n_items`` candidates after ``n_incr`` incremental
    tokens over ``n_prefix`` cached tokens: incremental tokens attend
    causally, each candidate sees the prefix, the incremental tokens
    and itself."""
    _, d, _, _, T, _ = _dims(cfg)
    pairs = (n_incr * n_prefix + n_incr * (n_incr + 1) / 2
             + n_items * (n_prefix + n_incr + 1))
    tower = n_items * (2.0 * d * 4 * d + 2.0 * 4 * d * T)
    return (_proj_per_token(cfg) * (n_incr + n_items)
            + _attn_per_pair(cfg) * pairs + tower)


def _psi_bytes(cfg: dict, n: int) -> int:
    L, _, H, hd, _, b = _dims(cfg)
    return L * 2 * n * H * hd * b


def _embed_bytes(cfg: dict, n: int) -> int:
    _, d, _, _, _, b = _dims(cfg)
    return n * (d * b + 4)           # embedding row + int32 token id


def prefill_work(cfg: dict, lens: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill launch over real rows ``lens``."""
    flops = sum(prefill_row_flops(cfg, n) for n in lens)
    nbytes = layer_param_bytes(cfg) + sum(
        _embed_bytes(cfg, n) + _psi_bytes(cfg, n) for n in lens)
    return flops, float(nbytes)


def rank_work(cfg: dict, prefix_lens: Sequence[int], n_incr: int,
              n_items: int, cached: bool) -> Tuple[float, float]:
    """(FLOPs, bytes) of one rank launch over real rows: from cached
    psi, or (``cached=False``) a full rank that first infers the
    prefix itself."""
    _, _, _, _, T, b = _dims(cfg)
    flops = 0.0
    nbytes = layer_param_bytes(cfg) + tower_param_bytes(cfg)
    for p in prefix_lens:
        flops += rank_row_flops(cfg, p, n_incr, n_items)
        nbytes += _embed_bytes(cfg, n_incr + n_items) + n_items * T * b
        if cached:
            nbytes += _psi_bytes(cfg, p)
        else:
            flops += prefill_row_flops(cfg, p)
            nbytes += _embed_bytes(cfg, p)
    return flops, float(nbytes)


def lower_bound_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute
    and the memory bound."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
