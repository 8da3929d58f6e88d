"""What decides ``correct``: the served scores of a sample of the
window's completed requests against the plain reference.

The sample is drawn from the seed, and always holds the longest
history served from cached psi and the longest served by a full rank.
For each request the model's reference (``bench/models/<arch>.py``) is
fed the sequence that path served (its ``served_prefix``), and the gap
is

    max |served - reference| / max |reference|

over the request's candidate scores.  The number compared is the
widest gap of the sample, against the reference at the precision the
configuration states (the module's first mode).  A control puts the
reference at one of the module's lower precisions in the program's
place.
"""

from __future__ import annotations

from types import ModuleType
from typing import Dict, List, Sequence, Set

import numpy as np


def gap(served, want) -> float:
    s = np.asarray(served, np.float64)
    w = np.asarray(want, np.float64)
    return float(np.abs(s - w).max() / max(np.abs(w).max(), 1e-30))


def pick(completed: Sequence, n: int, seed: int,
         tails: Set[int] = frozenset(), n_tails: int = 4) -> List:
    """``n`` requests drawn from the seed: the longest cached and the
    longest full-rank request, up to ``n_tails`` of the users ranked as
    a later row of a multi-row launch (``tails``), and the rest drawn
    from all completed requests."""
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 0xC0FFEE])
    out = []
    for cached in (True, False):
        cls = [r for r in completed if _cached(r) == cached]
        if cls:
            out.append(max(cls, key=lambda r: r.prefix_len))

    def draw(pool, k):
        pool = [r for r in pool if all(r is not o for o in out)]
        k = max(min(k, len(pool)), 0)
        for i in (rng.choice(len(pool), size=k, replace=False) if k
                  else []):
            out.append(pool[int(i)])

    draw([r for r in completed if r.uid in tails], n_tails)
    draw(completed, n - len(out))
    return out


def _cached(req) -> bool:
    return req.result.hit.value != "miss"


def collect(reqs: Sequence, store, arch: ModuleType) -> List[Dict]:
    """Everything the comparison needs from the program, on the host,
    so that the program's state can be freed before it runs."""
    out = []
    for r in reqs:
        cached = _cached(r)
        tokens, n_fed = arch.served_prefix(store, r.uid, cached)
        out.append({"uid": r.uid, "cached": cached,
                    "prefix_len": r.prefix_len, "tokens": tokens,
                    "n_fed": n_fed, "incr": store.short_term(r.uid),
                    "items": store.candidates(r.uid),
                    "served": np.asarray(r.result.scores)})
    return out


def compare(arch: ModuleType, dims: dict, weights, sample: Sequence[Dict],
            served_mode: str = "") -> Dict[str, float]:
    """Widest gap of the sample, by hit class, against ``arch``'s
    reference at its first mode.  ``served_mode`` puts the reference at
    that precision in the program's place (a control)."""
    gaps = {"cached": [], "full": []}
    for s in sample:
        want = arch.rank_scores(dims, weights, s["tokens"], s["n_fed"],
                                s["incr"], s["items"], arch.MODES[0])
        got = s["served"] if not served_mode else arch.rank_scores(
            dims, weights, s["tokens"], s["n_fed"], s["incr"], s["items"],
            served_mode)
        gaps["cached" if s["cached"] else "full"].append(
            gap(np.asarray(got).reshape(-1), np.asarray(want).reshape(-1)))
    return {k: max(v) for k, v in gaps.items() if v} | {
        "n_cached": len(gaps["cached"]), "n_full": len(gaps["full"])}
