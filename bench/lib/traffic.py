"""Traffic generation, read from a mix's data file.

WHO arrives: users drawn from a bounded Zipf law over a population
(``skew`` 0 is uniform).  WHEN: a named arrival process at a mean rate.
Both are copies of the program's generators (``ZipfPopularity.sample``
and the Poisson / MMPP processes of ``repro.data.synthetic``), kept
here so that a change to the program cannot move the yardstick.

Every seed gets the same work: the mix's ``base_seed`` draws the
arrival times and users, and ``--seed`` draws only the weights and the
requests the correctness check samples.  So the spread between runs
measures the system, not the draw.  (At the low rates these cells
sustain, a seed that reordered the same users and gaps moved the
tails by 40 % between seeds, far more than between two runs of one
order.)
"""

from __future__ import annotations

from typing import Iterator, List, Tuple

import numpy as np


def zipf_users(rng: np.random.Generator, population: int, skew: float,
               n: int) -> np.ndarray:
    """``n`` user ids from a bounded continuous Zipf(``skew``) law over
    ``population`` ids; rank ``r`` is user ``r - 1`` (popular users are
    the low ids)."""
    u = rng.random(n)
    pop, s = int(population), float(skew)
    if pop == 1:
        ranks = np.ones(n)
    elif abs(s - 1.0) < 1e-9:
        ranks = np.exp(u * np.log(pop))
    else:
        ranks = (1.0 + u * (pop ** (1.0 - s) - 1.0)) ** (1.0 / (1.0 - s))
    ids = np.floor(ranks).astype(np.int64) - 1
    return np.clip(ids, 0, pop - 1)


def poisson_arrivals(rate: float, seconds: float,
                     rng: np.random.Generator) -> Iterator[float]:
    t = 0.0
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= seconds:
            return
        yield t


def mmpp_arrivals(rate: float, seconds: float, rng: np.random.Generator,
                  low: float = 0.3, high: float = 1.7,
                  dwell_s: float = 1.0) -> Iterator[float]:
    """Two-state Markov-modulated Poisson process: the rate alternates
    between ``low * rate`` and ``high * rate`` with exponential dwell
    times of mean ``dwell_s`` (mean rate ``(low + high) / 2 * rate``)."""
    if low < 0 or high < low:
        raise ValueError(f"need 0 <= low <= high, got {low}, {high}")
    t, hot = 0.0, bool(rng.random() < 0.5)
    t_switch = rng.exponential(dwell_s)
    while True:
        r = rate * (high if hot else low)
        gap = rng.exponential(1.0 / r) if r > 0 else float("inf")
        if t + gap >= t_switch:
            t = t_switch
            hot = not hot
            t_switch = t + rng.exponential(dwell_s)
            if t >= seconds:
                return
            continue
        t += gap
        if t >= seconds:
            return
        yield t


PROCESSES = {"poisson": poisson_arrivals, "mmpp": mmpp_arrivals}


def stream(mix: dict, seconds: float, base_seed: int,
           rate: float = 0.0) -> List[Tuple[float, int]]:
    """``(t, user_id)`` arrivals in ``[0, seconds)``, sorted by time.

    ``mix["arrivals"]`` names the process, its ``rate_rps`` (``rate``
    overrides it, for a sweep) and the process's own parameters;
    ``mix["users"]`` the ``population`` and Zipf ``skew``."""
    arr = dict(mix["arrivals"])
    process = PROCESSES[arr.pop("process")]
    rate = float(rate or arr.pop("rate_rps"))
    arr.pop("rate_rps", None)
    base = np.random.default_rng(base_seed)
    times = np.fromiter(process(rate, seconds, base, **arr), float)
    users = zipf_users(base, mix["users"]["population"],
                       mix["users"]["skew"], len(times))
    return [(float(t), int(u)) for t, u in zip(times, users)]
