"""What a per-layer metric's reader gets: one run's launches, requests,
compile count, the program's own record of the window and, from a
traced run, the reduced trace.  Helpers here are the arithmetic that
several readers share."""

from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import Any, Dict, List, Optional, Sequence

from .spans import Launch
from .trace import Reduced, Span, Trace


@dataclasses.dataclass
class Program:
    """The program's spans and counters over the window
    (``repro.core.tracing``), as a traced run takes them."""
    spans: List[Any]                # Tracer.spans: name, t0, t1, args
    marks: Dict[Any, Dict[str, float]]   # Tracer.marks, by request
    counters: Dict[str, int]        # the executors' counters, difference
    h2d: Dict[str, int]             # the page pools' h2d, difference
    profiled: List[Span] = dataclasses.field(default_factory=list)
    trace: Optional[Trace] = None   # the profile read back
    n_devices: int = 1              # devices the trace is read over


@dataclasses.dataclass
class Run:
    arch: ModuleType                # bench/models/<arch>.py
    dims: dict                      # arch.dims of the program's config
    n_incr: int
    n_items: int
    launches: List[Launch]          # the window's, in call order
    hits: List[str]                 # hit class of each completed request
    compiles: List[str]             # functions compiled inside the window
    trace: Optional[Reduced] = None
    peaks: Optional[dict] = None    # None where no device was traced
    program: Optional[Program] = None   # traced runs

    def work(self, launch: Launch):
        """(FLOPs, bytes) the launch had to do."""
        if launch.kind == "prefill":
            return self.arch.prefill_work(self.dims, launch.lens)
        if launch.kind in ("cached", "full"):
            return self.arch.rank_work(self.dims, launch.lens, self.n_incr,
                                       self.n_items, launch.kind == "cached")
        return 0.0, 0.0

    def of(self, span: str) -> List[Launch]:
        """The window's launches of ``span``."""
        return [r for r in self.launches if r.span == span]


# the program's jitted programs (``jit_<name>``) behind each launch span
PROGRAMS = {"prefill": ("jit_prefill",),
            "rank": ("jit_rank_pages", "jit_rank_full", "jit_rank_cached"),
            "scatter": ("jit_pool_scatter",)}


def device_s(run: Run, span: str) -> Optional[float]:
    """Device seconds of the span's programs in the traced window (by
    name: a launch's short device work need not fall inside its host
    span on the profile's clock); None where the trace saw none."""
    if run.trace is None:
        return None
    t = sum(run.trace.program_s.get(p, 0.0) for p in PROGRAMS[span])
    return t if t > 0 else None


def lower_bound_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute
    and the memory bound."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])


def device_ms_per_launch(run: Run, span: str) -> Optional[float]:
    n, busy = len(run.of(span)), device_s(run, span)
    if not n or busy is None:
        return None
    return 1e3 * busy / n


def roofline_pct(run: Run, span: str) -> Optional[float]:
    """Lower-bound time of the span's launches over their device time."""
    busy = device_s(run, span)
    if not run.of(span) or busy is None or run.peaks is None:
        return None
    bound = sum(lower_bound_s(*run.work(r), run.peaks)
                for r in run.of(span))
    return 100.0 * bound / busy


def mean(xs: Sequence[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None
