"""What a per-layer metric's reader gets: one run's launches, requests,
compile count and, from a traced run, the reduced trace.  Helpers here
are the arithmetic that several readers share."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

from . import flops
from .spans import Launch
from .trace import Reduced


@dataclasses.dataclass
class Run:
    dims: dict                      # model sizes (flops.py's keys)
    n_incr: int
    n_items: int
    launches: List[Launch]          # the window's, in call order
    hits: List[str]                 # hit class of each completed request
    compiles: List[str]             # functions compiled inside the window
    trace: Optional[Reduced] = None
    peaks: Optional[dict] = None    # None where no device was traced

    def work(self, launch: Launch):
        """(FLOPs, bytes) the launch had to do."""
        if launch.kind == "prefill":
            return flops.prefill_work(self.dims, launch.lens)
        if launch.kind in ("cached", "full"):
            return flops.rank_work(self.dims, launch.lens, self.n_incr,
                                   self.n_items, launch.kind == "cached")
        return 0.0, 0.0

    def traced(self, span: str) -> Dict[int, Launch]:
        """Launches of ``span`` that the trace saw, by index."""
        if self.trace is None:
            return {}
        return {i: r for i, r in enumerate(self.launches)
                if r.span == span and i in self.trace.span_device_s}


def device_ms_per_launch(run: Run, span: str) -> Optional[float]:
    seen = run.traced(span)
    if not seen:
        return None
    return 1e3 * sum(run.trace.span_device_s[i] for i in seen) / len(seen)


def roofline_pct(run: Run, span: str) -> Optional[float]:
    """Lower-bound time of the span's launches over their device time."""
    seen = run.traced(span)
    if not seen or run.peaks is None:
        return None
    bound = sum(flops.lower_bound_s(*run.work(r), run.peaks)
                for r in seen.values())
    busy = sum(run.trace.span_device_s[i] for i in seen)
    return 100.0 * bound / busy if busy > 0 else None


def mean(xs: Sequence[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None
