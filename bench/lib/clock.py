"""The pacing clock: turns the relay runtime's event loop into a server
that runs on the wall clock.

``RelayRuntime.drain`` calls ``clock.advance(t)`` before it handles the
event due at ``t``.  This clock sleeps until the host's monotonic clock
reaches ``t`` (and returns at once when the loop is already late), so
arrivals, the trigger signal and the pipeline slack fall due on the
wall clock, and a launch that blocks the loop delays everything behind
it.  A ``deadline`` ends the drain: the first event due after it raises
``WindowClosed``.
"""

from __future__ import annotations

import time
from contextlib import nullcontext


class WindowClosed(Exception):
    """The drain reached an event due after the clock's deadline."""


class PacingClock:
    def __init__(self, annotate: bool = False):
        self._t0 = time.monotonic()
        self.origin = self._t0        # monotonic seconds at clock 0
        self.deadline = float("inf")
        self.slept_s = 0.0
        self.late_s = 0.0           # summed lateness of the loop at events
        self.max_late_s = 0.0
        self.events = 0
        self._annotate = annotate

    def now(self) -> float:
        return time.monotonic() - self._t0

    def advance(self, t: float) -> None:
        if t > self.deadline:
            raise WindowClosed(t)
        self.events += 1
        dt = t - self.now()
        if dt <= 0:
            self.late_s -= dt
            self.max_late_s = max(self.max_late_s, -dt)
            return
        span = _annotation("wait_arrival") if self._annotate \
            else nullcontext()
        with span:
            time.sleep(dt)
        self.slept_s += dt


def _annotation(name: str, **kw):
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)
