"""Spans and per-request marks of the serving path, on the profiler's
clock.

A ``Tracer`` is off by default, and every component holds the shared
off tracer ``OFF`` until a runtime built with ``tracer=Tracer(on=True)``
wires its own in.  Off, ``span`` returns one shared no-op context and
``mark`` returns at once: no ``jax.profiler.TraceAnnotation`` is
constructed and nothing is kept.  On, each span opens a
``TraceAnnotation(name, **args)``, so a trace taken with
``jax.profiler.start_trace`` shows it on the host thread beside the
device operations, on the same clock, and the span is also kept in
``Tracer.spans`` on ``time.perf_counter`` with the index of its parent.
Marks are instants of one request on the runtime's clock
(``clock.now()``), kept in memory only, keyed by the request id.

Spans (names are stable; ``uid``/``uids``/``req`` args join the spans
of one request):

    relay.event        one event handler of RelayRuntime.drain
                       (kind, uid, late_ms: how late the loop reached it)
    exec.rank          a rank launch (kind cached | full, rows, pad_rows,
                       bucket: prefix tokens per row, lens, uids)
    exec.prefill       a side-path prefill launch (same args)
    exec.prepare       host input building inside a launch
    exec.put           host-to-device puts inside a launch (bytes)
    exec.wait          dispatch through block_until_ready
    window.stage       psi readied for the window: on a host pool, sliced
                       into its page buffer (d2h_bytes, mirror_bytes);
                       on a device pool, the landing's index built and
                       a host value put on the device (pages, put_bytes)
    window.scatter     the landing dispatched on the device (pages,
                       bytes)
    window.materialize a dense host copy gathered out of the pool
                       (bytes); a device pool gathers on the device and
                       pulls once
    dram.spill         psi copied into the DRAM expander (uid, bytes)
    relay.sink         scores handed to the request's sink (req, uid)

Marks: ``due`` (the rank request fell due), ``launch`` and ``launched``
(its rank launch started and returned) and ``sink`` (scores delivered).

Counters stay on the ledgers that already exist and count whether or
not tracing is on: the page pool's ``h2d`` and the executor's
``counters``.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


@dataclasses.dataclass
class Span:
    name: str
    t0: float                  # perf_counter seconds
    t1: float
    parent: int                # index in Tracer.spans; -1 at the top
    args: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def _annotation_value(v):
    """TraceMe splits its arguments at commas: lists travel
    space-separated."""
    if isinstance(v, (list, tuple)):
        return " ".join(str(x) for x in v)
    return v


class _OpenSpan:
    __slots__ = ("tracer", "name", "args", "span", "annotation")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.args = args

    def __enter__(self):
        import jax
        tr = self.tracer
        self.annotation = jax.profiler.TraceAnnotation(
            self.name, **{k: _annotation_value(v)
                          for k, v in self.args.items()})
        self.annotation.__enter__()
        stack = tr._stack()
        self.span = Span(self.name, time.perf_counter(), 0.0,
                         stack[-1] if stack else -1, self.args)
        with tr._lock:                  # threads may open spans at once
            stack.append(len(tr.spans))
            tr.spans.append(self.span)
        return self

    def __exit__(self, *exc):
        self.span.t1 = time.perf_counter()
        self.tracer._stack().pop()
        self.annotation.__exit__(*exc)
        return False


class Tracer:
    """Spans and marks of one runtime; see the module docstring."""

    def __init__(self, on: bool = False):
        self._on = bool(on)
        self.spans: List[Span] = []
        self.marks: Dict[Any, Dict[str, float]] = {}
        self._local = threading.local()       # each thread's open spans
        self._lock = threading.Lock()

    @property
    def on(self) -> bool:
        return self._on

    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **args):
        if not self._on:
            return NULL_SPAN
        return _OpenSpan(self, name, args)

    def mark(self, key, name: str, t: float) -> None:
        if self._on:
            self.marks.setdefault(key, {})[name] = float(t)

    def clear(self) -> None:
        """Forget what was recorded (e.g. set-up, before a window); call
        it while no span is open."""
        self.spans = []
        self.marks = {}

    def children(self, index: int) -> List[int]:
        return [i for i, s in enumerate(self.spans) if s.parent == index]

    def self_seconds(self, index: int) -> float:
        """A span's duration less the time its children cover (children
        of one span run one after another on its thread)."""
        return self.spans[index].seconds - sum(
            self.spans[c].seconds for c in self.children(index))


OFF = Tracer()
