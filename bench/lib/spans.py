"""Spans and counters taken from outside the program.

``instrument`` wraps an executor's public launch methods.  Each call is
recorded as a ``Launch`` (its kind, real rows and lengths, host start
and end), and, when ``annotate`` is on, wrapped in a
``jax.profiler.TraceAnnotation`` that carries the launch's index, so
the trace reduction can take the device time inside it.

    pre_infer, pre_infer_group            -> span "prefill"
    rank_group, rank_cached, rank_full    -> span "rank" (cached | full)
    insert_pages                          -> span "scatter"

Launches end in ``block_until_ready``, so their device work falls
inside their span; the page scatter is dispatched without a wait, so
in an annotated (traced) run the span waits for the pool buffer before
it closes.

``CompileLedger`` counts backend compiles (persistent-cache loads pass
through the same event) through JAX's monitoring hooks, with the name
of each compiled function.
"""

from __future__ import annotations

import dataclasses
import time
from contextlib import nullcontext
from typing import List, Sequence


@dataclasses.dataclass
class Launch:
    span: str                  # prefill | rank | scatter
    kind: str                  # prefill | cached | full | scatter
    lens: List[int]            # real prefix length of each real row
    t0: float = 0.0            # host perf_counter seconds
    t1: float = 0.0
    pages: int = 0             # scatter: pages written
    uids: List[int] = dataclasses.field(default_factory=list)


def _rank_launch(group) -> Launch:
    kind = "full" if group[0].psi is None else "cached"
    return Launch("rank", kind, [int(w.prefix_len) for w in group],
                  uids=[int(w.user_id) for w in group])


def _describe(method: str, args) -> Launch:
    if method == "pre_infer":
        return Launch("prefill", "prefill", [int(args[0].prefix_len)])
    if method == "pre_infer_group":
        return Launch("prefill", "prefill",
                      [int(m.prefix_len) for m in args[0]])
    if method == "rank_group":
        return _rank_launch(args[0])
    if method == "rank_cached":
        return Launch("rank", "cached", [int(args[0].prefix_len)])
    if method == "rank_full":
        return Launch("rank", "full", [int(args[0].prefix_len)])
    if method == "insert_pages":
        return Launch("scatter", "scatter", [], pages=len(args[1]))
    raise KeyError(method)


METHODS = ("pre_infer", "pre_infer_group", "rank_group", "rank_cached",
           "rank_full", "insert_pages")


class LaunchLog:
    """Every instrumented call, in call order; ``recording`` gates
    whether calls are kept (set-up launches are not)."""

    def __init__(self, annotate: bool = False):
        self.launches: List[Launch] = []
        self.recording = False
        self.annotate = annotate

    def instrument(self, executor) -> None:
        for method in METHODS:
            inner = getattr(executor, method, None)
            if inner is None:
                continue
            setattr(executor, method, self._wrap(method, inner))

    def _wrap(self, method, inner):
        def call(*args, **kw):
            if not self.recording:
                return inner(*args, **kw)
            rec = _describe(method, args)
            index = len(self.launches)
            self.launches.append(rec)
            span = (_annotation(rec.span, kind=rec.kind, launch=index)
                    if self.annotate else nullcontext())
            rec.t0 = time.perf_counter()
            with span:
                out = inner(*args, **kw)
                if self.annotate and method == "insert_pages":
                    import jax
                    jax.block_until_ready(args[0].device_buffer)
            rec.t1 = time.perf_counter()
            return out
        return call


def _annotation(name: str, **kw):
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


class CompileLedger:
    """Backend compiles seen through JAX's monitoring events, with the
    function each one compiled.  Only events while ``recording`` count."""
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.recording = False
        self.names: List[str] = []
        self.seconds = 0.0

    def _on_duration(self, event, duration, **kw):
        if event == self.COMPILE and self.recording:
            self.names.append(str(kw.get("fun_name", "?")))
            self.seconds += duration

    def install(self) -> "CompileLedger":
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self


def window_annotation(annotate: bool):
    return _annotation("bench_window") if annotate else nullcontext()


def batched_tails(launches: Sequence[Launch]) -> set:
    """Users ranked as a later row of a multi-row launch: the rows a
    batching fault would get wrong."""
    return {u for r in launches if r.span == "rank" for u in r.uids[1:]}
