"""Reduction of a profiler trace to device busy time, idle time and the
host spans that idle time falls in.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
``Trace``: per device, the intervals of the operations that ran on it
and of each jitted program's executions, and the benchmark's host spans
(``jax.profiler.TraceAnnotation``) with their arguments.  Host and
device events share the profiler's clock, up to an offset that the
first process on a fresh machine can show, so device time is given to
a launch kind by the programs' names, not by the host spans it falls
in.  On a TPU the operations are the events of each device plane's
``XLA Ops`` line and the programs those of its ``XLA Modules`` line
(``jit_<name>(<id>)``); on the CPU backend, which has no device plane,
both are the host events that carry an ``hlo_op`` argument, the
program named by their ``hlo_module``, and all its devices read as one.

Busy time is the union of operation intervals, so overlapping
operations count once.  Everything else here is interval arithmetic on
that union.  Over several devices each device is reduced on its own: a
program's device seconds are the union of its executions within each
device, summed over the devices, since two chips that run it at once
each spend that time.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]           # seconds, on the trace's clock

SPANS = ("bench_window", "prefill", "rank", "scatter", "wait_arrival")
HOST = "/host:CPU"


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    args: dict


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Tuple[float, float, str]]]   # device -> (s, e, op)
    spans: List[Span]
    # device -> (s, e, program), one entry per execution (per op on CPU)
    programs: Dict[str, List[Tuple[float, float, str]]] = \
        dataclasses.field(default_factory=dict)

    def window(self) -> Interval:
        w = [s for s in self.spans if s.name == "bench_window"]
        if not w:
            raise ValueError("trace has no bench_window span")
        return (w[0].start, w[0].end)


def _stats(event) -> dict:
    out = {}
    for item in event.stats:
        try:
            k, v = item
        except (TypeError, ValueError):
            continue
        out[str(k)] = v
    return out


def _op_name(event) -> str:
    """A device operation's program and HLO text, cut to a readable
    length (the text's shapes tell which program it belongs to)."""
    module = _stats(event).get("hlo_module", "")
    text = event.name[:100]
    return f"{module}: {text}" if module else text


def load(profile_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(paths[-1])
    ops: Dict[str, list] = defaultdict(list)
    programs: Dict[str, list] = defaultdict(list)
    spans: List[Span] = []
    host_ops: list = []
    host_programs: list = []
    for plane in data.planes:
        device = plane.name.startswith("/device:") \
            and not plane.name.startswith("/device:CPU")
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if device and line.name == "XLA Modules":
                    programs[plane.name].append(
                        (s, e, ev.name.split("(")[0]))
                elif device:
                    ops[plane.name].append((s, e, _op_name(ev)))
                elif plane.name == HOST:
                    if ev.name in SPANS:
                        spans.append(Span(ev.name, s, e, _stats(ev)))
                    elif ev.duration_ns > 0:
                        st = _stats(ev)
                        if "hlo_op" in st:
                            host_ops.append((s, e, ev.name))
                            host_programs.append(
                                (s, e, str(st.get("hlo_module", ""))))
    if not ops and host_ops:
        ops[HOST] = host_ops
        programs[HOST] = host_programs
    return Trace(dict(ops), spans, dict(programs))


# --- interval arithmetic ------------------------------------------------------


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if e > lo and s < hi]


def length(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


class Cover:
    """A merged interval list that answers "how much of [lo, hi) do
    you cover" in logarithmic time."""

    def __init__(self, merged: Sequence[Interval]):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.prefix = [0.0]
        for s, e in merged:
            self.prefix.append(self.prefix[-1] + e - s)

    def __call__(self, lo: float, hi: float) -> float:
        i = bisect.bisect_right(self.ends, lo)       # first end > lo
        j = bisect.bisect_left(self.starts, hi)      # first start >= hi
        if j <= i:
            return 0.0
        total = self.prefix[j] - self.prefix[i]
        total -= max(0.0, lo - self.starts[i])
        total -= max(0.0, self.ends[j - 1] - hi)
        return total


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi) that a merged interval list covers."""
    return Cover(merged)(lo, hi)


def gaps(merged: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The complement of ``merged`` inside [lo, hi)."""
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


# --- the reduction ------------------------------------------------------------


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                     # mean over the devices used
    device_busy_s: Dict[str, float]   # device -> its busy seconds
    idle_host_s: float                # idle and the host not waiting
    program_s: Dict[str, float]       # jitted program -> device seconds
    device_ops: List[list]            # [[op, seconds], ...] top 10
    idle_gaps: List[list]             # [[host span, seconds], ...] top 10
    n_devices: int = 1                # devices the cell uses


def reduce(trace: Trace, n_devices: int = 1,
           devices: Optional[Sequence[str]] = None) -> Reduced:
    lo, hi = trace.window()
    names = list(devices) if devices else sorted(trace.ops)[:n_devices]
    busy = {d: union([(s, e) for s, e, _ in trace.ops.get(d, ())])
            for d in names}
    wait = union([(s.start, s.end) for s in trace.spans
                  if s.name == "wait_arrival"])
    # each program's executions: a union within each device, summed
    # over the devices
    program_s: Dict[str, float] = defaultdict(float)
    for d in names:
        runs: Dict[str, list] = defaultdict(list)
        for s, e, name in trace.programs.get(d, ()):
            runs[name].append((s, e))
        for name, ivs in runs.items():
            program_s[name] += length(clip(union(ivs), lo, hi))
    device_busy_s = {d: covered(busy[d], lo, hi) for d in names}
    busy_s = sum(device_busy_s.values()) / len(names)
    # idle while the host was not waiting for an arrival: the idle time
    # less the waiting that fell on idle device time
    idle_host = 0.0
    named = []
    for d in names:
        on = Cover(busy[d])
        waiting = clip(wait, lo, hi)
        idle_host += (hi - lo) - on(lo, hi) - sum(
            (e - s) - on(s, e) for s, e in waiting)
        named += [(g[1] - g[0], g) for g in gaps(busy[d], lo, hi)]
    idle_host /= len(names)
    per_op: Dict[str, float] = defaultdict(float)
    for d in names:
        for s, e, op in trace.ops.get(d, ()):
            if e > lo and s < hi:
                per_op[op] += min(e, hi) - max(s, lo)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    named.sort(key=lambda x: -x[0])
    return Reduced(window_s=hi - lo, busy_s=busy_s,
                   device_busy_s=device_busy_s, idle_host_s=idle_host,
                   program_s={k: v for k, v in sorted(
                       program_s.items(), key=lambda kv: -kv[1]) if v > 0},
                   device_ops=[[op, t] for op, t in top],
                   idle_gaps=[[_host_state(trace.spans, g), t]
                              for t, g in named[:10]],
                   # the CPU backend's devices share one host plane
                   n_devices=1 if HOST in busy else max(n_devices,
                                                        len(names)))


def _host_state(spans: Sequence[Span], gap: Interval) -> str:
    """The benchmark span the host spent most of ``gap`` in; ``host``
    where it was in none (the runtime's own event handling)."""
    best, name = 0.0, "host"
    for s in spans:
        if s.name == "bench_window":
            continue
        ov = min(s.end, gap[1]) - max(s.start, gap[0])
        if ov > best:
            best, name = ov, s.name
    if best < 0.5 * (gap[1] - gap[0]):
        return "host"
    return name
