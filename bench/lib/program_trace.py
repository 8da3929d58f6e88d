"""The program's own spans and counters (``repro.core.tracing``) in a
traced run, and the per-layer readings they give.

``load`` reads the spans the program writes into the profiler's trace
(``PROGRAM_SPANS``, on the host thread), on the trace's clock, beside
the device operations ``trace.load`` reads from the same file.  The
readings:

    rank_wait_ms       median over completed requests of the ``launch``
                       mark less the ``due`` mark: loop lateness, slot
                       and batcher wait
    rank_deliver_ms    median of ``sink`` less ``launched``: completion
                       ordering, spill and materialize before the sink
    psi_host_ms        time in window.stage, window.scatter,
                       window.materialize and dram.spill (their union)
                       per psi staged into the window
    psi_host_mb        megabytes of psi that crossed between host and
                       device or were copied on the host, per psi staged
                       (``psi_host_bytes``)
    rank_pad_share     100 x (1 - real / launched prefix tokens) of the
                       rank launches
    idle_unattributed_share
                       share of the window in which the device is idle,
                       the host is not waiting for an arrival and is in
                       no program span

``ledgers`` sums a deployment's executor counters and page-pool ``h2d``
ledgers, each pool's with its ``psi_host_bytes``; ``less`` of two such
sums is the window's share.

``idle_gaps`` names each idle gap by the program span whose own
(innermost) time covers most of it, or ``wait_arrival``, and falls back
to ``trace._host_state`` (the benchmark's own spans, or ``host``) where
those cover less than half of it; its shape is ``Reduced.idle_gaps``.
``idle_by_span`` splits the device's idle time by the span the host
was in, innermost first: where the host time that holds the chip idle
goes.
"""

from __future__ import annotations

import glob
import os
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

from . import trace as tr

PROGRAM_SPANS = ("relay.event", "relay.sink", "exec.rank", "exec.prefill",
                 "exec.prepare", "exec.put", "exec.wait", "window.stage",
                 "window.scatter", "window.materialize", "dram.spill")
PSI_HOST_SPANS = ("window.stage", "window.scatter", "window.materialize",
                  "dram.spill")


def _sum(ledgers) -> Dict[str, int]:
    out: Dict[str, int] = defaultdict(int)
    for ledger in ledgers:
        for k, v in ledger.items():
            out[k] += v
    return dict(out)


def psi_host_bytes(h2d: Dict[str, int], device_pool: bool) -> int:
    """Bytes of psi that one page pool's ledger says crossed between
    host and device or were copied on the host: pulls to the host
    (``d2h_bytes``), host page-buffer writes (``mirror_bytes``) and
    landings put from the host (scattered but not device-sourced).  A
    host pool's dense copies (``materialized_bytes``) are host copies
    too; a device pool's are its gathers' pulls, already in
    ``d2h_bytes``."""
    out = (h2d.get("d2h_bytes", 0) + h2d.get("mirror_bytes", 0)
           + h2d.get("bytes_scattered", 0)
           - h2d.get("device_sourced_bytes", 0))
    if not device_pool:
        out += h2d.get("materialized_bytes", 0)
    return out


def ledgers(dep):
    """(executor counters, pool h2d ledgers) of the deployment, summed;
    the pools' sum carries ``psi_host_bytes``."""
    from repro.core.paging import DevicePagePool
    pools = [i.hbm.pool for i in dep.svc.instances.values()
             if getattr(i.hbm, "pool", None) is not None]
    return (_sum(ex.counters for ex in dep.executors),
            _sum({**p.h2d, "psi_host_bytes": psi_host_bytes(
                p.h2d, isinstance(p, DevicePagePool))} for p in pools))


def less(after: Dict[str, int], before: Dict[str, int]) -> Dict[str, int]:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def load(profile_dir: str) -> List[tr.Span]:
    """The program's spans in the newest ``.xplane.pb`` under
    ``profile_dir``, with their arguments."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in PROGRAM_SPANS:
                    s = ev.start_ns * 1e-9
                    out.append(tr.Span(ev.name, s, s + ev.duration_ns * 1e-9,
                                       tr._stats(ev)))
    return out


# --- readings of the in-memory record ----------------------------------------


def _median_between(marks: Dict, first: str, last: str) -> Optional[float]:
    gaps = [m[last] - m[first] for m in marks.values()
            if first in m and last in m and "sink" in m]
    return 1e3 * statistics.median(gaps) if gaps else None


def rank_wait_ms(marks: Dict) -> Optional[float]:
    return _median_between(marks, "due", "launch")


def rank_deliver_ms(marks: Dict) -> Optional[float]:
    return _median_between(marks, "launched", "sink")


def psi_host_ms(spans: Sequence) -> Optional[float]:
    """``spans`` carry ``name``, ``t0`` and ``t1`` (``Tracer.spans``)."""
    staged = sum(s.name == "window.stage" for s in spans)
    if not staged:
        return None
    busy = tr.union([(s.t0, s.t1) for s in spans
                     if s.name in PSI_HOST_SPANS])
    return 1e3 * tr.length(busy) / staged


def psi_host_mb(h2d: Dict[str, int], staged: int) -> Optional[float]:
    """``h2d``: the pools' ledger over the window (a difference), with
    ``psi_host_bytes``."""
    if not staged or "psi_host_bytes" not in h2d:
        return None
    return h2d["psi_host_bytes"] / staged / 1e6


def rank_pad_share(counters: Dict[str, int]) -> Optional[float]:
    """``counters``: the executors' rank counters over the window."""
    launched = counters.get("rank_tokens_launched", 0)
    if launched <= 0:
        return None
    return 100.0 * (1.0 - counters["rank_tokens_real"] / launched)


# --- readings of the trace ----------------------------------------------------


def _busy(trace: tr.Trace, n_devices: int) -> Dict[str, list]:
    names = sorted(trace.ops)[:n_devices]
    return {d: tr.union([(s, e) for s, e, _ in trace.ops.get(d, ())])
            for d in names}


def idle_unattributed_s(trace: tr.Trace, program: Sequence[tr.Span],
                        n_devices: int = 1) -> float:
    """Seconds of the window (mean over the devices) in which the device
    is idle and the host is neither waiting for an arrival nor in a
    program span."""
    lo, hi = trace.window()
    named = tr.Cover(tr.union(
        [(s.start, s.end) for s in trace.spans if s.name == "wait_arrival"]
        + [(s.start, s.end) for s in program]))
    busy = _busy(trace, n_devices)
    total = 0.0
    for merged in busy.values():
        total += sum((e - s) - named(s, e)
                     for s, e in tr.gaps(merged, lo, hi))
    return total / max(len(busy), 1)


def idle_unattributed_share(trace: tr.Trace, program: Sequence[tr.Span],
                            n_devices: int = 1) -> Optional[float]:
    lo, hi = trace.window()
    if hi <= lo:
        return None
    return 100.0 * idle_unattributed_s(trace, program, n_devices) / (hi - lo)


def own_time(program: Sequence[tr.Span]) -> Dict[str, List[tr.Interval]]:
    """Each span name's own time: the intervals in which a span of that
    name is the innermost open one (spans of one host thread nest)."""
    own: Dict[str, List[tr.Interval]] = defaultdict(list)
    stack: List[tr.Span] = []
    t = 0.0

    def attribute(until: float) -> None:
        if stack and until > t:
            own[stack[-1].name].append((t, until))

    for s in sorted(program, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= s.start:
            attribute(stack[-1].end)
            t = max(t, stack.pop().end)
        attribute(s.start)
        stack.append(s)
        t = max(t, s.start)
    while stack:
        attribute(stack[-1].end)
        t = max(t, stack.pop().end)
    return dict(own)


def idle_by_span(trace: tr.Trace, program: Sequence[tr.Span],
                 n_devices: int = 1) -> Dict[str, float]:
    """Seconds of the window (mean over the devices) in which the device
    is idle while the host is in each span name's own time, and under
    ``wait_arrival`` and ``none`` the rest of the idle time."""
    lo, hi = trace.window()
    busy = _busy(trace, n_devices)
    named = {**own_time(program), "wait_arrival": tr.union(
        [(s.start, s.end) for s in trace.spans if s.name == "wait_arrival"])}
    out: Dict[str, float] = defaultdict(float)
    for merged in busy.values():
        on = tr.Cover(merged)
        for name, ivs in named.items():
            for s, e in tr.clip(tr.union(ivs), lo, hi):
                out[name] += (e - s) - on(s, e)
    out = {k: v / max(len(busy), 1) for k, v in out.items()}
    out["none"] = idle_unattributed_s(trace, program, n_devices)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


class GapNamer:
    """Names an idle gap by the program span whose own time (innermost,
    ``own_time``) covers most of it, or ``wait_arrival`` where the host
    waited longer, once the two together cover at least half of the
    gap; else by the benchmark's own naming (``trace._host_state``: its
    spans, or ``host``)."""

    def __init__(self, trace: tr.Trace, program: Sequence[tr.Span]):
        self.trace = trace
        self.own = {name: tr.Cover(tr.union(ivs))
                    for name, ivs in own_time(program).items()}
        self.own["wait_arrival"] = tr.Cover(tr.union(
            [(s.start, s.end) for s in trace.spans
             if s.name == "wait_arrival"]))

    def __call__(self, gap: tr.Interval) -> str:
        cover = {name: c(*gap) for name, c in self.own.items()}
        if cover and sum(cover.values()) >= 0.5 * (gap[1] - gap[0]):
            return max(cover, key=cover.get)
        return tr._host_state(self.trace.spans, gap)


def idle_gaps(trace: tr.Trace, program: Sequence[tr.Span],
              n_devices: int = 1, top: int = 10,
              skip: Sequence[str] = ()) -> List[list]:
    """The ``top`` longest idle gaps, each named by ``GapNamer``; gaps
    named in ``skip`` are passed over."""
    lo, hi = trace.window()
    found = [g for merged in _busy(trace, n_devices).values()
             for g in tr.gaps(merged, lo, hi)]
    found.sort(key=lambda g: g[0] - g[1])
    name = GapNamer(trace, program)
    out = []
    for g in found:
        if len(out) == top:
            break
        named = name(g)
        if named not in skip:
            out.append([named, g[1] - g[0]])
    return out
