"""The program's spans and counters read back: the reduction of
``bench/lib/program_trace.py`` on hand-made intervals and records, and
a rehearsed traced run (``bench/trace_program.py``) on the CPU."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_program  # noqa: E402
from bench.lib import program_trace as pt  # noqa: E402
from bench.lib import trace as tr  # noqa: E402
from repro.core.tracing import Span  # noqa: E402

NEW = ("rank_wait_ms", "rank_deliver_ms", "psi_host_ms", "psi_host_mb",
       "rank_pad_share", "idle_unattributed_share")


def _trace(program=()):
    """Window [0, 10); device busy [1, 2) and [6, 7); the host waits for
    an arrival over [2, 3)."""
    ops = {"/device:TPU:0": [(1.0, 2.0, "a"), (6.0, 7.0, "b")]}
    spans = [tr.Span("bench_window", 0.0, 10.0, {}),
             tr.Span("wait_arrival", 2.0, 3.0, {})]
    return tr.Trace(ops, spans + list(program))


def test_idle_unattributed_is_idle_host_time_in_no_program_span():
    program = [tr.Span("relay.event", 3.0, 6.0, {}),
               tr.Span("window.stage", 4.0, 5.0, {})]
    # idle 8 s: 1 s of it waiting, 3 s in program spans, 4 s in none
    assert pt.idle_unattributed_s(_trace(), program) == pytest.approx(4.0)
    assert pt.idle_unattributed_s(_trace(), program + [
        tr.Span("relay.event", 7.0, 9.5, {})]) == pytest.approx(1.5)
    assert pt.idle_unattributed_share(_trace(), program) == \
        pytest.approx(40.0)


def test_idle_gaps_are_named_by_the_span_whose_own_time_covers_most():
    program = [tr.Span("relay.event", 2.5, 6.0, {}),
               tr.Span("dram.spill", 3.0, 5.5, {}),
               tr.Span("window.materialize", 3.0, 4.0, {}),
               tr.Span("relay.event", 7.0, 7.5, {}),
               tr.Span("relay.event", 7.5, 8.0, {}),
               tr.Span("window.stage", 8.0, 8.6, {})]
    gaps = pt.idle_gaps(_trace(), program)
    # [2, 6): dram.spill's own 1.5 s beats materialize's 1 s and the
    # event's own 1 s
    assert gaps[0] == ["dram.spill", pytest.approx(4.0)]
    # [7, 10): program spans cover 1.6 s of 3, the two events most
    assert gaps[1] == ["relay.event", pytest.approx(3.0)]
    # [0, 1): in no program span -> the benchmark's naming
    assert gaps[2] == ["host", pytest.approx(1.0)]
    assert pt.idle_gaps(_trace(), program, skip=("relay.event",)) == \
        [gaps[0], gaps[2]]


def test_a_gap_half_waiting_half_in_spans_is_named_by_the_larger():
    trace = tr.Trace({"/device:TPU:0": [(0.0, 1.0, "a"), (3.0, 4.0, "b")]},
                     [tr.Span("bench_window", 0.0, 4.0, {}),
                      tr.Span("wait_arrival", 1.0, 1.8, {})])
    # [1, 3): waiting 0.8 s and in a handler 0.6 s; neither is half
    program = [tr.Span("relay.event", 1.9, 2.5, {})]
    assert pt.idle_gaps(trace, program) == [["wait_arrival",
                                             pytest.approx(2.0)]]
    assert pt.idle_gaps(trace, []) == [["host", pytest.approx(2.0)]]


def test_idle_time_splits_by_the_innermost_span():
    program = [tr.Span("relay.event", 3.0, 6.5, {}),
               tr.Span("dram.spill", 3.0, 5.5, {}),
               tr.Span("window.materialize", 3.0, 4.0, {})]
    own = pt.own_time(program)
    assert own == {"window.materialize": [(3.0, 4.0)],
                   "dram.spill": [(4.0, 5.5)],
                   "relay.event": [(5.5, 6.5)]}
    idle = pt.idle_by_span(_trace(), program)
    # relay.event's own [6, 6.5) falls on device work
    assert idle == pytest.approx({
        "window.materialize": 1.0, "dram.spill": 1.5, "relay.event": 0.5,
        "wait_arrival": 1.0, "none": 4.0})
    assert sum(idle.values()) == pytest.approx(8.0)     # all idle time


def test_readings_of_marks_spans_and_counters():
    marks = {1: {"due": 0.0, "launch": 0.2, "launched": 0.25, "sink": 0.4},
             2: {"due": 1.0, "launch": 1.1, "launched": 1.15, "sink": 1.2},
             3: {"due": 2.0, "launch": 2.6, "launched": 2.7, "sink": 2.75},
             4: {"due": 3.0}}                  # never completed
    assert pt.rank_wait_ms(marks) == pytest.approx(200.0)
    assert pt.rank_deliver_ms(marks) == pytest.approx(50.0)
    spans = [Span("window.stage", 0.0, 0.010, -1, {}),
             Span("window.scatter", 0.010, 0.012, -1, {}),
             Span("dram.spill", 0.5, 0.530, -1, {}),
             Span("window.materialize", 0.505, 0.525, 2, {}),
             Span("window.stage", 1.0, 1.008, -1, {}),
             Span("exec.rank", 0.0, 2.0, -1, {})]
    # union: 10 + 2 + 30 + 8 ms over two staged psi
    assert pt.psi_host_ms(spans) == pytest.approx(25.0)
    h2d = {"d2h_bytes": 3e6, "mirror_bytes": 3e6, "materialized_bytes": 2e6,
           "bytes_scattered": 4e6, "pages_scattered": 10}
    assert pt.psi_host_mb(h2d, 2) == pytest.approx(6.0)
    # one launch of three rows (40, 70, 90 tokens) padded to 4 x 128
    counters = {"rank_tokens_launched": 512, "rank_tokens_real": 200}
    assert pt.rank_pad_share(counters) == pytest.approx(
        100 * (1 - 200 / 512))
    assert pt.rank_pad_share({"rank_tokens_launched": 0}) is None
    assert pt.psi_host_ms([]) is None and pt.psi_host_mb(h2d, 0) is None


def test_rehearsed_traced_run_reports_the_program_metrics(tmp_path):
    args = trace_program.run.parse_args(
        ["--workload", "L8k-zipf-steady", "--seed", str(2**31 + 5),
         "--seconds", "1", "--rehearse", "--rate", "10"])
    line = trace_program.traced_run(args, str(tmp_path))
    assert line["platform"] == "cpu" and line["completed"] > 5
    for name in NEW:
        assert line["metrics"][name] is not None, name
    assert {"relay_hit_share", "rank_device_ms", "device_idle_share",
            "device_idle_host_share"} <= set(line["metrics"])
    assert line["counters"]["rank_rows"] >= line["completed"]
    assert line["program_spans_in_trace"] == line["spans"]
    # the device time is named by the served path's programs
    assert {"jit_prefill", "jit_rank_pages", "jit_pool_scatter"} <= \
        set(line["program_device_s"])

    # the benchmark's readings do not move when a loader keeps the
    # program's spans beside its own
    trace = tr.load(str(tmp_path))
    program = pt.load(str(tmp_path))
    assert {s.name for s in program} >= {"relay.event", "exec.rank",
                                         "window.stage", "relay.sink"}
    assert not {s.name for s in trace.spans} & set(pt.PROGRAM_SPANS)
    alone = tr.reduce(trace)
    beside = tr.reduce(tr.Trace(trace.ops, trace.spans + program))
    for field in ("window_s", "busy_s", "idle_host_s", "span_device_s",
                  "device_ops"):
        assert getattr(alone, field) == getattr(beside, field), field
    assert alone.span_device_s
