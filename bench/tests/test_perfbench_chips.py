"""The benchmark over several chips: the trace reduction counts each
device on its own, ``chip_busy_spread`` reads the busiest chip against
the mean, and ``L8k-zipf-x4`` rehearses on four virtual CPU devices
(``--xla_force_host_platform_device_count=4``, in a process of its own,
since the flag must be set before JAX starts): one executor per device,
one special and one normal instance on each, and a fault planted on one
chip alone still reads ``correct`` false."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import spec, trace  # noqa: E402
from bench.lib.readings import Run, device_ms_per_launch  # noqa: E402
from bench.lib.spans import Launch  # noqa: E402
from bench.models import hstu_gr  # noqa: E402

SPREAD = spec.metric_reader("chip_busy_spread")


def _two_chips():
    """Both chips run ``jit_prefill`` over [1, 2); chip 1 also runs
    ``jit_rank_pages`` over [4, 4.5)."""
    window = [trace.Span("bench_window", 0.0, 10.0, {})]
    ops = {"/device:TPU:0": [(1.0, 2.0, "fusion.1")],
           "/device:TPU:1": [(1.0, 2.0, "fusion.1"), (4.0, 4.5, "while")]}
    programs = {"/device:TPU:0": [(1.0, 2.0, "jit_prefill")],
                "/device:TPU:1": [(1.0, 2.0, "jit_prefill"),
                                  (4.0, 4.5, "jit_rank_pages")]}
    return trace.Trace(ops, window, programs)


def _run(reduced):
    return Run(hstu_gr, {}, 16, 32, [Launch("prefill", "prefill", [64])] * 2,
               [], [], reduced)


def test_a_program_on_two_chips_at_once_counts_on_each():
    r = trace.reduce(_two_chips(), 2)
    assert r.program_s == pytest.approx({"jit_prefill": 2.0,
                                         "jit_rank_pages": 0.5})
    assert r.device_busy_s == pytest.approx({"/device:TPU:0": 1.0,
                                             "/device:TPU:1": 1.5})
    assert r.busy_s == pytest.approx(1.25)
    # two prefill launches, one on each chip, each 1 s on its device
    assert device_ms_per_launch(_run(r), "prefill") == pytest.approx(1e3)


def test_one_device_reads_as_the_union_of_its_executions():
    """On one device the reduction is the union it always was, to the
    digit, overlapping executions and the window's edges included."""
    rng = np.random.default_rng(7)
    starts = np.sort(rng.random(200) * 12.0 - 1.0)
    names = rng.choice(["jit_prefill", "jit_rank_pages",
                        "jit_pool_scatter"], 200)
    execs = [(float(s), float(s + d), str(n))
             for s, d, n in zip(starts, rng.random(200) * 0.3, names)]
    t = trace.Trace({"/device:TPU:0": [(s, e, "op") for s, e, _ in execs]},
                    [trace.Span("bench_window", 0.0, 10.0, {})],
                    {"/device:TPU:0": execs})
    r = trace.reduce(t)
    for name in set(names):
        ivs = [(s, e) for s, e, n in execs if n == name]
        assert r.program_s[name] == trace.length(
            trace.clip(trace.union(ivs), 0.0, 10.0))
    assert list(r.device_busy_s.values()) == [r.busy_s]
    assert r.n_devices == 1


def test_chip_busy_spread_reads_the_busiest_chip_over_the_mean():
    assert SPREAD(_run(trace.reduce(_two_chips(), 2))) == \
        pytest.approx(1.5 / 1.25)
    # a chip that ran nothing counts 0 in the mean
    assert SPREAD(_run(trace.reduce(_two_chips(), 3))) == \
        pytest.approx(1.5 / (2.5 / 3))


def test_chip_busy_spread_reads_nothing_on_one_chip():
    one = trace.Trace({"/device:TPU:0": [(1.0, 2.0, "fusion.1")]},
                      [trace.Span("bench_window", 0.0, 10.0, {})], {})
    assert SPREAD(_run(trace.reduce(one))) is None
    assert SPREAD(_run(None)) is None
    # the CPU backend's devices share one host plane: one device
    cpu = trace.Trace({trace.HOST: [(1.0, 2.0, "dot")]},
                      [trace.Span("bench_window", 0.0, 10.0, {})], {})
    assert SPREAD(_run(trace.reduce(cpu, 4))) is None


# --- the four-chip cell, rehearsed on four CPU devices -----------------------


def _rehearse(tmp_path, prelude: str = ""):
    """``bench/run.py`` rehearsing ``L8k-zipf-x4`` in a process of its own
    with four CPU devices; ``prelude`` runs first (a planted fault).
    Returns the JSON lines it printed."""
    script = tmp_path / "rehearse.py"
    script.write_text(textwrap.dedent(f"""
        import sys
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
    """) + textwrap.dedent(prelude) + textwrap.dedent("""
        from bench import run
        sys.exit(run.main(["--workload", "L8k-zipf-x4", "--seed",
                           str(2**33 + 41), "--seconds", "2",
                           "--rehearse", "--rate", "20"]))
    """))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run([sys.executable, str(script)], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return [json.loads(x) for x in done.stdout.splitlines()
            if x.startswith("{")]


def test_four_chip_cell_rehearses_with_an_instance_pair_on_each_device(
        tmp_path):
    out = _rehearse(tmp_path)
    line = out[-1]
    placement, = (d["instances"] for d in out
                  if d.get("phase") == "placement")
    window, = (d for d in out if d.get("phase") == "window")
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                              "memory_peak_bytes": 0}
    # four executors, one on each device; a special and a normal on each
    assert len(set(placement.values())) == 4
    for device in set(placement.values()):
        assert sorted(n.split("-")[0] for n, d in placement.items()
                      if d == device) == ["normal", "special"]
    assert line["correct"] is True and line["failed"] == 0
    assert sum(n for h, n in window["hits"].items() if h != "miss") > 0
    assert set(line["metrics"]) == {"rank_p50_ms", "setup_s"}


def test_a_fault_on_one_chip_is_not_correct(tmp_path):
    """Scores altered where one chip's executor produces them: the
    check's sample holds that chip's requests, so ``correct`` is
    false."""
    out = _rehearse(tmp_path, """
        import jax
        from repro.core.executors import BatchedLiveExecutor
        inner = BatchedLiveExecutor.rank_group
        last = jax.devices()[-1]

        def rank_group(self, group):
            scores, ms = inner(self, group)
            if self.device == last:
                scores = [s.at[0].add(0.05 * abs(s).max()) for s in scores]
            return scores, ms

        BatchedLiveExecutor.rank_group = rank_group
    """)
    line = out[-1]
    assert line["correct"] is False
    assert line["checks"]["score_gap"]["value"] > \
        line["checks"]["score_gap"]["limit"]
