"""Unit tests of the benchmark's own arithmetic: percentiles and
goodput, the pacing clock, the trace reduction, the FLOP count of
``bench/models/hstu_gr.py``, the peaks table and finding cells,
configurations and metrics by name."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import spec, stats, trace  # noqa: E402
from bench.lib.clock import PacingClock, WindowClosed  # noqa: E402
from bench.lib.peaks import peaks_of  # noqa: E402
from bench.lib.readings import (Run, device_ms_per_launch,  # noqa: E402
                                 roofline_pct)
from bench.lib.spans import Launch  # noqa: E402
from bench.models import hstu_gr  # noqa: E402


# --- percentiles and goodput ---------------------------------------------------


def test_one_stalled_request_moves_p95():
    lat = [5.0] * 19 + [6.0]
    assert stats.percentile(lat, 95) == 5.0
    lat[3] = 900.0                      # one request stalls
    assert stats.percentile(lat, 95) == 6.0
    lat[7] = 800.0
    assert stats.percentile(lat, 95) == 800.0
    assert stats.percentile(lat, 50) == 5.0


def test_failed_request_counts_as_a_miss():
    lat = [3.0, 4.0, 5.0, 30.0]
    done = [True, True, False, True]    # the third never completed
    assert stats.goodput(lat, done, budget_ms=20.0, seconds=2.0) == 1.0
    assert stats.goodput(lat, [True] * 4, 20.0, 2.0) == 1.5


def test_percentile_is_nearest_rank():
    assert stats.percentile([1, 2, 3, 4], 50) == 2
    assert stats.percentile([1, 2, 3, 4], 100) == 4
    assert stats.percentile([7], 95) == 7
    with pytest.raises(ValueError):
        stats.percentile([], 50)


# --- the pacing clock -------------------------------------------------------------


def test_clock_sleeps_to_due_time_and_never_goes_back():
    clock = PacingClock()
    due = clock.now() + 0.05
    clock.advance(due)
    assert clock.now() >= due
    t = clock.now()
    clock.advance(t - 1.0)              # late: returns at once
    assert clock.now() - t < 0.01
    assert clock.max_late_s >= 1.0
    seen = [clock.now() for _ in range(100)]
    assert all(b >= a for a, b in zip(seen, seen[1:]))


def test_clock_deadline_closes_the_window():
    clock = PacingClock()
    clock.deadline = clock.now() + 0.01
    clock.advance(clock.now())
    with pytest.raises(WindowClosed):
        clock.advance(clock.deadline + 1.0)


# --- trace reduction --------------------------------------------------------------


def _span(name, s, e, **args):
    return trace.Span(name, s, e, args)


def _small_trace():
    ops = {"/device:TPU:0": [(1.0, 2.0, "fusion.1"), (1.5, 2.5, "fusion.2"),
                             (4.0, 5.0, "fusion.1"), (8.0, 8.5, "copy")]}
    spans = [_span("bench_window", 0.0, 10.0),
             _span("rank", 0.9, 2.6, launch=0, kind="cached"),
             _span("prefill", 3.9, 5.1, launch=1, kind="prefill"),
             _span("wait_arrival", 5.5, 7.5),
             _span("scatter", 7.9, 8.6, launch=2, kind="scatter")]
    programs = {"/device:TPU:0": [(1.0, 2.5, "jit_rank_pages"),
                                  (4.0, 5.0, "jit_prefill"),
                                  (8.0, 8.5, "jit_pool_scatter")]}
    return trace.Trace(ops, spans, programs)


def test_busy_is_the_union_of_operations():
    r = trace.reduce(_small_trace())
    assert r.window_s == pytest.approx(10.0)
    # [1, 2.5] + [4, 5] + [8, 8.5]
    assert r.busy_s == pytest.approx(3.0)
    assert r.program_s == pytest.approx({"jit_rank_pages": 1.5,
                                         "jit_prefill": 1.0,
                                         "jit_pool_scatter": 0.5})
    assert r.device_ops[0] == ["fusion.1", pytest.approx(2.0)]


def test_idle_is_attributed_to_the_host_span():
    r = trace.reduce(_small_trace())
    # idle 7.0 s, of which 2.0 s waiting for an arrival
    assert r.idle_host_s == pytest.approx(5.0)
    longest = r.idle_gaps[0]
    assert longest[1] == pytest.approx(3.0)          # [5.0, 8.0]
    assert longest[0] == "wait_arrival"
    names = dict((round(t, 6), n) for n, t in r.idle_gaps)
    assert names[1.5] == "host"                        # [2.5, 4.0]


def test_cover_matches_clip_and_sum():
    rng = np.random.default_rng(0)
    ivs = trace.union([(s, s + d) for s, d in zip(rng.random(50) * 10,
                                                   rng.random(50))])
    cov = trace.Cover(ivs)
    for lo, hi in zip(rng.random(20) * 10, rng.random(20) * 10 + 5):
        assert cov(lo, hi) == pytest.approx(
            trace.length(trace.clip(ivs, lo, hi)))


def test_roofline_reads_work_over_device_time():
    dims = {"n_layers": 2, "d_model": 64, "n_heads": 2, "head_dim": 32,
            "n_tasks": 1, "dtype": "float32"}
    reduced = trace.reduce(_small_trace())
    launches = [Launch("rank", "cached", [100]), Launch("prefill",
                                                        "prefill", [64])]
    peaks = {"flops_per_s": 1e9, "bytes_per_s": 1e9}
    run = Run(hstu_gr, dims, 16, 32, launches, [], [], reduced, peaks)
    f, b = hstu_gr.rank_work(dims, [100], 16, 32, True)
    want = 100 * max(f / 1e9, b / 1e9) / 1.5
    assert roofline_pct(run, "rank") == pytest.approx(want)
    assert roofline_pct(Run(hstu_gr, dims, 16, 32, launches, [], [], None,
                            peaks), "rank") is None


def test_device_time_per_launch_is_read_by_program_not_host_span():
    """The first process on a fresh machine can show the device's
    timeline offset from the host's by a millisecond or more, which
    moves a 0.15 ms scatter out of its host span; the programs' own
    executions still give each launch kind its device time."""
    tr = _small_trace()
    shifted = trace.Trace(tr.ops, tr.spans, {d: [(s + 0.8, e + 0.8, n)
                                                  for s, e, n in ivs]
                                              for d, ivs in
                                              tr.programs.items()})
    launches = [Launch("rank", "cached", [100]),
                Launch("prefill", "prefill", [64]),
                Launch("scatter", "scatter", [], pages=4),
                Launch("scatter", "scatter", [], pages=4)]
    for t in (tr, shifted):
        run = Run(hstu_gr, {}, 16, 32, launches, [], [], trace.reduce(t))
        assert device_ms_per_launch(run, "scatter") == pytest.approx(250.0)
        assert device_ms_per_launch(run, "prefill") == pytest.approx(1e3)
    no_scatter = Run(hstu_gr, {}, 16, 32, launches[:2], [], [],
                     trace.reduce(tr))
    assert device_ms_per_launch(no_scatter, "scatter") is None


# --- FLOPs against the jaxpr count ------------------------------------------------


def test_prefill_flops_match_the_jaxpr_count_less_the_masked_half():
    import jax
    import jax.numpy as jnp
    from repro.launch.flops import step_flops
    from repro.models import build_model, get_config
    cfg = get_config("hstu-gr", smoke=True)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    dims = {k: getattr(cfg, k) for k in ("n_layers", "d_model", "n_heads",
                                          "head_dim", "n_tasks", "dtype")}
    for S in (64, 192):
        toks = jax.ShapeDtypeStruct((1, S), jnp.int32)
        dense = step_flops(lambda p, t: model.prefill(p, {"tokens": t}),
                           (params, toks))
        masked = (cfg.n_layers * 4 * cfg.n_heads * cfg.head_dim
                  * S * (S - 1) / 2)
        logits = 2 * cfg.d_model * cfg.vocab_padded   # last position only
        assert hstu_gr.prefill_row_flops(dims, S) == pytest.approx(
            dense - masked - logits)


def test_rank_flops_match_the_jaxpr_count_less_the_masked_pairs():
    import jax
    import jax.numpy as jnp
    from repro.launch.flops import step_flops
    from repro.models import build_model, get_config
    cfg = get_config("hstu-gr", smoke=True)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    dims = {k: getattr(cfg, k) for k in ("n_layers", "d_model", "n_heads",
                                          "head_dim", "n_tasks", "dtype")}
    L, H, D = cfg.n_layers, cfg.n_heads, cfg.head_dim
    P, n_incr, n_items = 128, 16, 32
    kv = jax.ShapeDtypeStruct((L, 1, P, H, D), jnp.float32)
    ids = [jax.ShapeDtypeStruct((1, n), jnp.int32) for n in (n_incr,
                                                            n_items)]
    dense = step_flops(model.rank_with_cache, (params, (kv, kv), *ids))
    Q, K = n_incr + n_items, P + n_incr + n_items
    kept = (n_incr * P + n_incr * (n_incr + 1) / 2
            + n_items * (P + n_incr + 1))
    masked = L * 4 * H * D * (Q * K - kept)
    assert hstu_gr.rank_row_flops(dims, P, n_incr, n_items) == pytest.approx(
        dense - masked)


# --- peaks ------------------------------------------------------------------------


def test_unknown_device_kind_raises():
    assert peaks_of("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_of("TPU v99")


# --- data-driven layout -----------------------------------------------------------


def test_cell_config_and_metric_added_as_files_are_found(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "configs" / "new-cfg.json").write_text(
        json.dumps({"name": "new-cfg", "history_cap": 123}))
    (tmp_path / "bench" / "traffic" / "new-mix.json").write_text(
        json.dumps({"arrivals": {"process": "poisson", "rate_rps": 3.0}}))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42 if run == 'ok' else None\n")
    bench["configs"].append({"name": "new-cfg", "source": "x",
                             "file": "bench/configs/new-cfg.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-cell", "config": "new-cfg",
                               "traffic": "new-mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "count",
                               "better": "lower", "source":
                               "program_counter", "layer": "x",
                               "moves": "rank_p95_ms",
                               "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = spec.cell("new-cell", tmp_path)
    assert c["config"]["history_cap"] == 123
    assert c["traffic"]["arrivals"]["rate_rps"] == 3.0
    assert [m["name"] for m in c["per_layer"]] == ["new_metric"]
    assert spec.read_metrics(c["per_layer"], "ok", tmp_path) == {
        "new_metric": {"value": 42.0, "unit": "count"}}
    assert spec.read_metrics(c["per_layer"], "none", tmp_path) == {}
    with pytest.raises(KeyError):
        spec.cell("no-such-cell", tmp_path)


def test_every_listed_metric_has_a_reader_and_every_cell_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for w in bench["workloads"]:
        c = spec.cell(w["name"])
        assert c["config"]["name"] == w["config"]
        assert c["end_to_end"] and c["per_layer"]


def test_same_work_for_every_seed():
    """The traffic depends on the mix alone: the seed draws weights and
    the checked sample, never the arrivals."""
    from bench.lib import traffic
    mix = json.loads((ROOT / "bench" / "traffic"
                      / "zipf-steady-8k.json").read_text())
    a = traffic.stream(mix, 5.0, base_seed=mix["base_seed"])
    assert a == traffic.stream(mix, 5.0, base_seed=mix["base_seed"])
    assert a != traffic.stream(mix, 5.0, base_seed=mix["base_seed"] + 1)
    assert all(0 <= t < 5.0 for t, _ in a)
    assert [t for t, _ in a] == sorted(t for t, _ in a)


def test_a_recorded_cpu_trace_reduces(tmp_path):
    """A real profile of a jitted call inside benchmark spans: the CPU
    backend's operations are read from the host plane."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench_window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("rank", kind="cached",
                                              launch=i):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    r = trace.reduce(trace.load(str(tmp_path)))
    assert 0 < r.busy_s <= r.window_s
    # the CPU names each operation's program by its hlo_module
    assert len(r.program_s) == 1
    assert 0 < sum(r.program_s.values()) <= r.busy_s + 1e-9
    assert r.device_ops and r.idle_gaps
