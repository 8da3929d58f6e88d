"""The harness end to end on the CPU: a rehearsal on the smoke model
prints a result that names the CPU, a traced rehearsal prints the
program's readings, and the measuring path refuses to run without a
TPU."""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]) if out else None


def test_measuring_path_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", "L2k-prod-uniform", "--seed", "1",
                   "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


def test_rehearsal_runs_the_harness_end_to_end(capsys):
    rc = run.main(["--workload", "L2k-prod-uniform", "--seed",
                   str(2**31 + 17), "--seconds", "2", "--rehearse",
                   "--rate", "25"])
    line = _last_line(capsys)
    assert rc == 0
    assert KEYS <= set(line)
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True
    assert line["attempted"] > 20 and line["failed"] == 0
    assert set(line["metrics"]) == {"rank_p50_ms", "rank_p95_ms",
                                    "slo_goodput_rps", "setup_s"}
    assert line["checks"]["score_gap"]["value"] <= \
        line["checks"]["score_gap"]["limit"]


def test_traced_rehearsal_prints_the_program_readings(capsys):
    """``--trace 1`` gives the service the program's tracer, and the
    readers of its marks, spans and counters find them in ``Run.program``;
    the profile is read back as on the chip (the CPU's operations stand
    for the device's), so ``idle_unattributed_share`` and the breakdown
    are there too.  Numbers of a rehearsal are not measurements."""
    rc = run.main(["--workload", "L8k-zipf-steady", "--seed",
                   str(2**31 + 29), "--seconds", "1", "--trace", "1",
                   "--rehearse", "--rate", "10"])
    line = _last_line(capsys)
    assert rc == 0 and line["correct"] is True
    metrics = line["metrics"]
    for name in ("rank_wait_ms", "rank_deliver_ms", "rank_pad_share",
                 "psi_host_ms", "psi_host_mb", "idle_unattributed_share"):
        assert metrics[name]["value"] >= 0, name
    assert metrics["rank_wait_ms"]["unit"] == "ms"
    assert line["device"]["busy_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
