"""The harness end to end on the CPU: a rehearsal on the smoke model
prints a result that names the CPU, and the measuring path refuses to
run without a TPU."""

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
