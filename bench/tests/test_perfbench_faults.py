"""A run with the timed path broken underneath reads ``correct`` false:
an answer altered where it is produced, and half of a batch left out
(its later rows given the first row's scores)."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402


def _altered(inner):
    def rank_group(self, group):
        scores, ms = inner(self, group)
        return [s.at[0].add(0.05 * abs(s).max()) for s in scores], ms
    return rank_group


def _half_batch(inner):
    def rank_group(self, group):
        scores, ms = inner(self, group)
        keep = max(len(scores) // 2, 1)
        return scores[:keep] + [scores[0]] * (len(scores) - keep), ms
    return rank_group


@pytest.mark.parametrize("fault", [_altered, _half_batch])
def test_broken_path_is_not_correct(fault, monkeypatch, capsys):
    from repro.core.executors import BatchedLiveExecutor
    monkeypatch.setattr(BatchedLiveExecutor, "rank_group",
                        fault(BatchedLiveExecutor.rank_group))
    rc = run.main(["--workload", "L8k-zipf-steady", "--seed", "5",
                   "--seconds", "1.5", "--rehearse", "--rate", "60"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert line["correct"] is False
    assert line["checks"]["score_gap"]["value"] > \
        line["checks"]["score_gap"]["limit"]
