"""A run with the timed path broken underneath reads ``correct`` false:
an answer altered where it is produced, and half of a batch left out
(its later rows given the first row's scores).

Each fault runs at its own offered rate.  Rows share a launch only
while more ranks overlap on one instance than it has model slots, so
the half-batch fault is offered about 500 req/s: an instance then holds
several ranks at once and the batcher groups them.  The executors'
``rank_rows`` and ``rank_launches`` counters assert that it did, so
that case cannot pass on single-row launches alone.
"""

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


@pytest.mark.parametrize("fault,rate,seconds,multi_row", [
    pytest.param(_altered, 60, 1.5, False, id="_altered"),
    pytest.param(_half_batch, 500, 0.4, True, id="_half_batch")])
def test_broken_path_is_not_correct(fault, rate, seconds, multi_row,
                                    monkeypatch, capsys):
    from repro.core.executors import BatchedLiveExecutor
    made = []
    init = BatchedLiveExecutor.__init__

    def keep(self, *a, **kw):
        init(self, *a, **kw)
        made.append(self)

    monkeypatch.setattr(BatchedLiveExecutor, "__init__", keep)
    monkeypatch.setattr(BatchedLiveExecutor, "rank_group",
                        fault(BatchedLiveExecutor.rank_group))
    rc = run.main(["--workload", "L8k-zipf-steady", "--seed", "5",
                   "--seconds", str(seconds), "--rehearse",
                   "--rate", str(rate)])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    if multi_row:
        rows = sum(ex.counters["rank_rows"] for ex in made)
        launches = sum(ex.counters["rank_launches"] for ex in made)
        assert rows > launches, "no multi-row rank launch formed"
    assert line["correct"] is False
    assert line["checks"]["score_gap"]["value"] > \
        line["checks"]["score_gap"]["limit"]
