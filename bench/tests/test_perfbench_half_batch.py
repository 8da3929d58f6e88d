"""Half of a batch left out (its later rows given the first row's
scores) reads ``correct`` false, on traffic that forms multi-row rank
launches.

Rows share a launch only while more ranks overlap on one instance than
it has model slots, so the rehearsal offers about 500 req/s: an
instance then holds several ranks at once and the batcher groups them.
The executors' ``rank_rows`` and ``rank_launches`` counters assert that
it did, so the test cannot pass on single-row launches alone.
"""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402


def _half_batch(inner):
    def rank_group(self, group):
        scores, ms = inner(self, group)
        keep = max(len(scores) // 2, 1)
        return scores[:keep] + [scores[0]] * (len(scores) - keep), ms
    return rank_group


def test_half_batch_on_multi_row_launches_is_not_correct(monkeypatch,
                                                          capsys):
    from repro.core.executors import BatchedLiveExecutor
    made = []
    init = BatchedLiveExecutor.__init__

    def keep(self, *a, **kw):
        init(self, *a, **kw)
        made.append(self)

    monkeypatch.setattr(BatchedLiveExecutor, "__init__", keep)
    monkeypatch.setattr(BatchedLiveExecutor, "rank_group",
                        _half_batch(BatchedLiveExecutor.rank_group))
    rc = run.main(["--workload", "L8k-zipf-steady", "--seed", "5",
                   "--seconds", "0.4", "--rehearse", "--rate", "500"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    rows = sum(ex.counters["rank_rows"] for ex in made)
    launches = sum(ex.counters["rank_launches"] for ex in made)
    assert rows > launches, "no multi-row rank launch formed"
    assert line["correct"] is False
    assert line["checks"]["score_gap"]["value"] > \
        line["checks"]["score_gap"]["limit"]
