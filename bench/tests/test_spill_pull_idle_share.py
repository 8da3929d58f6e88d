"""``spill_pull_idle_share`` on a hand-made ``Run.program``: the share
of deferred spill pulls settled while the loop was idle, and nothing
where the program's ledger has no such counter (a program that settles
its spill pulls before the sink) or deferred no pull."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import spec  # noqa: E402
from bench.lib.readings import Program, Run  # noqa: E402


def _run(h2d):
    return Run(None, {}, 64, 512, [], [], [],
               program=Program([], {}, {}, h2d))


@pytest.mark.parametrize("h2d,want", [
    ({"pulls_deferred": 40, "pulls_settled_idle": 37,
      "pulls_settled_forced": 3}, 92.5),
    ({"pulls_deferred": 8, "pulls_settled_idle": 0,
      "pulls_settled_forced": 8}, 0.0),
    ({"pulls_deferred": 0, "pulls_settled_idle": 0,
      "pulls_settled_forced": 0}, None),
    ({"d2h_bytes": 4096, "materialized_bytes": 4096}, None)])
def test_spill_pull_idle_share_reads_the_pools_ledger(h2d, want):
    read = spec.metric_reader("spill_pull_idle_share")
    got = read(_run(h2d))
    assert got == (None if want is None else pytest.approx(want))
    assert read(Run(None, {}, 64, 512, [], [], [])) is None
