"""``python -m benchmarks.run`` reports every phase and fails loudly: a
phase that raised still lets the rest run, then the command exits
non-zero naming it."""

import pytest

from benchmarks import figures, run


def _rows():
    return [("ok/row", 1.0, "fine")]


def _boom():
    raise RuntimeError("phase broke")


QUICK = ("fig11d_slo_throughput", "fig12_local_vs_remote",
         "table1_kv_footprint")


@pytest.mark.parametrize("broken", [None, "fig12_local_vs_remote"])
def test_run_exit_status_follows_phases(monkeypatch, capsys, broken):
    for name in QUICK:
        fn = _boom if name == broken else _rows
        monkeypatch.setattr(figures, name, fn)
    if broken is None:
        run.main(["--quick"])
    else:
        with pytest.raises(SystemExit) as exc:
            run.main(["--quick"])
        assert exc.value.code != 0 and "_boom" in str(exc.value.code)
    out = capsys.readouterr().out
    # every other phase still ran and printed its rows
    assert out.count("ok/row") == len(QUICK) - (broken is not None)
