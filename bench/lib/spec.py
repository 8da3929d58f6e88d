"""Finds what a run needs by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file is the one its entry in ``configs``
gives; the traffic mix is ``bench/traffic/<traffic>.json``; a per-layer
metric is read by ``bench/metrics/<name>.py``.  Adding any of them is
adding files and entries: nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration and traffic mix read:
    ``{"workload": ..., "config": ..., "traffic": ..., "end_to_end":
    [...], "per_layer": [...]}`` (metrics that this cell reports)."""
    bench = load_benchmark(root)
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    work = found[0]
    conf = [c for c in bench["configs"] if c["name"] == work["config"]][0]
    config = json.loads((Path(root) / conf["file"]).read_text())
    traffic = json.loads((Path(root) / "bench" / "traffic"
                          / f"{work['traffic']}.json").read_text())

    def reports(m):
        return name in m.get("workloads", [name])

    return {"workload": work, "config": config, "traffic": traffic,
            "run_seconds": bench["run_seconds"],
            "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
            "per_layer": [m for m in bench["per_layer"] if reports(m)]}


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    mod_name = "bench_metric_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[dict], run, root: Path = ROOT
                 ) -> Dict[str, dict]:
    """Each metric's reader applied to ``run``; a reader that finds
    nothing to read returns None, and the metric is left out."""
    out = {}
    for m in metrics:
        value = metric_reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
