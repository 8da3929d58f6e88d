"""A configuration's ``model.arch`` finds its module under
``bench/models/``, an arch without one fails before anything is built,
and the HSTU module gives the weights and work counts the benchmark
gave before the modules existed (numbers recorded from
``bench/lib/reference.py`` and ``bench/lib/flops.py`` as they were)."""

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402

NAMES = ("dims", "make_weights", "MODES", "rank_scores", "served_prefix",
         "prefill_work", "rank_work")


def _published() -> dict:
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "hstu_gr-L8k.json").read_text())
    return {k: v for k, v in cfg["model"].items() if k != "arch"}


def test_hstu_gr_resolves_to_its_module():
    mod = harness.model_module({"model": {"arch": "hstu-gr"}})
    assert Path(mod.__file__) == ROOT / "bench" / "models" / "hstu_gr.py"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for conf in bench["configs"]:
        config = json.loads((ROOT / conf["file"]).read_text())
        mod = harness.model_module(config)
        assert all(hasattr(mod, n) for n in NAMES), conf["name"]


def test_an_arch_without_a_module_fails_before_anything_is_built(
        monkeypatch):
    import repro.models

    def built(*a, **kw):
        raise AssertionError("a model was built")

    monkeypatch.setattr(repro.models, "build_model", built)
    with pytest.raises(FileNotFoundError) as err:
        harness.build({"model": {"arch": "no-such-arch"}}, {}, seed=1,
                      rehearse=True, annotate=False)
    assert str(ROOT / "bench" / "models" / "no_such_arch.py") in \
        str(err.value)


def test_hstu_gr_weights_match_the_recorded_checksum():
    import jax
    from repro.models import get_config
    mod = harness.model_module({"model": {"arch": "hstu-gr"}})
    dims = mod.dims(get_config("hstu-gr", smoke=True))
    w = mod.make_weights(dims, harness.seed_key(2**40 + 7))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(w):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == \
        "5c60099051609f544e47dc356b338c746741091d9af905932f9d4f17be19dcb6"


@pytest.mark.parametrize("call,want", [
    (("prefill_work", [64, 3000, 8192]), (370818646016.0, 206491616.0)),
    (("rank_work", [3000, 8192], 64, 512, True),
     (59968585728.0, 196112896.0)),
    (("rank_work", [490], 64, 512, False), (9445629952.0, 12652712.0))])
def test_hstu_gr_work_counts_match_the_recorded_numbers(call, want):
    mod = harness.model_module({"model": {"arch": "hstu-gr"}})
    name, *args = call
    assert getattr(mod, name)(_published(), *args) == want
