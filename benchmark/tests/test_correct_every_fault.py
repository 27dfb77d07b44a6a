"""Every fault a reference plants fails its cell's comparison, the ones
test_correct.py does not name too (a reference may plant more than three)."""

import json

import pytest

import manifest
import run
import stand_in
from test_correct import CASES
from test_run_cells import CELLS, tiny_manifest


def _more():
    man = tiny_manifest()
    out = []
    for cell in CELLS:
        cfg = manifest.config(man, next(
            w["config"] for w in man["workloads"] if w["name"] == cell))
        algo = manifest.load_module("algos", cfg["algo"])
        ref = manifest.load_module("references", algo.REFERENCE)
        out += [(cell, cfg["algo"], f) for f in ref.FAULTS
                if f"fault:{f}" not in CASES]
    return out


@pytest.mark.parametrize("cell,algo,fault", _more())
def test_correct_is_false_for_a_fault_only_this_reference_plants(
        monkeypatch, capsys, cell, algo, fault):
    import jax

    man = tiny_manifest()
    fake = stand_in.adapter(
        algo, lambda ref, cfg, prep, params: ref.faulty(cfg, prep, params,
                                                        fault))
    real_load = manifest.load_module
    monkeypatch.setattr(manifest, "load_manifest", lambda root=None: man)
    monkeypatch.setattr(run, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "counters",
                        lambda: {"xla": {}, "phases": {}, "cache": {}})
    monkeypatch.setattr(
        manifest, "load_module",
        lambda kind, name: fake if kind == "algos" else real_load(kind, name))
    assert run.main(["--workload", cell, "--seed", "78", "--seconds", "1",
                     "--trace", "0"]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False, (fault, res["compared"])
