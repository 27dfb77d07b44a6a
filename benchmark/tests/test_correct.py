"""`correct` has to come out false for what it is there to catch. The plain
reference is put in the program's place (stand_in.py) and run.py drives the
rest of a run over it: as it stands it passes; one precision below what the
configuration states (the control) it fails; with each planted fault it
fails. The look for a chip is patched out here, sizes are the test-only ones."""

import json

import pytest

import manifest
import run
import stand_in
from test_run_cells import CELLS, tiny_manifest

CASES = ["exact", "control", "fault:state_unchanged", "fault:half_batch",
         "fault:altered_answer"]


def make_result(case):
    def make(ref, cfg, prep, params):
        if case == "exact":
            return ref.faulty(cfg, prep, params, None)
        if case == "control":
            return ref.control(cfg, prep, params)
        return ref.faulty(cfg, prep, params, case.split(":")[1])
    return make


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("cell", CELLS)
def test_correct_is_false_for_the_control_and_each_fault(
        monkeypatch, capsys, cell, case):
    import jax

    man = tiny_manifest()
    cfg_name = next(w["config"] for w in man["workloads"] if w["name"] == cell)
    algo = manifest.config(man, cfg_name)["algo"]
    fake = stand_in.adapter(algo, make_result(case))
    real_load = manifest.load_module
    monkeypatch.setattr(manifest, "load_manifest", lambda root=None: man)
    monkeypatch.setattr(run, "require_chips", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "counters", lambda: {"xla": {}, "phases": {}, "cache": {}})
    monkeypatch.setattr(
        manifest, "load_module",
        lambda kind, name: fake if kind == "algos" else real_load(kind, name))
    rc = run.main(["--workload", cell, "--seed", "77", "--seconds", "1",
                   "--trace", "0"])
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert res["correct"] is (case == "exact"), (case, res["compared"])


def test_the_programs_own_control_lowers_its_precision_and_puts_it_back():
    """algos/glm.py `lower_precision` is the control at the cell's own size
    (benchmark/readings.py, on the chip). Here, where DEFAULT and HIGHEST are
    the same arithmetic, only that it switches what it says it switches, that
    a fit runs under it, and that it leaves the program as it found it."""
    import jax

    from h2o3_tpu.models import glm

    algo = manifest.load_module("algos", "glm")
    cfg = manifest.config(tiny_manifest(), "glm_airlines")
    data = algo.make_data(cfg, 2 ** 31 + 9)
    coefs = []
    for lowered in (False, True, False):
        est = algo.make_estimator(cfg, {})
        if lowered:
            with algo.lower_precision():
                assert glm._HI == jax.lax.Precision.DEFAULT
                algo.train(est, algo.make_frame(algo.make_columns(data)))
        else:
            assert glm._HI == jax.lax.Precision.HIGHEST
            algo.train(est, algo.make_frame(algo.make_columns(data)))
        coefs.append(algo.result(cfg, est, {})["coef"])
    assert coefs[0] == coefs[2] and set(coefs[1]) == set(coefs[0])
