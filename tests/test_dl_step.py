"""The compiled DeepLearning step: the program against the benchmark's plain
reference (`benchmark/references/dl_reference.py`, which imports nothing of
h2o3_tpu), ONE trace and compile of the step for a grid of
hyperparameters on one frame, one scoring of the final weights a fit, and
the row order an epoch visits.

The fits run through the benchmark's own adapter (`benchmark/algos/dl.py`):
its `replay` hands the reference the fit's initial weights and row order
from the keys the fit recorded."""

import os
import sys

import numpy as np
import pytest

from h2o3_tpu.models import dataset_cache
from h2o3_tpu.models import deeplearning as dl
from h2o3_tpu.runtime import tracing

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SEED = 2 ** 31 + 41


@pytest.fixture(scope="module")
def bench():
    added = [p for p in (BENCH,) if p not in sys.path]
    sys.path[:0] = added
    import manifest

    cfg = manifest.load_json(os.path.join(BENCH, "tests", "configs",
                                          "dl_airlines.json"))
    # about 4,000 rows, three factors and one numeric
    cfg = dict(cfg, rows=4000, numeric_columns={
        "Distance": cfg["numeric_columns"]["Distance"]})
    algo = manifest.load_module("algos", cfg["algo"])
    ref = manifest.load_module("references", algo.REFERENCE)
    data = algo.make_data(cfg, SEED)
    yield cfg, algo, ref, data, ref.prepare(cfg, data)
    for p in added:
        sys.path.remove(p)


def _fit(bench, **over):
    cfg, algo, *_ = bench
    est = algo.make_estimator(cfg, dict(seed=7, **over))
    algo.train(est, algo.make_frame(algo.make_columns(bench[3])))
    return est, algo.result(cfg, est, dict(seed=7, **over))


@pytest.mark.parametrize("activation", ["Rectifier", "Tanh"])
@pytest.mark.parametrize("l2", [0.0, 1e-3])
def test_the_program_follows_the_reference_step_by_step(cloud1, bench,
                                                        activation, l2):
    cfg, _, ref, _, prep = bench
    prep.activation = activation
    est, result = _fit(bench, activation=activation, l2=l2)
    assert est.bench_steps == -(-4000 // 32) == est.model.scoring_history[
        -1]["iterations"]
    numbers = ref.compare(cfg, prep, result)
    # 125 steps of the same float32 arithmetic from the same weights over
    # the same rows: the two programs fuse differently, so each step may
    # round differently by an ulp or so, and an epoch carries that forward.
    # Weights: within 1e-4 of how far the fit moved them (the reference's
    # own control, bfloat16 matmul inputs, reads 0.009 and more here);
    # probabilities within 1e-5; the reported logloss within 1e-6 of the
    # reference network's, relative (it is a float64 mean of float32 terms)
    assert numbers["weight_gap"] <= 1e-4, numbers
    assert numbers["prob_gap"] <= 1e-5, numbers
    assert numbers["logloss_gap"] <= 1e-6, numbers
    lowered = ref.compare(cfg, prep, ref.control(cfg, prep,
                                                 result["params"]))
    assert lowered["weight_gap"] > 1e-3, lowered


def _requests(name):
    return [s for s in tracing.spans()
            if s["kind"] == "xla" and name in str(s["attrs"].get("program"))]


def test_a_grid_on_one_frame_traces_and_compiles_the_step_once(cloud1,
                                                               bench):
    """Two fits of different rho, epsilon and l2 on one frame: the compile
    listener (runtime/phases.py) records ONE request for the step program,
    in the first fit; a shape no other test uses, so the first is this
    process's first."""
    from h2o3_tpu.runtime import phases

    cfg, algo, *_ = bench
    phases.install_listener()
    frame = algo.make_frame(algo.make_columns(bench[3]))
    tracing.clear()
    counts = []
    for over in ({"rho": 0.95, "epsilon": 1e-10, "l2": 0.0},
                 {"rho": 0.999, "epsilon": 1e-6, "l2": 1e-5}):
        est = algo.make_estimator(cfg, dict(over, hidden=[13, 7], seed=3))
        algo.train(est, frame)
        counts.append(len(_requests("dl_train_chunk")))
    assert counts == [1, 1], counts
    tracing.clear()


def test_a_fit_scores_its_final_weights_once(cloud1, bench, monkeypatch):
    cfg, algo, *_ = bench
    calls = []
    real = dl.DeepLearningModel._make_metrics

    def counted(self, frame, X_pre=None):
        calls.append(frame.nrow)
        return real(self, frame, X_pre=X_pre)

    monkeypatch.setattr(dl.DeepLearningModel, "_make_metrics", counted)
    frame = algo.make_frame(algo.make_columns(bench[3]))
    for tspi, events in ((-2, 1), (2000, 2)):
        calls.clear()
        est = algo.make_estimator(cfg, dict(
            seed=5, train_samples_per_iteration=tspi))
        algo.train(est, frame)
        assert len(calls) == events == len(est.model.scoring_history)
        assert est.model.training_metrics.logloss == pytest.approx(
            est.model.scoring_history[-1]["logloss"], rel=0, abs=0)


def test_an_epoch_visits_every_row_once_in_the_permutations_order():
    import jax

    rows, keys = dl._chunk_rows(jax.random.PRNGKey(4), 1000, 32, 32)
    order = np.asarray(rows).reshape(-1)
    assert rows.shape == (32, 32) and keys.shape == (32, 2)
    # 1,000 rows fill 31 steps and 8 rows of the 32nd: the last 24 slots
    # repeat the permutation from its start
    assert np.array_equal(np.sort(order[:1000]), np.arange(1000))
    assert np.array_equal(order[1000:], order[:24])
    # a multiple of the batch: every row exactly once
    rows, _ = dl._chunk_rows(jax.random.PRNGKey(4), 1024, 32, 32)
    assert np.array_equal(np.sort(np.asarray(rows).reshape(-1)),
                          np.arange(1024))


def test_the_fit_records_one_epoch_of_chunks(cloud1, bench):
    est, _ = _fit(bench)
    rec = est.model.rng_record
    assert [(int(it0), n) for _, it0, n in rec["chunks"]] == [(0, 125)]
    dataset_cache.clear()
