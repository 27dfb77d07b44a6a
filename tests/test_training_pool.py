"""Parallel multi-model training engine (ISSUE 4): dataset-artifact cache
hit/miss/eviction, CV fold reuse vs the H2O3_CV_REBIN=1 seed path,
parallel-grid leaderboard determinism, per-job error isolation, and the
`GET /3/Training/metrics` REST surface."""

import time

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import dataset_cache
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
from h2o3_tpu.models.grid import H2OGridSearch
from h2o3_tpu.runtime import trainpool

from conftest import make_classification


def _cls_frame(n=900, f=5, seed=0):
    X, y = make_classification(n, f, seed)
    return Frame.from_numpy(
        np.column_stack([X, y]), names=[f"x{i}" for i in range(f)] + ["y"]
    ).asfactor("y")


@pytest.fixture(autouse=True)
def _fresh_stats():
    dataset_cache.clear()
    dataset_cache.reset_stats()
    trainpool.reset()
    yield
    dataset_cache.clear()


# -- dataset-artifact cache ---------------------------------------------------
def test_dataset_cache_hit_miss_and_reuse_across_candidates(cloud1):
    fr = _cls_frame(600, 4, seed=1)
    for _ in range(3):   # three candidates sharing (frame, x, nbins, hist)
        m = H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=5)
        m.train(y="y", training_frame=fr)
    s = dataset_cache.snapshot()
    assert s["matrix_misses"] == 1 and s["matrix_hits"] == 2
    assert s["bins_misses"] == 1 and s["bins_hits"] == 2
    assert s["device_misses"] == 1 and s["device_hits"] == 2
    assert s["entries"] == 1 and s["bytes"] > 0


def test_dataset_cache_key_isolation_and_mutation_invalidates(cloud1):
    fr = _cls_frame(500, 4, seed=2)
    H2OGradientBoostingEstimator(ntrees=2, max_depth=2, seed=1) \
        .train(y="y", training_frame=fr)
    # different nbins → new bins layer, same matrix layer
    H2OGradientBoostingEstimator(ntrees=2, max_depth=2, nbins=12, seed=1) \
        .train(y="y", training_frame=fr)
    s = dataset_cache.snapshot()
    assert s["matrix_hits"] == 1 and s["bins_misses"] == 2
    # in-place mutation bumps Frame._version → full re-fingerprint
    fr["x0"] = fr.vec("x0").numeric_np() * 2.0
    H2OGradientBoostingEstimator(ntrees=2, max_depth=2, seed=1) \
        .train(y="y", training_frame=fr)
    assert dataset_cache.snapshot()["matrix_misses"] == 2


def test_dataset_cache_eviction_and_disable(cloud1, monkeypatch):
    monkeypatch.setenv("H2O3_DATASET_CACHE_ENTRIES", "1")
    frames = [_cls_frame(400, 4, seed=s) for s in (3, 4)]
    for fr in frames:
        H2OGradientBoostingEstimator(ntrees=2, max_depth=2, seed=1) \
            .train(y="y", training_frame=fr)
    s = dataset_cache.snapshot()
    assert s["evictions"] >= 1 and s["entries"] == 1
    monkeypatch.setenv("H2O3_DATASET_CACHE", "0")
    assert dataset_cache.enabled() is False
    dataset_cache.reset_stats()
    H2OGradientBoostingEstimator(ntrees=2, max_depth=2, seed=1) \
        .train(y="y", training_frame=frames[0])
    s = dataset_cache.snapshot()   # disabled: no layer is consulted
    assert s["matrix_hits"] == s["matrix_misses"] == 0


# -- the targets layer: response, weights and initial margin -----------------
_X4 = ["x0", "x1", "x2", "x3"]


def _targets_frame(n=600, seed=21):
    """Four features and one column for each role the targets layer keys:
    binary, three-class and real responses, two weight columns, an
    offset."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 4))
    z = X[:, 0] + 0.5 * X[:, 1]
    cols = [X, (z > 0.3).astype(float), np.digitize(z, [-0.8, 0.4]),
            z + 0.1 * rng.normal(size=n), rng.uniform(0.5, 2.0, n),
            rng.uniform(0.2, 3.0, n), 0.1 * rng.normal(size=n)]
    return Frame.from_numpy(
        np.column_stack(cols),
        names=_X4 + ["yb", "ym", "yr", "w", "w2", "off"]) \
        .asfactor("yb").asfactor("ym")


def _tree_fit(fr, y, drf=False, npad_floor=None, **kw):
    from h2o3_tpu.models.drf import H2ORandomForestEstimator

    cls = H2ORandomForestEstimator if drf else H2OGradientBoostingEstimator
    est = cls(ntrees=3, max_depth=3, seed=7, score_tree_interval=1, **kw)
    if npad_floor is not None:
        est._parms["_npad_floor"] = npad_floor
    est.train(x=_X4, y=y, training_frame=fr)
    return est.model


def _assert_same_fit(a, b):
    """Forest, f0, class-balancing priors, training metrics and every
    scoring-history loss, bit for bit."""
    from h2o3_tpu.models import tree as treelib
    from h2o3_tpu.models.metrics import MetricValue

    assert a.ntrees_built == b.ntrees_built
    for ta, tb in zip(a.forest, b.forest):
        for f in treelib.Tree._fields:
            np.testing.assert_array_equal(np.asarray(getattr(ta, f)),
                                          np.asarray(getattr(tb, f)), f)
    np.testing.assert_array_equal(np.asarray(a.f0), np.asarray(b.f0))
    assert (a.balance_dists is None) == (b.balance_dists is None)
    for pa, pb in zip(a.balance_dists or (), b.balance_dists or ()):
        np.testing.assert_array_equal(pa, pb)
    ma, mb = vars(a.training_metrics), vars(b.training_metrics)
    named = [k for k, v in ma.items() if isinstance(v, MetricValue)]
    assert named
    for k in named:
        np.testing.assert_array_equal(float(ma[k]), float(mb[k]), k)
    assert len(a.scoring_history) == len(b.scoring_history) > 0
    for ea, eb in zip(a.scoring_history, b.scoring_history):
        np.testing.assert_equal(   # NaN equals NaN here, as it should
            {k: v for k, v in ea.items() if k != "timestamp"},
            {k: v for k, v in eb.items() if k != "timestamp"})


def test_targets_layer_built_once_across_candidates(cloud1):
    fr = _targets_frame(seed=31)
    for lr, min_rows in ((0.1, 10), (0.2, 5), (0.3, 1)):
        _tree_fit(fr, "yb", learn_rate=lr, min_rows=min_rows)
    s = dataset_cache.snapshot()
    assert s["targets_misses"] == 1 and s["targets_hits"] == 2
    assert s["device_misses"] == 1 and s["device_hits"] == 2


@pytest.mark.parametrize("y,kw", [
    ("yb", {}),
    ("yr", {"weights_column": "w"}),
    ("ym", {}),
    ("yb", {"drf": True}),
    ("ym", {"balance_classes": True}),
    ("yr", {"offset_column": "off"}),
], ids=["gbm_bernoulli", "gbm_gaussian_weights", "gbm_multinomial", "drf",
        "balance_classes", "offset"])
def test_targets_hit_is_bit_identical_to_uncached(cloud1, monkeypatch, y,
                                                  kw):
    """A fit that takes its targets from the cache trains exactly what a
    fit with the dataset cache off trains."""
    fr = _targets_frame(seed=32)
    _tree_fit(fr, y, **kw)          # builds the entry
    hit = _tree_fit(fr, y, **kw)
    s = dataset_cache.snapshot()
    assert s["targets_misses"] == 1 and s["targets_hits"] == 1
    monkeypatch.setenv("H2O3_DATASET_CACHE", "0")
    _assert_same_fit(hit, _tree_fit(fr, y, **kw))
    assert dataset_cache.snapshot()["targets_misses"] == 1


def _swap_response(fr):
    # a new Vec under the same name, the frame's version left alone: only
    # the response column's identity can tell the fits apart
    from h2o3_tpu.frame.vec import Vec

    fr._vecs["yr"] = Vec.from_numpy(fr.vec("yr").numeric_np() * 3.0 + 1.0)


@pytest.mark.parametrize("before,after,mutate", [
    ({}, {}, _swap_response),
    ({}, {}, Frame._touch),
    ({"weights_column": "w"}, {"weights_column": "w2"}, None),
    ({}, {"distribution": "laplace"}, None),
    ({}, {"drf": True}, None),
    ({}, {"npad_floor": 4096}, None),
], ids=["response_swapped", "frame_touched", "weights_swapped",
        "distribution", "drf_after_gbm", "npad_floor"])
def test_targets_key_parts_invalidate(cloud1, monkeypatch, before, after,
                                      mutate):
    """Each part of the key on its own forces a fresh build, and the fit
    after it trains what an uncached fit of the same change trains."""
    fr = _targets_frame(seed=33)
    _tree_fit(fr, "yr", **before)
    if mutate is not None:
        mutate(fr)
    changed = _tree_fit(fr, "yr", **after)
    s = dataset_cache.snapshot()
    assert s["targets_misses"] == 2 and s["targets_hits"] == 0
    monkeypatch.setenv("H2O3_DATASET_CACHE", "0")
    _assert_same_fit(changed, _tree_fit(fr, "yr", **after))


def test_targets_layer_skipped_by_cv_folds_and_ooc(cloud1, monkeypatch):
    fr = _targets_frame(seed=34)
    H2OGradientBoostingEstimator(ntrees=2, max_depth=3, seed=7, nfolds=3) \
        .train(x=_X4, y="yb", training_frame=fr)
    assert trainpool.snapshot()["cv"]["reuse_folds"] == 3
    s = dataset_cache.snapshot()   # the main fit alone: fold fits skip it
    assert s["targets_misses"] == 1 and s["targets_hits"] == 0
    dataset_cache.clear()
    dataset_cache.reset_stats()
    monkeypatch.setenv("H2O3_TREE_OOC", "1")
    for _ in range(2):
        _tree_fit(fr, "yb")
    s = dataset_cache.snapshot()
    assert s["blocks_misses"] == 1 and s["blocks_hits"] == 1
    assert s["targets_misses"] == 0 and s["targets_hits"] == 0


# -- CV fold reuse -------------------------------------------------------------
def test_cv_reuse_metric_parity_with_rebin(cloud1, monkeypatch):
    """Fold reuse slices the parent's binned codes (fold-local bin edges
    differ from the seed per-fold re-bin) — the xval metrics must agree
    within a pinned tolerance, and H2O3_CV_REBIN=1 must actually flip the
    path (trainpool fold counters prove which ran)."""
    fr = _cls_frame(1000, 5, seed=6)

    def run():
        g = H2OGradientBoostingEstimator(ntrees=10, max_depth=3, nfolds=3,
                                         seed=11)
        g.train(y="y", training_frame=fr)
        return g

    reuse = run()
    assert trainpool.snapshot()["cv"] == dict(reuse_folds=3, rebin_folds=0)
    trainpool.reset()
    monkeypatch.setenv("H2O3_CV_REBIN", "1")
    rebin = run()
    assert trainpool.snapshot()["cv"] == dict(reuse_folds=0, rebin_folds=3)
    for metric in ("auc", "logloss"):
        a = float(getattr(reuse, metric)(xval=True))
        b = float(getattr(rebin, metric)(xval=True))
        assert abs(a - b) < 0.03, (metric, a, b)
    # holdout prediction vectors stay close row-by-row, not just on average
    d = np.abs(reuse.model._cv_holdout_pred - rebin.model._cv_holdout_pred)
    assert float(np.mean(d)) < 0.05


def test_cv_rebin_is_deterministic_seed_path(cloud1, monkeypatch):
    """parallelism=1 + H2O3_CV_REBIN=1 is the bit-exact seed path: two runs
    (one with the artifact cache live, one with it off) agree exactly."""
    fr = _cls_frame(700, 4, seed=7)
    monkeypatch.setenv("H2O3_CV_REBIN", "1")

    def run():
        g = H2OGradientBoostingEstimator(ntrees=6, max_depth=3, nfolds=3,
                                         seed=3)
        g.train(y="y", training_frame=fr)
        return g

    a = run()
    monkeypatch.setenv("H2O3_DATASET_CACHE", "0")
    b = run()
    assert float(a.auc(xval=True)) == float(b.auc(xval=True))
    np.testing.assert_array_equal(a.model._cv_holdout_pred,
                                  b.model._cv_holdout_pred)


def test_cv_reuse_respects_fold_column_and_weights(cloud1):
    """Reuse keeps *_column parameters working: the slim fold frame carries
    the weights column, and fold_column-driven CV reuses codes too."""
    X, y = make_classification(800, 4, seed=9)
    w = np.where(y == 1, 2.0, 1.0)
    foldc = np.arange(800) % 3
    fr = Frame.from_numpy(
        np.column_stack([X, y, w, foldc]),
        names=["a", "b", "c", "d", "y", "w", "fold"]).asfactor("y")
    g = H2OGradientBoostingEstimator(ntrees=5, max_depth=3, seed=2,
                                     weights_column="w", fold_column="fold")
    g.train(y="y", training_frame=fr, x=["a", "b", "c", "d"])
    assert g.model.cross_validation_metrics is not None
    assert trainpool.snapshot()["cv"]["reuse_folds"] == 3


# -- grid scheduler -------------------------------------------------------------
def _grid(fr, parallelism, **crit):
    g = H2OGridSearch(
        H2OGradientBoostingEstimator(ntrees=5, nfolds=2, seed=13),
        {"max_depth": [2, 3], "learn_rate": [0.1, 0.3]},
        parallelism=parallelism, search_criteria=crit or None)
    g.train(y="y", training_frame=fr)
    return g


def test_grid_parallel_leaderboard_identical_to_sequential(cloud1):
    fr = _cls_frame(700, 5, seed=21)
    seq = _grid(fr, 1).get_grid(sort_by="auc")
    par = _grid(fr, 4).get_grid(sort_by="auc")
    assert len(seq) == len(par) == 4
    lb_seq = [(m._grid_combo, float(m.auc(xval=True))) for m in seq.models]
    lb_par = [(m._grid_combo, float(m.auc(xval=True))) for m in par.models]
    assert lb_seq == lb_par   # same order AND bit-identical metrics
    assert trainpool.snapshot()["last_pool"]["parallelism"] == 4


def test_grid_per_job_error_isolation(cloud1):
    fr = _cls_frame(500, 4, seed=22)
    g = H2OGridSearch(
        H2OGradientBoostingEstimator(ntrees=4, seed=1),
        {"max_depth": [3, -1], "learn_rate": [0.2]},   # -1 → ValueError
        parallelism=2)
    g.train(y="y", training_frame=fr)
    assert len(g.models) == 1
    assert len(g.failed) == 1
    assert g.failed[0]["params"]["max_depth"] == -1
    assert "max_depth" in g.failed[0]["error"]


def test_grid_parent_job_cancel_skips_candidates(cloud1):
    from h2o3_tpu.models.model_base import Job

    fr = _cls_frame(500, 4, seed=23)
    g = H2OGridSearch(H2OGradientBoostingEstimator(ntrees=4, seed=1),
                      {"max_depth": [2, 3, 4]}, parallelism=1)
    job = Job(dest="grid_job", description="grid").start()
    job.cancel()
    g._external_job = job
    g.train(y="y", training_frame=fr)
    assert g.models == [] and g.failed == []
    snap = trainpool.snapshot()
    assert snap["totals"]["cancelled"] == 3


def test_trainpool_occupancy_and_error_records():
    def ok(job):
        time.sleep(0.01)
        return "fine"

    def boom(job):
        raise RuntimeError("candidate exploded")

    recs = trainpool.TrainPool(2, label="unit").run(
        [("a", ok), ("b", boom), ("c", ok)])
    assert [r.status for r in recs] == ["done", "failed", "done"]
    assert recs[1].error == "candidate exploded"
    snap = trainpool.snapshot()
    assert snap["totals"]["completed"] == 2
    assert snap["totals"]["failed"] == 1
    assert snap["last_pool"]["n_jobs"] == 3
    assert 0.0 < snap["last_pool"]["occupancy"] <= 1.0
    names = [c["name"] for c in snap["candidates"]]
    assert set(names) == {"a", "b", "c"}


def test_automl_parallel_smoke(cloud1):
    from h2o3_tpu.automl import H2OAutoML

    fr = _cls_frame(600, 4, seed=25)
    aml = H2OAutoML(max_models=2, seed=1, nfolds=2, parallelism=2,
                    include_algos=["GBM"])
    aml.train(y="y", training_frame=fr)
    assert len(aml._models) == 2
    assert aml.leader is not None


# -- REST surface ----------------------------------------------------------------
def test_training_metrics_rest_surface(cloud1):
    import json
    import urllib.request

    from h2o3_tpu.rest import start_server

    fr = _cls_frame(500, 4, seed=30)
    _grid(fr, 2)
    srv = start_server(port=0)
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}{path}") as r:
                return json.loads(r.read())

        body = get("/3/Training/metrics")
        assert body["__meta"]["schema_type"] == "TrainingMetricsV3"
        assert body["active"] is True
        assert body["totals"]["completed"] >= 4
        assert body["cache"]["bins_hits"] >= 1
        assert body["cv"]["reuse_folds"] >= 8
        assert body["last_pool"]["occupancy"] > 0
        assert body["candidates"] and "wall_s" in body["candidates"][0]
        schema = get("/3/Training/metrics?schema=1")
        assert schema["name"] == "TrainingMetricsV3"
        assert any(f["name"] == "cache" for f in schema["fields"])
        prof = get("/3/Profiler")
        assert "training" in prof and prof["training"]["active"] is True
    finally:
        srv.stop()
