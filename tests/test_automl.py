"""XGBoost / Grid / StackedEnsemble / AutoML tests — the `testdir_algos`
+ automl suites analog."""

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator
from h2o3_tpu.models.drf import H2ORandomForestEstimator
from h2o3_tpu.models.xgboost import H2OXGBoostEstimator
from h2o3_tpu.models.grid import H2OGridSearch
from h2o3_tpu.models.ensemble import H2OStackedEnsembleEstimator
from h2o3_tpu.automl import H2OAutoML

from conftest import make_classification


def _cls_frame(n=1200, f=6, seed=0):
    X, y = make_classification(n, f, seed)
    return Frame.from_numpy(
        np.column_stack([X, y]), names=[f"x{i}" for i in range(f)] + ["y"]
    ).asfactor("y")


def test_xgboost_binomial(cloud1):
    fr = _cls_frame(2000, 8, seed=1)
    xgb = H2OXGBoostEstimator(ntrees=30, max_depth=5, eta=0.3, reg_lambda=1.0,
                              subsample=0.8, colsample_bytree=0.8, seed=2)
    xgb.train(y="y", training_frame=fr)
    assert xgb.auc() > 0.9
    p = xgb.predict(fr).vec("1").numeric_np()
    assert ((p >= 0) & (p <= 1)).all()


def test_xgboost_lambdarank_ndcg(cloud1):
    rng = np.random.default_rng(3)
    nq, per_q = 60, 20
    rows = nq * per_q
    X = rng.normal(size=(rows, 5))
    # relevance driven by two features
    rel_score = X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.normal(size=rows)
    rel = np.digitize(rel_score, np.quantile(rel_score, [0.5, 0.75, 0.9])).astype(float)
    qid = np.repeat(np.arange(nq), per_q)
    fr = Frame.from_dict({
        **{f"f{i}": X[:, i] for i in range(5)},
        "qid": qid.astype(float), "rel": rel,
    })
    xgb = H2OXGBoostEstimator(ntrees=20, max_depth=4, eta=0.3, seed=4,
                              objective="rank:ndcg", group_column="qid")
    xgb.train(y="rel", training_frame=fr,
              x=[f"f{i}" for i in range(5)])
    ndcg = xgb.ndcg(fr)
    # random ordering gives much lower ndcg; learned model should be high
    assert ndcg > 0.8
    # the fit reports it as a number too, beside the description's string
    reported = xgb.model.training_metrics.ndcg
    assert isinstance(reported, float) and reported == xgb.ndcg()
    assert reported == pytest.approx(ndcg, abs=1e-6)


def test_grid_search_cartesian(cloud1):
    fr = _cls_frame(900, 5, seed=5)
    grid = H2OGridSearch(
        H2OGradientBoostingEstimator,
        hyper_params={"max_depth": [2, 4], "learn_rate": [0.1, 0.3]},
    )
    grid.train(y="y", training_frame=fr, x=[f"x{i}" for i in range(5)])
    assert len(grid) == 4
    grid.get_grid(sort_by="auc", decreasing=True)
    aucs = [m.auc() for m in grid]
    assert aucs == sorted(aucs, reverse=True)


def test_grid_search_random_discrete(cloud1):
    fr = _cls_frame(800, 5, seed=6)
    grid = H2OGridSearch(
        H2OGradientBoostingEstimator(ntrees=5, seed=1),
        hyper_params={"max_depth": [2, 3, 4, 5], "learn_rate": [0.05, 0.1, 0.2, 0.3]},
        search_criteria={"strategy": "RandomDiscrete", "max_models": 5, "seed": 7},
    )
    grid.train(y="y", training_frame=fr)
    assert len(grid) == 5


def test_stacked_ensemble(cloud1):
    fr = _cls_frame(900, 5, seed=8)
    common = dict(nfolds=2, keep_cross_validation_predictions=True, seed=9)
    gbm = H2OGradientBoostingEstimator(ntrees=15, max_depth=3, **common)
    gbm.train(y="y", training_frame=fr)
    drf = H2ORandomForestEstimator(ntrees=15, max_depth=8, **common)
    drf.train(y="y", training_frame=fr)
    glm = H2OGeneralizedLinearEstimator(family="binomial", lambda_=0.0, **common)
    glm.train(y="y", training_frame=fr)
    se = H2OStackedEnsembleEstimator(base_models=[gbm, drf, glm])
    se.train(y="y", training_frame=fr)
    best_base = max(gbm.auc(xval=True), drf.auc(xval=True), glm.auc(xval=True))
    assert se.auc() > best_base - 0.03  # ensemble ≥ roughly best base
    pred = se.predict(fr)
    assert pred.names == ["predict", "0", "1"]


def test_automl_leaderboard(cloud1):
    fr = _cls_frame(500, 4, seed=10)
    aml = H2OAutoML(max_models=4, max_runtime_secs=600, seed=11, nfolds=2,
                    exclude_algos=["DeepLearning"])
    aml.train(y="y", training_frame=fr)
    lb = aml.leaderboard
    assert len(lb) >= 4  # 4 base + ensembles
    assert aml.leader is not None
    # leaderboard sorted by AUC desc
    aucs = [r["auc"] for r in lb.rows if not np.isnan(r["auc"])]
    assert aucs == sorted(aucs, reverse=True)
    pred = aml.predict(fr)
    assert pred.nrow == fr.nrow
    algos = {r["algo"] for r in lb.rows}
    assert "stackedensemble" in algos


def test_xgboost_reg_alpha_shrinks_leaves(cloud1):
    fr = _cls_frame(1000, 5, seed=12)
    plain = H2OXGBoostEstimator(ntrees=5, max_depth=3, eta=0.3, seed=13)
    plain.train(y="y", training_frame=fr)
    strong = H2OXGBoostEstimator(ntrees=5, max_depth=3, eta=0.3, seed=13,
                                 reg_alpha=50.0)
    strong.train(y="y", training_frame=fr)
    v0 = float(np.abs(np.asarray(plain.model.forest[0].value)).sum())
    v1 = float(np.abs(np.asarray(strong.model.forest[0].value)).sum())
    assert v1 < v0  # L1 soft-threshold shrinks leaf outputs


def test_leaderboard_frame_and_best_model(cloud1):
    import numpy as np
    from h2o3_tpu.automl.automl import H2OAutoML
    from h2o3_tpu.frame.frame import Frame

    rng = np.random.default_rng(0)
    n = 600
    X = rng.normal(size=(n, 4))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    fr = Frame.from_numpy(np.column_stack([X, y]),
                          names=["a", "b", "c", "d", "y"]).asfactor("y")
    aml = H2OAutoML(max_models=2, max_runtime_secs=120, nfolds=2, seed=1,
                    include_algos=["GBM", "GLM"])
    aml.train(y="y", training_frame=fr)
    lb = aml.leaderboard.as_frame()
    assert lb.nrow >= 2 and "auc" in lb.names
    best_glm = aml.get_best_model(algorithm="glm")
    assert best_glm is not None and best_glm.algo == "glm"
    assert aml.get_best_model() is aml.leaderboard[0]["_est"]


def test_se_level_one_cache_invalidation(cloud1):
    """The SE level-one cache must refresh when the frame mutates in
    place (keyed on the frame version counter)."""
    import numpy as np

    import h2o3_tpu as h2o
    from h2o3_tpu.models.ensemble import H2OStackedEnsembleEstimator
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    rng = np.random.default_rng(4)
    X = rng.normal(size=(600, 4))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    fr = h2o.H2OFrame_from_python(
        {**{f"c{i}": X[:, i] for i in range(4)}, "y": y.astype(str)},
        column_types={"y": "enum"})
    bases = []
    for depth in (2, 3):
        g = H2OGradientBoostingEstimator(
            ntrees=5, max_depth=depth, nfolds=2,
            keep_cross_validation_predictions=True, seed=1)
        g.train(x=[f"c{i}" for i in range(4)], y="y", training_frame=fr)
        bases.append(g)
    se = H2OStackedEnsembleEstimator(base_models=bases)
    se.train(x=[f"c{i}" for i in range(4)], y="y", training_frame=fr)
    p1 = se.predict(fr).as_data_frame()["1"].to_numpy()
    p1b = se.predict(fr).as_data_frame()["1"].to_numpy()  # cache hit
    np.testing.assert_array_equal(p1, p1b)
    fr["c0"] = np.zeros(600)  # in-place mutation bumps the version
    p2 = se.predict(fr).as_data_frame()["1"].to_numpy()
    assert not np.allclose(p1, p2)  # stale cache would return p1
