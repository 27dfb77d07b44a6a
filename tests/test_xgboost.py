"""XGBoost estimator surface: lossguide growth, parameter honesty, leaf caps.

Reference behaviors: `h2o-ext-xgboost/.../XGBoostModel.java` createParamsMap
(grow_policy / max_leaves / booster passthrough to the native booster);
xgboost's `hist` updater semantics.
"""

import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
from h2o3_tpu.models.xgboost import H2OXGBoostEstimator


def _frame(n=4000, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=n)) > 0)
    d = {f"f{i}": X[:, i] for i in range(f)}
    d["y"] = y.astype(int).astype(str)
    fr = h2o.H2OFrame_from_python(d, column_types={"y": "enum"})
    return fr, [f"f{i}" for i in range(f)]


def _leaf_counts(model):
    """Leaves per tree from the heap arrays: #splits + 1."""
    out = []
    for k_forest in model.forest:
        for t in range(k_forest.is_split.shape[0]):
            out.append(int(np.asarray(k_forest.is_split[t]).sum()) + 1)
    return out


def test_lossguide_leaf_cap_honored():
    fr, x = _frame()
    xgb = H2OXGBoostEstimator(ntrees=8, max_depth=6, seed=1,
                              grow_policy="lossguide", max_leaves=8)
    xgb.train(x=x, y="y", training_frame=fr)
    leaves = _leaf_counts(xgb.model)
    assert max(leaves) <= 8, leaves
    assert max(leaves) > 2, "trees did not grow at all"
    assert float(xgb.auc()) > 0.85


def test_lossguide_depth_cap_binds():
    fr, x = _frame()
    xgb = H2OXGBoostEstimator(ntrees=5, max_depth=2, seed=1,
                              grow_policy="lossguide", max_leaves=64)
    xgb.train(x=x, y="y", training_frame=fr)
    # depth 2 heap can hold at most 4 leaves regardless of the leaf budget
    assert max(_leaf_counts(xgb.model)) <= 4


def test_lossguide_matches_depthwise_when_unconstrained():
    # with a leaf budget >= 2^depth every positive-gain node splits in both
    # policies; split decisions are local, so the models score identically
    fr, x = _frame(n=2000)
    kw = dict(ntrees=4, max_depth=3, seed=7, min_rows=10)
    a = H2OXGBoostEstimator(**kw)
    a.train(x=x, y="y", training_frame=fr)
    b = H2OXGBoostEstimator(grow_policy="lossguide", max_leaves=8, **kw)
    b.train(x=x, y="y", training_frame=fr)
    pa = a.predict(fr).vec("1").numeric_np()
    pb = b.predict(fr).vec("1").numeric_np()
    np.testing.assert_allclose(pa, pb, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("params", [
    dict(rate_drop=0.1),                       # DART param without dart
    dict(one_drop=True),
    dict(skip_drop=0.5),
    dict(booster="dart", rate_drop=1.5),       # out of range
    dict(booster="gbforest"),                  # unknown booster
    dict(booster="dart", normalize_type="bogus"),
    dict(grow_policy="bogus"),
    dict(max_leaves=16),                       # needs lossguide
    dict(grow_policy="lossguide", max_depth=0),
    dict(grow_policy="lossguide", max_leaves=1),
])
def test_unimplemented_params_raise(params):
    fr, x = _frame(n=500)
    est = H2OXGBoostEstimator(ntrees=2, **params)
    with pytest.raises(ValueError):
        est.train(x=x, y="y", training_frame=fr)


# ---- DART booster (xgboost dart.cc; h2o-ext-xgboost passthrough) --------


def test_dart_skip_drop_one_equals_gbtree():
    """skip_drop=1.0 means dropout never fires — DART must be bit-equal to
    gbtree (all round scales stay 1)."""
    fr, x = _frame(n=2000)
    kw = dict(ntrees=6, max_depth=3, seed=5)
    a = H2OXGBoostEstimator(**kw)
    a.train(x=x, y="y", training_frame=fr)
    b = H2OXGBoostEstimator(booster="dart", rate_drop=0.5, skip_drop=1.0,
                            **kw)
    b.train(x=x, y="y", training_frame=fr)
    pa = a.predict(fr).vec("1").numeric_np()
    pb = b.predict(fr).vec("1").numeric_np()
    np.testing.assert_allclose(pb, pa, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("normalize_type", ["tree", "forest"])
def test_dart_trains_and_scores_sane(normalize_type):
    fr, x = _frame(n=3000)
    est = H2OXGBoostEstimator(booster="dart", rate_drop=0.3, one_drop=True,
                              normalize_type=normalize_type,
                              ntrees=12, max_depth=3, seed=11)
    est.train(x=x, y="y", training_frame=fr)
    assert est.auc() > 0.8
    # margins maintained incrementally through drop/commit cycles must
    # agree with the final baked forest rescored from scratch (f32 drift
    # from per-round scale adjustments allows a few near-tie rank flips)
    auc_rescore = est.model_performance(fr).auc()
    assert abs(est.auc() - auc_rescore) < 1e-3
    # determinism: same seed, same dropout path, same model
    est2 = H2OXGBoostEstimator(booster="dart", rate_drop=0.3, one_drop=True,
                               normalize_type=normalize_type,
                               ntrees=12, max_depth=3, seed=11)
    est2.train(x=x, y="y", training_frame=fr)
    p1 = est.predict(fr).vec("1").numeric_np()
    p2 = est2.predict(fr).vec("1").numeric_np()
    np.testing.assert_array_equal(p1, p2)


def test_dart_normalization_math_exact():
    """rate_drop=1 with 2 trees: round 2 always drops round 1, so (with no
    row/col sampling) both trees learn the SAME f0-residual tree c. 'tree'
    normalization must yield margin = f0 + c/(1+lr) + c/(1+lr)."""
    fr, x = _frame(n=1500)
    lr = 0.3
    g = H2OXGBoostEstimator(ntrees=1, max_depth=3, seed=2, learn_rate=lr)
    g.train(x=x, y="y", training_frame=fr)
    d = H2OXGBoostEstimator(booster="dart", rate_drop=1.0, skip_drop=0.0,
                            ntrees=2, max_depth=3, seed=2, learn_rate=lr)
    d.train(x=x, y="y", training_frame=fr)
    Xm = g.model._matrix(fr)
    c = g.model._margins(Xm)[:, 0] - float(g.model.f0)   # lr-folded tree
    md = d.model._margins(Xm)[:, 0] - float(d.model.f0)
    np.testing.assert_allclose(md, 2.0 * c / (1.0 + lr), rtol=2e-5,
                               atol=2e-6)


def test_dart_with_validation_frame_consistent():
    """DART's validation margins go through drop/commit adjustments; the
    scoring-history valid metric must match a from-scratch rescore."""
    fr, x = _frame(n=3000)
    tr, va = fr.split_frame([0.7], seed=1)
    est = H2OXGBoostEstimator(booster="dart", rate_drop=0.4, one_drop=True,
                              ntrees=10, max_depth=3, seed=3,
                              score_tree_interval=5)
    est.train(x=x, y="y", training_frame=tr, validation_frame=va)
    va_auc_hist = est.model._m(valid=True).auc()
    va_auc_rescore = est.model_performance(va).auc()
    # same f32-drift allowance as the train-side test: AUC is rank-based,
    # so per-round adjustment rounding can flip a few near-ties
    assert abs(va_auc_hist - va_auc_rescore) < 1e-3


def test_max_abs_leafnode_pred_clamps_gbm():
    fr, x = _frame(n=2000)
    cap, lr = 0.02, 0.1
    gbm = H2OGradientBoostingEstimator(ntrees=5, max_depth=4, seed=3,
                                       learn_rate=lr,
                                       max_abs_leafnode_pred=cap)
    gbm.train(x=x, y="y", training_frame=fr)
    for k_forest in gbm.model.forest:
        vals = np.asarray(k_forest.value)
        assert np.abs(vals).max() <= cap * lr * (1 + 1e-5)


def test_max_delta_step_clamps_xgb():
    fr, x = _frame(n=2000)
    xgb = H2OXGBoostEstimator(ntrees=5, max_depth=4, seed=3, learn_rate=0.3,
                              max_delta_step=0.05)
    xgb.train(x=x, y="y", training_frame=fr)
    for k_forest in xgb.model.forest:
        vals = np.asarray(k_forest.value)
        assert np.abs(vals).max() <= 0.05 * 0.3 * (1 + 1e-5)


# ---- gblinear booster (updater_shotgun.cc CoordinateDelta)


def test_gblinear_gaussian_matches_glm():
    """With no regularization a converged gblinear IS the least-squares
    GLM — coefficient-level parity on a linear problem."""
    from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator

    rng = np.random.default_rng(1)
    n = 3000
    X = rng.normal(size=(n, 4)).astype(np.float32)
    beta_true = np.asarray([2.0, -1.0, 0.5, 0.0])
    yv = X @ beta_true + 1.5 + 0.05 * rng.normal(size=n)
    d = {f"f{i}": X[:, i] for i in range(4)}
    d["y"] = yv
    fr = h2o.H2OFrame_from_python(d)
    x = [f"f{i}" for i in range(4)]

    xgb = H2OXGBoostEstimator(booster="gblinear", ntrees=300, learn_rate=0.5,
                              reg_lambda=0.0, reg_alpha=0.0, seed=1)
    xgb.train(x=x, y="y", training_frame=fr)
    glm = H2OGeneralizedLinearEstimator(family="gaussian", lambda_=0.0,
                                        standardize=False)
    glm.train(x=x, y="y", training_frame=fr)
    cx, cg = xgb.model.coef(), glm.model.coef()
    for k in cg:
        assert abs(cx[k] - cg[k]) < 2e-2, (k, cx[k], cg[k])
    # and both recover the generating coefficients
    assert abs(cx["f0"] - 2.0) < 0.05 and abs(cx["Intercept"] - 1.5) < 0.05


def test_gblinear_binomial_trains_and_scores():
    fr, x = _frame(n=3000)
    xgb = H2OXGBoostEstimator(booster="gblinear", ntrees=100, learn_rate=0.5,
                              reg_lambda=1.0, seed=1)
    xgb.train(x=x, y="y", training_frame=fr)
    assert float(xgb.auc()) > 0.80          # x0 + x1*x2: linear part learnable
    pred = xgb.predict(fr)
    assert pred.names == ["predict", "0", "1"]
    p1 = pred.vec("1").numeric_np()
    assert np.isfinite(p1).all() and 0 <= p1.min() and p1.max() <= 1


def test_gblinear_reg_alpha_sparsifies():
    """L1 soft-thresholding: noise features' weights are driven to
    (near-)zero while the signal survives — the CoordinateDelta clamp."""
    rng = np.random.default_rng(3)
    n = 4000
    X = rng.normal(size=(n, 6)).astype(np.float32)
    yv = 3.0 * X[:, 0] + 0.02 * rng.normal(size=n)
    d = {f"f{i}": X[:, i] for i in range(6)}
    d["y"] = yv
    fr = h2o.H2OFrame_from_python(d)
    x = [f"f{i}" for i in range(6)]
    xgb = H2OXGBoostEstimator(booster="gblinear", ntrees=200, learn_rate=0.5,
                              reg_lambda=0.0, reg_alpha=200.0, seed=1)
    xgb.train(x=x, y="y", training_frame=fr)
    c = xgb.model.coef()
    assert abs(c["f0"]) > 1.0               # signal survives
    for k in ("f1", "f2", "f3", "f4", "f5"):
        assert abs(c[k]) < 5e-3, (k, c[k])  # noise soft-thresholded away


def test_gblinear_multinomial():
    rng = np.random.default_rng(5)
    n = 3000
    X = rng.normal(size=(n, 4)).astype(np.float32)
    cls = (X[:, 0] > 0.5).astype(int) + (X[:, 1] > 0).astype(int)
    d = {f"f{i}": X[:, i] for i in range(4)}
    d["y"] = np.asarray(["a", "b", "c"], dtype=object)[cls]
    fr = h2o.H2OFrame_from_python(d, column_types={"y": "enum"})
    xgb = H2OXGBoostEstimator(booster="gblinear", ntrees=150, learn_rate=0.5,
                              reg_lambda=1.0, seed=1)
    xgb.train(x=[f"f{i}" for i in range(4)], y="y", training_frame=fr)
    pred = xgb.predict(fr)
    assert pred.names == ["predict", "a", "b", "c"]
    acc = (np.asarray(pred.vec("predict").data)
           == np.asarray(fr.vec("y").data)).mean()
    assert acc > 0.75, acc


def test_gblinear_rejects_dart_params():
    fr, x = _frame(n=300)
    est = H2OXGBoostEstimator(booster="gblinear", rate_drop=0.3, ntrees=2)
    with pytest.raises(ValueError):
        est.train(x=x, y="y", training_frame=fr)


def test_gblinear_cv_and_identity():
    """nfolds CV works on the linear booster, and the model carries the
    xgboost identity (id prefix + summary algo), not glm."""
    fr, x = _frame(n=1500)
    xgb = H2OXGBoostEstimator(booster="gblinear", ntrees=60, learn_rate=0.5,
                              nfolds=3, seed=1)
    xgb.train(x=x, y="y", training_frame=fr)
    assert xgb.model.model_id.startswith("xgboost")
    assert xgb.model.summary()["algo"] == "xgboost"
    assert float(xgb.auc()) > 0.8
    assert xgb.model.cross_validation_metrics is not None


def test_gblinear_rejects_rank_and_exotic_distributions():
    fr, x = _frame(n=300)
    with pytest.raises(ValueError):
        H2OXGBoostEstimator(booster="gblinear", objective="rank:ndcg",
                            group_column="qid", ntrees=2).train(
            x=x, y="y", training_frame=fr)
    with pytest.raises(ValueError):
        H2OXGBoostEstimator(booster="gblinear", distribution="poisson",
                            ntrees=2).train(x=x, y="y", training_frame=fr)
