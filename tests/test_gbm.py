"""GBM/DRF end-to-end — the `h2o-py/tests/testdir_algos/gbm` analog:
train on synthetic data, assert metric quality with tolerances."""

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
from h2o3_tpu.models.drf import H2ORandomForestEstimator

from conftest import make_classification, make_regression


def _cls_frame(n=2000, f=10, seed=0):
    X, y = make_classification(n, f, seed)
    fr = Frame.from_numpy(np.column_stack([X, y]),
                          names=[f"x{i}" for i in range(f)] + ["y"])
    return fr.asfactor("y")


def test_gbm_binomial_auc(cloud1):
    fr = _cls_frame()
    train, valid = fr.split_frame([0.8], seed=7)
    gbm = H2OGradientBoostingEstimator(ntrees=30, max_depth=4, learn_rate=0.2, seed=42)
    gbm.train(y="y", training_frame=train, validation_frame=valid)
    assert gbm.auc() > 0.90
    assert gbm.auc(valid=True) > 0.80
    assert gbm.logloss() < 0.45
    pred = gbm.predict(valid)
    assert pred.names == ["predict", "0", "1"]
    assert pred.nrow == valid.nrow
    p1 = pred.vec("1").numeric_np()
    assert ((p1 >= 0) & (p1 <= 1)).all()


def test_gbm_regression(cloud1):
    X, y = make_regression(1500, 6, seed=3)
    names = [f"x{i}" for i in range(6)] + ["y"]
    fr = Frame.from_numpy(np.column_stack([X, y]), names=names)
    gbm = H2OGradientBoostingEstimator(ntrees=40, max_depth=5, learn_rate=0.2, seed=1)
    gbm.train(y="y", training_frame=fr)
    base = float(np.var(y))
    assert gbm.mse() < 0.3 * base
    assert gbm.model.varimp_table is not None
    top = gbm.model.varimp_table[0][0]
    assert top in ("x0", "x1", "x2")


def test_gbm_multinomial(cloud1):
    rng = np.random.default_rng(5)
    n = 1800
    X = rng.normal(size=(n, 5))
    y = (X[:, 0] > 0.5).astype(int) + (X[:, 1] > 0).astype(int)  # 3 classes
    fr = Frame.from_numpy(np.column_stack([X, y]),
                          names=["a", "b", "c", "d", "e", "y"]).asfactor("y")
    gbm = H2OGradientBoostingEstimator(ntrees=25, max_depth=4, learn_rate=0.3, seed=2)
    gbm.train(y="y", training_frame=fr)
    m = gbm.model.training_metrics
    assert m.logloss < 0.4
    assert m.accuracy > 0.85
    pred = gbm.predict(fr)
    assert pred.ncol == 4  # predict + 3 class probs


def test_gbm_with_nas(cloud1):
    X, y = make_classification(1200, 6, seed=9)
    X[::5, 2] = np.nan
    fr = Frame.from_numpy(np.column_stack([X, y]),
                          names=[f"x{i}" for i in range(6)] + ["y"]).asfactor("y")
    gbm = H2OGradientBoostingEstimator(ntrees=20, max_depth=4, seed=3)
    gbm.train(y="y", training_frame=fr)
    assert gbm.auc() > 0.80
    pred = gbm.predict(fr)
    assert not np.isnan(pred.vec("1").numeric_np()).any()


def test_gbm_categorical_features(cloud1):
    rng = np.random.default_rng(11)
    n = 1500
    cat = rng.integers(0, 4, n)
    x1 = rng.normal(size=n)
    y = ((cat >= 2) ^ (x1 > 0)).astype(int)
    fr = Frame.from_dict({
        "cat": np.asarray(["lvl%d" % c for c in cat], dtype=object),
        "x1": x1,
        "y": y,
    }).asfactor("y")
    gbm = H2OGradientBoostingEstimator(ntrees=30, max_depth=4, learn_rate=0.3, seed=4)
    gbm.train(y="y", training_frame=fr)
    assert gbm.auc() > 0.95


def test_gbm_early_stopping(cloud1):
    # noisy response ⇒ validation logloss bottoms out and overfits back up;
    # ScoreKeeper watches the validation metric (hex.ScoreKeeper semantics)
    fr = _cls_frame(1500, 8, seed=13)
    train, valid = fr.split_frame([0.7], seed=13)
    gbm = H2OGradientBoostingEstimator(
        ntrees=500, max_depth=3, learn_rate=0.3, seed=5,
        stopping_rounds=3, stopping_tolerance=1e-3, score_tree_interval=5,
    )
    gbm.train(y="y", training_frame=train, validation_frame=valid)
    assert len(gbm.scoring_history) > 0
    assert gbm.model.forest[0].feat.shape[0] < 500  # stopped early
    assert "validation_logloss" in gbm.scoring_history[-1]


def test_gbm_weights_column(cloud1):
    X, y = make_classification(1000, 5, seed=17)
    w = np.where(y == 1, 2.0, 1.0)
    fr = Frame.from_numpy(np.column_stack([X, y, w]),
                          names=["a", "b", "c", "d", "e", "y", "w"]).asfactor("y")
    gbm = H2OGradientBoostingEstimator(ntrees=10, max_depth=3, weights_column="w", seed=6)
    gbm.train(y="y", training_frame=fr, x=["a", "b", "c", "d", "e"])
    assert gbm.auc() > 0.75


def test_gbm_distribution_poisson(cloud1):
    rng = np.random.default_rng(21)
    n = 1200
    X = rng.normal(size=(n, 4))
    lam = np.exp(0.5 * X[:, 0] - 0.3 * X[:, 1])
    y = rng.poisson(lam)
    fr = Frame.from_numpy(np.column_stack([X, y]), names=["a", "b", "c", "d", "y"])
    gbm = H2OGradientBoostingEstimator(ntrees=30, distribution="poisson", seed=7)
    gbm.train(y="y", training_frame=fr)
    pred = gbm.predict(fr).vec("predict").numeric_np()
    assert (pred >= 0).all()  # log link ⇒ positive means
    assert np.corrcoef(pred, lam)[0, 1] > 0.7


def test_drf_binomial(cloud1):
    fr = _cls_frame(2000, 8, seed=23)
    drf = H2ORandomForestEstimator(ntrees=30, max_depth=12, seed=8)
    drf.train(y="y", training_frame=fr)
    # training metrics are OOB (DRF semantics) — lower than in-bag
    assert drf.auc() > 0.74
    p = drf.predict(fr).vec("1").numeric_np()
    assert ((p >= 0) & (p <= 1)).all()


def test_drf_regression(cloud1):
    X, y = make_regression(1500, 6, seed=29)
    fr = Frame.from_numpy(np.column_stack([X, y]),
                          names=[f"x{i}" for i in range(6)] + ["y"])
    drf = H2ORandomForestEstimator(ntrees=40, max_depth=14, seed=9)
    drf.train(y="y", training_frame=fr)
    # OOB mse (honest estimate) — looser than the old in-bag bound
    assert drf.mse() < 0.8 * float(np.var(y))


def test_gbm_cv(cloud1):
    fr = _cls_frame(1200, 6, seed=31)
    gbm = H2OGradientBoostingEstimator(ntrees=15, max_depth=3, nfolds=3, seed=10,
                                       keep_cross_validation_predictions=True)
    gbm.train(y="y", training_frame=fr)
    assert gbm.model.cross_validation_metrics is not None
    assert gbm.auc(xval=True) > 0.75
    assert gbm.model._cv_holdout_pred is not None
    assert gbm.model._cv_holdout_pred.shape[0] == fr.nrow


def test_gbm_multichip_shard_map(cloud8):
    """The distributed path: rows sharded over 8 devices, histogram psum."""
    fr = _cls_frame(2048, 6, seed=37)
    gbm = H2OGradientBoostingEstimator(ntrees=8, max_depth=3, seed=11)
    gbm.train(y="y", training_frame=fr)
    auc8 = gbm.auc()
    assert auc8 > 0.85


def test_balance_classes_weights_minority(cloud1):
    import numpy as np
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    rng = np.random.default_rng(5)
    n = 2000
    X = rng.normal(size=(n, 3))
    # rare positive class (5%) driven by x0
    y = ((X[:, 0] > 1.6) | (rng.uniform(size=n) < 0.01)).astype(int)
    fr = Frame.from_dict({
        "a": X[:, 0], "b": X[:, 1], "c": X[:, 2],
        "y": np.asarray(["n", "p"], dtype=object)[y]}, column_types={"y": "enum"})
    m = H2OGradientBoostingEstimator(ntrees=10, max_depth=3,
                                     balance_classes=True, seed=1)
    m.train(x=["a", "b", "c"], y="y", training_frame=fr)
    # the priorClassDist correction keeps scored probabilities calibrated to
    # the ORIGINAL prior despite balanced training (hex.Model semantics)
    pm = m.predict(fr).vec("p").numeric_np().mean()
    prior = y.mean()
    assert abs(pm - prior) < 0.1
    assert m.auc() > 0.8


def test_monotone_constraints(cloud1):
    import numpy as np
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    rng = np.random.default_rng(7)
    n = 1500
    x = rng.uniform(-2, 2, n)
    z = rng.normal(size=n)
    # mostly increasing relationship with local noise dips
    y = x + 0.6 * np.sin(4 * x) + 0.3 * z
    fr = Frame.from_dict({"x": x, "z": z, "y": y})
    m = H2OGradientBoostingEstimator(ntrees=40, max_depth=4,
                                     monotone_constraints={"x": 1}, seed=1)
    m.train(x=["x", "z"], y="y", training_frame=fr)
    # predictions along x (z fixed) must be non-decreasing
    grid = Frame.from_dict({"x": np.linspace(-2, 2, 200),
                            "z": np.zeros(200)})
    p = m.predict(grid).vec("predict").numeric_np()
    # bound propagation guarantees ZERO violations (hex/tree Constraints)
    viol = np.diff(p) < -1e-5
    assert viol.sum() == 0, f"{viol.sum()} monotonicity violations"
    # unconstrained model does violate (the sin dips)
    m2 = H2OGradientBoostingEstimator(ntrees=40, max_depth=4, seed=1)
    m2.train(x=["x", "z"], y="y", training_frame=fr)
    p2 = m2.predict(grid).vec("predict").numeric_np()
    assert (np.diff(p2) < -1e-4).sum() > 0
    # categorical constraint is rejected
    fr2 = Frame.from_dict({"c": np.asarray(["a", "b"] * 50, dtype=object),
                           "y": rng.normal(size=100)},
                          column_types={"c": "enum"})
    with pytest.raises(ValueError):
        H2OGradientBoostingEstimator(ntrees=2, monotone_constraints={"c": 1}
                                     ).train(x=["c"], y="y", training_frame=fr2)


def test_calibrate_model_platt_and_isotonic(cloud1):
    rng = np.random.default_rng(31)
    n = 3000
    X = rng.normal(size=(n, 4))
    p_true = 1 / (1 + np.exp(-(1.5 * X[:, 0] - 0.5)))
    y = (rng.uniform(size=n) < p_true).astype(int)
    fr = Frame.from_numpy(np.column_stack([X, y]),
                          names=["a", "b", "c", "d", "y"]).asfactor("y")
    tr, cal = fr.split_frame([0.7], seed=1)
    for method in ("PlattScaling", "IsotonicRegression"):
        m = H2OGradientBoostingEstimator(
            ntrees=30, max_depth=5, learn_rate=0.3, seed=1,
            calibrate_model=True, calibration_frame=cal,
            calibration_method=method)
        m.train(y="y", training_frame=tr)
        pred = m.predict(cal)
        assert "cal_1" in pred.names and "cal_0" in pred.names
        raw = pred.vec("1").numeric_np()
        calp = pred.vec("cal_1").numeric_np()
        ycal = np.asarray(cal.vec("y").data, np.float64)
        # calibrated probabilities are no worse (usually better) in brier
        brier_raw = np.mean((raw - ycal) ** 2)
        brier_cal = np.mean((calp - ycal) ** 2)
        assert brier_cal <= brier_raw + 0.01, (method, brier_raw, brier_cal)
    with pytest.raises(ValueError):
        H2OGradientBoostingEstimator(ntrees=2, calibrate_model=True).train(
            y="y", training_frame=tr)


def test_drf_oob_training_metrics(cloud1):
    # OOB metrics are pessimistic vs in-bag: on noisy data the OOB AUC must
    # sit clearly below a deliberately-overfit forest's in-bag AUC
    rng = np.random.default_rng(41)
    n = 1500
    X = rng.normal(size=(n, 4))
    p = 1 / (1 + np.exp(-1.0 * X[:, 0]))
    y = (rng.uniform(size=n) < p).astype(int)
    fr = Frame.from_numpy(np.column_stack([X, y]),
                          names=["a", "b", "c", "d", "y"]).asfactor("y")
    drf = H2ORandomForestEstimator(ntrees=30, max_depth=12, seed=1)
    drf.train(y="y", training_frame=fr)
    oob_auc = drf.auc()
    # in-bag AUC computed via predict() on the training frame
    pr = drf.predict(fr).vec("1").numeric_np()
    from h2o3_tpu.models.metrics import auc_exact
    inbag_auc = auc_exact(y.astype(float), pr)
    assert oob_auc < inbag_auc - 0.02, (oob_auc, inbag_auc)
    # and OOB should approximate the true generalization (~AUC of p)
    true_auc = auc_exact(y.astype(float), p)
    # ~11 OOB trees per row at ntrees=30 → a noisy but unbiased-ish estimate
    assert abs(oob_auc - true_auc) < 0.12, (oob_auc, true_auc)


def test_sample_rate_per_class(cloud1):
    rng = np.random.default_rng(51)
    n = 2000
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] > 1.0).astype(int)  # ~16% minority
    fr = Frame.from_numpy(np.column_stack([X, y]),
                          names=["a", "b", "c", "y"]).asfactor("y")
    m = H2ORandomForestEstimator(ntrees=20, max_depth=6, seed=1,
                                 sample_rate_per_class=[0.3, 1.0])
    m.train(y="y", training_frame=fr)
    assert m.auc() > 0.8
    with pytest.raises(ValueError):
        H2ORandomForestEstimator(ntrees=2, sample_rate_per_class=[0.5]).train(
            y="y", training_frame=fr)


@pytest.mark.parametrize("scorer", ["fused", "walk"])
def test_predict_pages_equal_one_dispatch(cloud1, monkeypatch, scorer):
    """A frame past one scorer page is scored page by page through ONE
    compiled program (the fused walk's gather transients at 1M rows × 100
    trees do not fit a chip): the paged result equals the one-dispatch
    result bit for bit, multinomial forests included."""
    from h2o3_tpu.models import shared_tree

    monkeypatch.setenv("H2O3_FOREST_SCORER", scorer)
    rng = np.random.default_rng(5)
    n, f = 2300, 6
    X = rng.normal(size=(n, f))
    y = (X[:, 0] > 0.5).astype(int) + (X[:, 1] > 0).astype(int)
    fr = Frame.from_numpy(np.column_stack([X, y]),
                          names=[f"x{i}" for i in range(f)] + ["y"]
                          ).asfactor("y")
    gbm = H2OGradientBoostingEstimator(ntrees=5, max_depth=3, seed=1)
    gbm.train(y="y", training_frame=fr)
    whole = gbm.predict(fr)
    monkeypatch.setattr(shared_tree, "_SCORE_PAGE_BYTES", 1)  # 512-row pages
    assert shared_tree._score_page_rows(8) == 512
    paged = gbm.predict(fr)
    for c in whole.names:
        assert np.array_equal(whole.vec(c).numeric_np(),
                              paged.vec(c).numeric_np()), c
