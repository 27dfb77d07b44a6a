"""MOJO export across the model zoo: every new artifact
kind round-trips save → load → predict with row-level parity against the
in-cluster model. Reference: `hex/genmodel/algos/**` scorers +
`EasyPredictModelWrapper` (in-cluster ≡ MOJO parity is upstream's
contract)."""

import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.frame.frame import Frame


def _cls_frame(n=500, p=4, seed=0, enum_col=False):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, p))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(int)
    names = [f"c{i}" for i in range(p)]
    d = {nm: X[:, i] for i, nm in enumerate(names)}
    if enum_col:
        d["cat"] = np.asarray(
            [f"k{v}" for v in rng.integers(0, 3, n)], dtype=object)
    d["y"] = y.astype(str)
    return h2o.H2OFrame_from_python(
        d, column_types={"y": "enum", **({"cat": "enum"} if enum_col else {})})


def _roundtrip(est, tmp_path):
    path = h2o.save_model(est, str(tmp_path))
    return h2o.load_model(path)


def test_mojo_eif(tmp_path, cloud1):
    from h2o3_tpu.models.extended_isolation_forest import \
        H2OExtendedIsolationForestEstimator

    fr = _cls_frame(400, seed=1)
    est = H2OExtendedIsolationForestEstimator(ntrees=12, sample_size=64,
                                              extension_level=1, seed=2)
    est.train(x=[f"c{i}" for i in range(4)], training_frame=fr)
    sc = _roundtrip(est, tmp_path)
    live = est.predict(fr)
    mojo = sc.predict(fr)
    np.testing.assert_allclose(mojo.vec("anomaly_score").numeric_np(),
                               live.vec("anomaly_score").numeric_np(),
                               rtol=1e-5, atol=1e-6)


def test_mojo_stacked_ensemble(tmp_path, cloud1):
    from h2o3_tpu.estimators import (H2OGradientBoostingEstimator,
                                     H2OGeneralizedLinearEstimator,
                                     H2OStackedEnsembleEstimator)

    fr = _cls_frame(600, seed=3)
    x = [f"c{i}" for i in range(4)]
    gbm = H2OGradientBoostingEstimator(
        ntrees=6, max_depth=3, seed=1, nfolds=3,
        keep_cross_validation_predictions=True)
    gbm.train(x=x, y="y", training_frame=fr)
    glm = H2OGeneralizedLinearEstimator(
        family="binomial", nfolds=3, seed=1,
        keep_cross_validation_predictions=True)
    glm.train(x=x, y="y", training_frame=fr)
    se = H2OStackedEnsembleEstimator(base_models=[gbm, glm], seed=1)
    se.train(x=x, y="y", training_frame=fr)
    sc = _roundtrip(se, tmp_path)
    np.testing.assert_allclose(
        sc.predict(fr).vec("1").numeric_np(),
        se.predict(fr).vec("1").numeric_np(), rtol=1e-5, atol=1e-6)


def test_mojo_word2vec(tmp_path, cloud1):
    from h2o3_tpu.models.word2vec import H2OWord2vecEstimator

    rng = np.random.default_rng(0)
    words = [w for _ in range(60)
             for w in ("cat", "dog", "fish", "bird", "tree")]
    rng.shuffle(words)
    fr = h2o.H2OFrame_from_python(
        {"w": np.asarray(words, dtype=object)}, column_types={"w": "enum"})
    est = H2OWord2vecEstimator(vec_size=8, epochs=2, seed=1)
    est.train(training_frame=fr)
    sc = _roundtrip(est, tmp_path)
    live = est.model.transform(fr)
    mojo = sc.transform(fr)
    for j in range(8):
        np.testing.assert_allclose(mojo.vec(f"C{j+1}").numeric_np(),
                                   live.vec(f"C{j+1}").numeric_np(),
                                   rtol=1e-5, atol=1e-6)
    syn_live = est.model.find_synonyms("cat", 3)
    syn_mojo = sc.find_synonyms("cat", 3)
    assert list(syn_live) == list(syn_mojo)


def test_mojo_glrm(tmp_path, cloud1):
    from h2o3_tpu.models.glrm import H2OGeneralizedLowRankEstimator

    rng = np.random.default_rng(4)
    base = rng.normal(size=(200, 2))
    X = base @ rng.normal(size=(2, 5)) + 0.01 * rng.normal(size=(200, 5))
    X[rng.random(X.shape) < 0.05] = np.nan
    fr = h2o.H2OFrame_from_python({f"c{i}": X[:, i] for i in range(5)})
    est = H2OGeneralizedLowRankEstimator(k=2, seed=1)
    est.train(x=[f"c{i}" for i in range(5)], training_frame=fr)
    sc = _roundtrip(est, tmp_path)
    live = est.predict(fr)
    mojo = sc.predict(fr)
    for nm in live.names:
        np.testing.assert_allclose(mojo.vec(nm).numeric_np(),
                                   live.vec(nm).numeric_np(),
                                   rtol=1e-4, atol=1e-5)
    # transform (archetype loadings) parity too
    lt = est.model.transform(fr)
    mt = sc.transform(fr)
    for nm in lt.names:
        np.testing.assert_allclose(mt.vec(nm).numeric_np(),
                                   lt.vec(nm).numeric_np(),
                                   rtol=1e-4, atol=1e-5)


def test_mojo_targetencoder(tmp_path, cloud1):
    from h2o3_tpu.models.targetencoder import H2OTargetEncoderEstimator

    fr = _cls_frame(400, seed=5, enum_col=True)
    est = H2OTargetEncoderEstimator(blending=True, noise=0.0)
    est.train(x=["cat"], y="y", training_frame=fr)
    sc = _roundtrip(est, tmp_path)
    live = est.model.transform(fr)
    mojo = sc.predict(fr)
    np.testing.assert_allclose(mojo.vec("cat_te").numeric_np(),
                               live.vec("cat_te").numeric_np(),
                               rtol=1e-5, atol=1e-6)


def test_mojo_rulefit(tmp_path, cloud1):
    from h2o3_tpu.models.rulefit import H2ORuleFitEstimator

    fr = _cls_frame(600, seed=6)
    est = H2ORuleFitEstimator(rule_generation_ntrees=10, seed=1,
                              max_rule_length=3)
    est.train(x=[f"c{i}" for i in range(4)], y="y", training_frame=fr)
    sc = _roundtrip(est, tmp_path)
    np.testing.assert_allclose(
        sc.predict(fr).vec("1").numeric_np(),
        est.predict(fr).vec("1").numeric_np(), rtol=1e-5, atol=1e-6)


def test_mojo_coxph(tmp_path, cloud1):
    from h2o3_tpu.models.coxph import H2OCoxProportionalHazardsEstimator

    rng = np.random.default_rng(7)
    n = 300
    age = rng.normal(60, 10, n)
    sev = rng.normal(size=n)
    t = rng.exponential(np.exp(-0.02 * (age - 60) - 0.4 * sev))
    ev = (rng.random(n) < 0.8).astype(int)
    fr = h2o.H2OFrame_from_python(
        {"age": age, "sev": sev, "time": t, "event": ev.astype(np.float64)})
    est = H2OCoxProportionalHazardsEstimator(stop_column="time")
    est.train(x=["age", "sev"], y="event", training_frame=fr)
    sc = _roundtrip(est, tmp_path)
    np.testing.assert_allclose(
        sc.predict(fr).vec("lp").numeric_np(),
        est.predict(fr).vec("lp").numeric_np(), rtol=1e-5, atol=1e-6)


def test_mojo_naive_bayes(tmp_path, cloud1):
    from h2o3_tpu.models.naive_bayes import H2ONaiveBayesEstimator

    fr = _cls_frame(500, seed=8, enum_col=True)
    est = H2ONaiveBayesEstimator(laplace=1.0)
    est.train(x=["c0", "c1", "c2", "c3", "cat"], y="y", training_frame=fr)
    sc = _roundtrip(est, tmp_path)
    np.testing.assert_allclose(
        sc.predict(fr).vec("1").numeric_np(),
        est.predict(fr).vec("1").numeric_np(), rtol=1e-5, atol=1e-6)


def test_mojo_isotonic(tmp_path, cloud1):
    from h2o3_tpu.models.isotonic import H2OIsotonicRegressionEstimator

    rng = np.random.default_rng(9)
    x = rng.uniform(0, 10, 400)
    y = np.sqrt(x) + 0.1 * rng.normal(size=400)
    fr = h2o.H2OFrame_from_python({"x": x, "y": y})
    est = H2OIsotonicRegressionEstimator()
    est.train(x=["x"], y="y", training_frame=fr)
    sc = _roundtrip(est, tmp_path)
    np.testing.assert_allclose(
        sc.predict(fr).vec("predict").numeric_np(),
        est.predict(fr).vec("predict").numeric_np(),
        rtol=1e-6, atol=1e-8)


def test_mojo_svd(tmp_path, cloud1):
    from h2o3_tpu.models.svd import H2OSingularValueDecompositionEstimator

    rng = np.random.default_rng(10)
    X = rng.normal(size=(200, 4))
    fr = h2o.H2OFrame_from_python({f"c{i}": X[:, i] for i in range(4)})
    est = H2OSingularValueDecompositionEstimator(nv=2)
    est.train(x=[f"c{i}" for i in range(4)], training_frame=fr)
    sc = _roundtrip(est, tmp_path)
    live = est.predict(fr)
    mojo = sc.predict(fr)
    for nm in live.names:
        np.testing.assert_allclose(mojo.vec(nm).numeric_np(),
                                   live.vec(nm).numeric_np(),
                                   rtol=1e-5, atol=1e-7)


def test_mojo_unexportable_raises_documented(tmp_path, cloud1):
    from h2o3_tpu.models.aggregator import H2OAggregatorEstimator

    fr = _cls_frame(300, seed=11)
    est = H2OAggregatorEstimator(target_num_exemplars=20)
    est.train(x=[f"c{i}" for i in range(4)], training_frame=fr)
    with pytest.raises(TypeError, match="docs/mojo.md"):
        h2o.save_model(est, str(tmp_path))


def test_mojo_gam_carries_spline_basis(tmp_path, cloud1):
    """the GAM artifact scores NEW data offline with the
    same spline basis (knots + centering) the cluster fit — not just the
    inner GLM."""
    from h2o3_tpu.models.gam import H2OGeneralizedAdditiveEstimator

    rng = np.random.default_rng(2)
    n = 800
    X = rng.normal(size=(n, 3))
    y = (np.sin(X[:, 0] * 2) + 0.5 * X[:, 1] + 0.3 * rng.normal(size=n) > 0)
    d = {f"c{i}": X[:, i] for i in range(3)}
    d["y"] = y.astype(int).astype(str)
    fr = h2o.H2OFrame_from_python(d, column_types={"y": "enum"})
    est = H2OGeneralizedAdditiveEstimator(
        family="binomial", gam_columns=["c0"], num_knots=[6])
    est.train(x=["c1", "c2"], y="y", training_frame=fr)
    sc = _roundtrip(est, tmp_path)
    # NEW data — the basis must transfer, not just memorized training rows
    Xn = rng.normal(size=(300, 3))
    fn = h2o.H2OFrame_from_python({f"c{i}": Xn[:, i] for i in range(3)})
    live = est.model.predict(fn)
    mojo = sc.predict(fn)
    np.testing.assert_allclose(mojo.vec("1").numeric_np(),
                               live.vec("1").numeric_np(),
                               rtol=1e-5, atol=1e-6)
    assert list(mojo.names) == list(live.names)


def test_mojo_upliftdrf(tmp_path, cloud1):
    """UpliftDRF artifact: offline uplift_predict ≡ in-cluster on new
    rows (upstream genmodel uplift scoring)."""
    from h2o3_tpu.models.uplift import H2OUpliftRandomForestEstimator

    rng = np.random.default_rng(4)
    n = 1200
    X = rng.normal(size=(n, 4))
    treat = rng.integers(0, 2, n)
    # treatment helps when c0 > 0
    p = 0.3 + 0.3 * treat * (X[:, 0] > 0) + 0.1 * (X[:, 1] > 0)
    y = (rng.random(n) < p).astype(int)
    d = {f"c{i}": X[:, i] for i in range(4)}
    d["treatment"] = np.asarray(["control", "treatment"],
                                dtype=object)[treat]
    d["y"] = y.astype(str)
    fr = h2o.H2OFrame_from_python(
        d, column_types={"y": "enum", "treatment": "enum"})
    est = H2OUpliftRandomForestEstimator(
        treatment_column="treatment", ntrees=10, max_depth=5, seed=7)
    est.train(x=[f"c{i}" for i in range(4)], y="y", training_frame=fr)
    sc = _roundtrip(est, tmp_path)
    Xn = rng.normal(size=(300, 4))
    fn = h2o.H2OFrame_from_python({f"c{i}": Xn[:, i] for i in range(4)})
    live = est.model.predict(fn)
    mojo = sc.predict(fn)
    np.testing.assert_allclose(mojo.vec("uplift_predict").numeric_np(),
                               live.vec("uplift_predict").numeric_np(),
                               rtol=1e-5, atol=1e-6)
