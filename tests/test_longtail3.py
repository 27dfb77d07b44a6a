"""Long-tail algos batch 3: RuleFit, PSVM, UpliftDRF, ExtendedIsolationForest.

Mirrors reference pyunits `pyunit_rulefit_*`, `pyunit_psvm_*`,
`pyunit_uplift_*`, `pyunit_extended_isolation_forest_*`."""

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.extended_isolation_forest import (
    H2OExtendedIsolationForestEstimator,
)
from h2o3_tpu.models.psvm import H2OSupportVectorMachineEstimator
from h2o3_tpu.models.rulefit import H2ORuleFitEstimator
from h2o3_tpu.models.uplift import H2OUpliftRandomForestEstimator, auuc


def _binary_frame(n=800, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 4))
    y = ((X[:, 0] > 0.3) & (X[:, 1] < 0.5) | (X[:, 2] > 1.0)).astype(int)
    d = {f"x{i}": X[:, i] for i in range(4)}
    d["y"] = np.asarray(["no", "yes"], dtype=object)[y]
    return Frame.from_dict(d, column_types={"y": "enum"})


def test_rulefit_rules_and_predict(cloud1):
    fr = _binary_frame()
    rf = H2ORuleFitEstimator(max_num_rules=20, min_rule_length=2,
                             max_rule_length=3, rule_generation_ntrees=20, seed=7)
    rf.train(x=["x0", "x1", "x2", "x3"], y="y", training_frame=fr)
    assert rf.model.training_metrics.auc > 0.8
    imp = rf.model.rule_importance()
    assert 0 < imp.nrow <= 25  # rules + linear terms, sparse
    # rule strings mention real feature names
    rv = imp.vec("rule")
    rules_txt = [rv.domain[c] for c in np.asarray(rv.data)]
    assert any("x0" in r or "x2" in r for r in rules_txt)
    p = rf.predict(fr)
    assert "predict" in p.names


def test_rulefit_regression(cloud1):
    rng = np.random.default_rng(1)
    X = rng.normal(size=(500, 3))
    y = np.where(X[:, 0] > 0, 2.0, -1.0) + 0.1 * rng.normal(size=500)
    d = {f"x{i}": X[:, i] for i in range(3)}
    d["y"] = y
    fr = Frame.from_dict(d)
    rf = H2ORuleFitEstimator(model_type="rules", min_rule_length=1,
                             max_rule_length=2, rule_generation_ntrees=10, seed=3)
    rf.train(x=["x0", "x1", "x2"], y="y", training_frame=fr)
    assert rf.model.training_metrics.rmse < 0.6


def test_psvm_separable(cloud1):
    rng = np.random.default_rng(2)
    n = 400
    X = rng.normal(size=(n, 2))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    fr = Frame.from_dict(
        {"a": X[:, 0], "b": X[:, 1],
         "y": np.asarray(["n", "p"], dtype=object)[y]},
        column_types={"y": "enum"})
    svm = H2OSupportVectorMachineEstimator(hyper_param=1.0, kernel_type="gaussian",
                                           seed=5)
    svm.train(x=["a", "b"], y="y", training_frame=fr)
    assert svm.model.training_metrics.auc > 0.95
    assert svm.model.svs_count > 0
    pred = svm.predict(fr)
    assert set(pred.names) >= {"predict", "decision_function"}
    # nonlinear ring data needs the gaussian kernel
    r = np.sqrt((X**2).sum(axis=1))
    y2 = (r > 1.1).astype(int)
    fr2 = Frame.from_dict(
        {"a": X[:, 0], "b": X[:, 1],
         "y": np.asarray(["in", "out"], dtype=object)[y2]},
        column_types={"y": "enum"})
    svm2 = H2OSupportVectorMachineEstimator(kernel_type="gaussian", gamma=1.0, seed=5)
    svm2.train(x=["a", "b"], y="y", training_frame=fr2)
    assert svm2.model.training_metrics.auc > 0.9


def test_uplift_drf(cloud1):
    rng = np.random.default_rng(3)
    n = 2000
    X = rng.normal(size=(n, 3))
    treat = rng.integers(0, 2, n)
    # uplift only where x0>0: treated respond more
    base = (X[:, 1] > 0.5).astype(float) * 0.2
    lift = np.where(X[:, 0] > 0, 0.4, 0.0) * treat
    y = (rng.uniform(size=n) < base + lift + 0.1).astype(int)
    fr = Frame.from_dict({
        "x0": X[:, 0], "x1": X[:, 1], "x2": X[:, 2],
        "treatment": np.asarray(["control", "treatment"], dtype=object)[treat],
        "y": np.asarray(["0", "1"], dtype=object)[y],
    }, column_types={"treatment": "enum", "y": "enum"})
    up = H2OUpliftRandomForestEstimator(
        treatment_column="treatment", uplift_metric="KL", ntrees=20,
        max_depth=4, seed=11)
    up.train(x=["x0", "x1", "x2"], y="y", training_frame=fr)
    u = up.predict(fr).vec("uplift_predict").numeric_np()
    # predicted uplift should be higher where true uplift exists
    assert u[X[:, 0] > 0].mean() > u[X[:, 0] <= 0].mean() + 0.1
    m = up.model.training_metrics
    assert np.isfinite(m.auuc)
    # qini auuc of the model ranking beats a random ranking
    rand_auuc, _ = auuc(y.astype(float), treat.astype(float),
                        rng.uniform(size=n))
    assert m.auuc > rand_auuc


def test_extended_isolation_forest(cloud1):
    rng = np.random.default_rng(4)
    X = rng.normal(size=(500, 3))
    X[:5] += 8.0  # planted anomalies
    fr = Frame.from_numpy(X, names=["a", "b", "c"])
    eif = H2OExtendedIsolationForestEstimator(ntrees=50, sample_size=128,
                                              extension_level=2, seed=9)
    eif.train(x=["a", "b", "c"], training_frame=fr)
    out = eif.predict(fr)
    s = out.vec("anomaly_score").numeric_np()
    assert out.vec("mean_length").numeric_np().min() >= 0
    # planted anomalies rank in the top scores
    top = np.argsort(-s)[:10]
    assert len(set(top) & set(range(5))) >= 4
    assert s.min() >= 0 and s.max() <= 1


def test_eif_extension_level_validation(cloud1):
    fr = Frame.from_numpy(np.random.default_rng(0).normal(size=(50, 2)),
                          names=["a", "b"])
    with pytest.raises(ValueError):
        H2OExtendedIsolationForestEstimator(extension_level=5).train(
            x=["a", "b"], training_frame=fr)


def test_save_grid_load_grid_roundtrip(tmp_path, cloud1):
    """h2o.save_grid on a grid trained WITHOUT recovery_dir exports state +
    artifacts; h2o.load_grid restores the models and their metrics."""
    import numpy as np

    import h2o3_tpu as h2o
    from h2o3_tpu.estimators import H2OGradientBoostingEstimator
    from h2o3_tpu.models.grid import H2OGridSearch

    rng = np.random.default_rng(0)
    n = 800
    X = rng.normal(size=(n, 4))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    d = {f"c{i}": X[:, i] for i in range(4)}
    d["y"] = y.astype(str)
    fr = h2o.H2OFrame_from_python(d, column_types={"y": "enum"})
    gs = H2OGridSearch(H2OGradientBoostingEstimator(ntrees=4, seed=1),
                       hyper_params={"max_depth": [2, 3]}, grid_id="sg1")
    gs.train(x=[f"c{i}" for i in range(4)], y="y", training_frame=fr)
    out = h2o.save_grid(gs, str(tmp_path / "gdir"))
    g2 = h2o.load_grid(out)
    assert g2.grid_id == "sg1"
    assert len(g2.models) == 2
    # restored models score: predictions finite on the training frame
    p = g2.models[0].predict(fr)
    assert np.isfinite(p.vec("1").numeric_np()).all()
    # a SECOND save to a different dir must carry the artifacts along
    out2 = h2o.save_grid(gs, str(tmp_path / "gdir2"))
    g3 = h2o.load_grid(out2)
    assert len(g3.models) == 2


def test_save_grid_numpy_hypers_and_kwargs(tmp_path, cloud1):
    import numpy as np
    import pytest

    import h2o3_tpu as h2o
    from h2o3_tpu.estimators import H2OGradientBoostingEstimator
    from h2o3_tpu.models.grid import H2OGridSearch

    rng = np.random.default_rng(1)
    d = {"a": rng.normal(size=300), "b": rng.normal(size=300),
         "y": (rng.random(300) > 0.5).astype(int).astype(str)}
    fr = h2o.H2OFrame_from_python(d, column_types={"y": "enum"})
    gs = H2OGridSearch(H2OGradientBoostingEstimator(ntrees=2, seed=1),
                       hyper_params={"max_depth": np.arange(2, 4)},
                       grid_id="sgnp")
    gs.train(x=["a", "b"], y="y", training_frame=fr)
    out = h2o.save_grid(gs, str(tmp_path / "np_gdir"))   # np scalars OK
    assert len(h2o.load_grid(out).models) == 2
    with pytest.raises(NotImplementedError):
        h2o.save_grid(gs, str(tmp_path / "x"),
                      export_cross_validation_predictions=True)


def test_misc_surface_functions(tmp_path, cloud1):
    """h2o.models/as_list/list_timezones/estimate_cluster_mem/
    log_and_echo/download_all_logs/network_test/cluster_status parity."""
    import numpy as np
    import pytest

    import h2o3_tpu as h2o
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    rng = np.random.default_rng(0)
    d = {"a": rng.normal(size=200), "y": (rng.random(200) > 0.5).astype(int).astype(str)}
    fr = h2o.H2OFrame_from_python(d, column_types={"y": "enum"})
    m = H2OGradientBoostingEstimator(ntrees=2, max_depth=2, seed=1)
    m.train(y="y", training_frame=fr)
    assert m.model.model_id in h2o.ls()

    lst = h2o.as_list(fr, header=True)
    assert lst[0] == ["a", "y"] and len(lst) == 201

    tz = h2o.list_timezones()
    assert tz.nrow > 100 and "UTC" in set(tz.vec("Timezones").to_numpy())

    gb = h2o.estimate_cluster_mem(ncols=10, nrows=1_000_000)
    assert gb == pytest.approx(4 * 10 * 8 * 1e6 / 1e9, rel=1e-6)
    with pytest.raises(ValueError):
        h2o.estimate_cluster_mem(ncols=2, nrows=10, string_cols=3)

    h2o.log_and_echo("marker-xyz")
    z = h2o.download_all_logs(str(tmp_path))
    import zipfile

    with zipfile.ZipFile(z) as zf:
        text = zf.read("h2o3_tpu.log").decode()
    assert "marker-xyz" in text

    res = h2o.network_test()
    assert len(res) == 3 and all(r["mbytes_per_sec"] > 0 for r in res)
    h2o.cluster_status()        # prints, must not raise


def test_model_transfer_and_make_metrics(tmp_path, cloud1):
    """h2o.download_model/print_mojo/make_metrics in-process parity."""
    import json

    import numpy as np
    import pytest

    import h2o3_tpu as h2o
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    rng = np.random.default_rng(2)
    n = 800
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    d = {f"c{i}": X[:, i] for i in range(3)}
    d["y"] = y.astype(str)
    fr = h2o.H2OFrame_from_python(d, column_types={"y": "enum"})
    m = H2OGradientBoostingEstimator(ntrees=4, max_depth=3, seed=1)
    m.train(y="y", training_frame=fr)

    path = h2o.download_model(m, str(tmp_path))
    dump = json.loads(h2o.print_mojo(path))
    assert dump["meta"]["kind"] == "tree"
    assert any(k.startswith("forest0") for k in dump["arrays"])

    # make_metrics(binomial): must agree with the model's own AUC
    p1 = m.predict(fr)["1"]
    # predict-frame probabilities vs training-margin metrics: same model,
    # slightly different float paths — agree to ~1e-3, not bitwise
    mm = h2o.make_metrics(p1, fr["y"], domain=["0", "1"])
    assert float(mm.auc) == pytest.approx(float(m.auc()), abs=2e-3)
    # regression
    t = X[:, 0] * 2.0
    mm2 = h2o.make_metrics(t + 0.1, h2o.H2OFrame_from_python({"t": t})["t"])
    assert float(mm2.rmse) == pytest.approx(0.1, abs=1e-9)
    # h2o.api without a connection raises cleanly
    from h2o3_tpu.client import H2OConnectionError

    with pytest.raises(H2OConnectionError):
        h2o.api("GET /3/Cloud")


def test_upload_model_remote(tmp_path):
    """h2o.upload_model pushes a local artifact to a separate server
    process; the returned server-side model predicts over the wire."""
    import os
    import subprocess
    import sys
    import time

    import numpy as np

    import h2o3_tpu as h2o
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    rng = np.random.default_rng(3)
    n = 400
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] > 0).astype(int)
    d = {f"c{i}": X[:, i] for i in range(3)}
    d["y"] = y.astype(str)
    fr_local = h2o.H2OFrame_from_python(d, column_types={"y": "enum"})
    m = H2OGradientBoostingEstimator(ntrees=3, max_depth=2, seed=1)
    m.train(y="y", training_frame=fr_local)
    path = h2o.save_model(m, str(tmp_path))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    proc = subprocess.Popen([sys.executable, "-c", """
import jax; jax.config.update("jax_platforms", "cpu")
import time
from h2o3_tpu.rest.server import start_server
import h2o3_tpu as h2o
h2o.init()
s = start_server(port=0, auth_token=None)
print(s.port, flush=True)
time.sleep(600)
"""], env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    try:
        port = int(proc.stdout.readline())
        h2o.connect(url=f"http://127.0.0.1:{port}", verbose=False)
        rm = h2o.upload_model(path)
        fr = h2o.H2OFrame_from_python(
            {f"c{i}": X[:, i] for i in range(3)})
        pred = rm.predict(fr)
        got = np.asarray(pred.as_data_frame(use_pandas=False)["1"])
        want = m.predict(fr_local).vec("1").numeric_np()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        # the uploaded artifact is inspectable and downloads back
        info = h2o.connection().get(
            f"/3/Models/{rm.model_id}")["models"][0]
        assert info["uploaded_artifact"] and info["kind"] == "tree"
        back = h2o.download_model(rm, str(tmp_path / "back"))
        p2 = h2o.load_model(back).predict(fr_local).vec("1").numeric_np()
        np.testing.assert_allclose(p2, want, rtol=1e-5, atol=1e-6)
    finally:
        proc.kill()
        h2o.shutdown()
