"""Device-resident data paths: DataInfo.device_design parity, tree-step
program sharing, ntrees-bucketed scoring, GLM device lambda path.

These lock in the TPU-first data-movement design decisions: compact
uploads + on-device expansion must be bit-compatible (to f32) with the
host transform, shared compiled programs must not change results, and
zero-padded scoring forests must be exact.
"""

import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.estimators import (
    H2OGeneralizedLinearEstimator,
    H2OGradientBoostingEstimator,
)
from h2o3_tpu.models.model_base import DataInfo


def _mixed_frame(n=3000, seed=0, with_na=True):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=n)
    if with_na:
        a[rng.random(n) < 0.08] = np.nan
    cat = rng.choice(["x", "y", "z", "w"], size=n)
    return h2o.H2OFrame_from_python(
        {"a": a, "b": rng.normal(size=n), "c": cat},
        column_types={"c": "enum"})


@pytest.mark.parametrize("standardize", [True, False])
@pytest.mark.parametrize("impute", [True, False])
def test_device_design_matches_fit_transform(standardize, impute):
    fr = _mixed_frame()
    d_host = DataInfo(fr, ["a", "b", "c"], standardize=standardize,
                      impute_missing=impute)
    X_host = d_host.fit_transform(fr)
    d_dev = DataInfo(fr, ["a", "b", "c"], standardize=standardize,
                     impute_missing=impute)
    X_dev = np.asarray(d_dev.device_design(fr, fit=True))
    np.testing.assert_allclose(X_host, X_dev, atol=1e-5)
    if standardize:
        np.testing.assert_allclose(d_host.means, d_dev.means, atol=1e-6)
        np.testing.assert_allclose(d_host.stds, d_dev.stds, atol=1e-6)
    # transform path on a frame with an unseen level
    fr2 = _mixed_frame(300, seed=9, with_na=True)
    np.testing.assert_allclose(
        d_host.transform(fr2),
        np.asarray(d_dev.device_design(fr2, fit=False)), atol=1e-5)


def test_device_design_all_nan_column():
    n = 100
    fr = h2o.H2OFrame_from_python(
        {"dead": np.full(n, np.nan), "b": np.arange(n, dtype=float)})
    di = DataInfo(fr, ["dead", "b"], standardize=True)
    X = np.asarray(di.device_design(fr, fit=True))
    assert np.isfinite(X).all()
    np.testing.assert_allclose(X[:, 0], 0.0)  # fit_transform semantics


def test_tree_program_shared_across_scalar_hyperparams():
    fr = _mixed_frame(2000, with_na=False)
    rng = np.random.default_rng(1)
    y = (rng.random(2000) < 0.5).astype(int)
    fr = fr.cbind(h2o.H2OFrame_from_python(
        {"y": y.astype(str)}, column_types={"y": "enum"}))
    from h2o3_tpu.parallel import mesh as cloudlib

    aucs = []
    for lrate, mr in [(0.1, 10.0), (0.05, 5.0), (0.2, 20.0)]:
        g = H2OGradientBoostingEstimator(ntrees=5, max_depth=3, seed=1,
                                         learn_rate=lrate, min_rows=mr)
        g.train(x=["a", "b", "c"], y="y", training_frame=fr)
        aucs.append(g.auc())
    # different scalars must produce different models ...
    assert len({round(a, 6) for a in aucs}) > 1
    # ... from ONE cached step program (same structural cfg)
    cache = cloudlib.cloud().__dict__.get("_step_fns_cache", {})
    matching = [cfg for cfg in cache
                if cfg.max_depth == 3 and cfg.K == 1 and cfg.F == 3]
    assert len(matching) == 1


def test_padded_scoring_exact_for_any_ntrees():
    rng = np.random.default_rng(2)
    n = 1500
    a = rng.normal(size=n)
    y = (a + rng.normal(scale=0.5, size=n) > 0).astype(int)
    fr = h2o.H2OFrame_from_python({"a": a, "y": y.astype(str)},
                                  column_types={"y": "enum"})
    for nt in (1, 3, 7):
        g = H2OGradientBoostingEstimator(ntrees=nt, max_depth=3, seed=1)
        g.train(x=["a"], y="y", training_frame=fr)
        m = g.model
        # padded margins == unpadded reference sum over real trees
        import jax.numpy as jnp

        from h2o3_tpu.models import tree as treelib

        Xm = m._matrix(fr)
        ref = np.zeros(n)
        st = m.forest[0]
        for t in range(nt):
            one = treelib.Tree(*[jnp.asarray(np.asarray(f)[t])
                                 for f in st])
            ref += np.asarray(treelib.predict_raw(
                one, jnp.asarray(Xm, jnp.float32), m.max_depth))
        f0 = m.f0 if np.ndim(m.f0) == 0 else m.f0[0]
        np.testing.assert_allclose(m._margins(Xm)[:, 0], ref + f0,
                                   atol=1e-5)


def test_glm_device_lambda_path_matches_host():
    rng = np.random.default_rng(3)
    n = 4000
    a = rng.normal(size=n)
    b = rng.normal(size=n)
    y = (rng.random(n) < 1 / (1 + np.exp(-(1.2 * a - 0.4 * b)))).astype(int)
    fr = h2o.H2OFrame_from_python({"a": a, "b": b, "y": y.astype(str)},
                                  column_types={"y": "enum"})
    glm = H2OGeneralizedLinearEstimator(family="binomial",
                                        lambda_search=True, alpha=0.5)
    glm.train(x=["a", "b"], y="y", training_frame=fr)
    assert glm.auc() > 0.7
    path = glm.model.full_path
    assert len(path) >= 20
    # the path must shrink coefficients as lambda grows (elastic net)
    l1_first = np.abs(path[0][1][:-1]).sum()    # largest lambda
    l1_last = np.abs(path[-1][1][:-1]).sum()    # smallest lambda
    assert l1_last > l1_first
    assert np.isfinite(np.asarray(glm.model.beta)).all()
    # PARITY: recompute a few path points with the retained host f64 IRLS
    # (cold warm-start) and compare the device f32 betas against them
    import jax.numpy as jnp

    m = glm.model
    Xd = m.dinfo.device_design(fr, fit=False, add_intercept=True)
    yd = np.asarray(fr.vec("y").data, np.float32)
    wd = np.ones(fr.nrow, np.float32)
    for i in (0, len(path) // 2, len(path) - 1):
        lam_i, beta_dev = path[i]
        beta_host = glm._irls_warm(
            Xd, jnp.asarray(yd), jnp.asarray(wd), "binomial", float(lam_i),
            0.5, 50, 1e-4, 1.5, np.zeros(Xd.shape[1], np.float64))
        np.testing.assert_allclose(beta_dev, beta_host, atol=5e-3)


def test_device_design_sharded_mesh_matches_dense(cloud8):
    """Single-process multi-device mesh: device_design(cloud=) produces the
    row-sharded byte-compressed design, equal to the dense f32 path, with
    zero-padded quota rows at the tail."""
    import numpy as np

    import h2o3_tpu as h2o
    from h2o3_tpu.models.model_base import DataInfo
    from h2o3_tpu.parallel import mesh as cloudlib

    rng = np.random.default_rng(3)
    n = 500                                  # NOT divisible by 8 → padding
    d = {
        "a": rng.integers(0, 200, n).astype(np.float64),       # uint8 group
        "b": rng.integers(-1000, 1000, n).astype(np.float64),  # int16 group
        "f": rng.normal(size=n),                               # f32 group
        "c": np.asarray([f"k{v}" for v in rng.integers(0, 4, n)],
                        dtype=object),
    }
    fr = h2o.H2OFrame_from_python(d, column_types={"c": "enum"})
    dinfo = DataInfo(fr, ["a", "b", "f", "c"], standardize=True)
    X = dinfo.fit_transform(fr)
    Xd = dinfo.device_design(fr, fit=False, cloud=cloud8)
    assert dinfo._transfer_groups == [0, 1, 2]
    quota = cloudlib.pad_to_multiple(n, cloud8.size)
    assert int(Xd.shape[0]) == quota
    got = np.asarray(Xd)
    np.testing.assert_allclose(got[:n], X, rtol=1e-5, atol=1e-5)
    # sharding really is by rows over the mesh
    assert len(Xd.sharding.device_set) == cloud8.size
