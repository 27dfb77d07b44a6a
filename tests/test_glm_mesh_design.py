"""The engine's mesh design build (ISSUE 33): on a one-process cloud of more
than one device the GLM fit never builds the dense host design — the
statistics are fitted from the compact columns exactly as on one device, the
packs go up row-sharded and are expanded in place — and the fit plan says how
the fit was laid out."""

import jax
import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.vec import Vec
from h2o3_tpu.models import dataset_cache, estimator_engine as est
from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator
from h2o3_tpu.models.model_base import DataInfo
from h2o3_tpu.parallel import distdata, mesh
from h2o3_tpu.runtime import tracing

N = 1003                      # not a multiple of the 8-block grid: 5 pad rows
X_COLS = ["carrier", "origin", "dep", "dist"]
P = (5 - 1) + (7 - 1) + 2 + 1  # levels less the first, numerics, intercept


def _columns(seed=5, nas=True):
    rng = np.random.default_rng(seed)
    carrier = rng.integers(0, 5, N).astype(np.int32)
    origin = rng.integers(0, 7, N).astype(np.int32)
    dep = rng.integers(0, 2400, N).astype(np.float32)
    dist = np.abs(rng.normal(800, 500, N)).astype(np.float32)
    eta = 0.3 * (carrier == 2) - 0.4 * (origin == 1) + 2e-4 * (dist - 800)
    y = (rng.random(N) < 1 / (1 + np.exp(-eta))).astype(np.int32)
    if nas:
        origin[rng.choice(N, 40, replace=False)] = -1   # NA level code
        dist[rng.choice(N, 60, replace=False)] = np.nan
    return {"carrier": Vec(carrier, "enum", domain=list("abcde")),
            "origin": Vec(origin, "enum", domain=list("tuvwxyz")),
            "dep": Vec(dep, "real"), "dist": Vec(dist, "real"),
            "y": Vec(y, "enum", domain=["NO", "YES"])}


@pytest.fixture
def cloud4():
    dataset_cache.clear()
    yield mesh.init(jax.devices()[:4])
    dataset_cache.clear()


@pytest.fixture
def no_dense_host_design(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("the dense host design was built")

    monkeypatch.setattr(DataInfo, "_expand", refuse)
    monkeypatch.setattr(DataInfo, "fit_transform", refuse)


def _fit(frame):
    g = H2OGeneralizedLinearEstimator(family="binomial", solver="IRLSM",
                                      lambda_=0)
    g.train(y="y", training_frame=frame)
    return g, [p for p in est.est_stats()["plans"] if p["algo"] == "glm"][-1]


def test_mesh_fit_never_builds_the_dense_host_design(cloud4,
                                                     no_dense_host_design):
    frame = Frame(_columns())
    g1, plan1 = _fit(frame)
    g2, plan2 = _fit(frame)            # the std layer, keyed by n_devices
    assert plan1["matrix_cache"] == "miss" and plan2["matrix_cache"] == "hit"
    assert np.array_equal(g1.model.beta, g2.model.beta)
    assert 0.5 < g1.auc() < 1.0 and np.isfinite(g1.logloss())


@pytest.mark.parametrize("field,want", [
    ("path", "fused_mesh"), ("n_devices", 4), ("n_shards", 8),
    ("local_blocks", 2), ("rows_per_device", 1008 // 4),
    ("fold_bytes", 8 * P * (P + 1) * 4)])
def test_the_fit_plan_says_how_the_fit_was_laid_out(cloud4, field, want):
    _, plan = _fit(Frame(_columns()))
    assert plan[field] == want, plan


def test_one_device_plans_gather_nothing(cloud1, monkeypatch):
    dataset_cache.clear()
    _, plan = _fit(Frame(_columns()))
    assert (plan["path"], plan["local_blocks"], plan["fold_bytes"],
            plan["rows_per_device"]) == ("fused", 0, 0, N)
    monkeypatch.setenv("H2O3_EST_SHARD", "1")
    dataset_cache.clear()
    _, plan = _fit(Frame(_columns()))
    assert (plan["path"], plan["local_blocks"], plan["fold_bytes"],
            plan["rows_per_device"]) == ("fused_blocks", 8, 0, 1008)


def _design(ndev, nas):
    cloud = mesh.init(jax.devices()[:ndev])
    frame = Frame(_columns(nas=nas))
    dinfo = DataInfo(frame, X_COLS, standardize=True)
    if ndev == 1:
        Xd = dinfo.device_design(frame, fit=True, add_intercept=True)
    else:
        Xd = dinfo.device_design(frame, fit=True, add_intercept=True,
                                 cloud=cloud, quota=est.pad_rows(N, 8))
    return dinfo, Xd


@pytest.mark.parametrize("nas", [False, True])
@pytest.mark.parametrize("ndev", [2, 4, 8])
def test_mesh_statistics_and_design_equal_the_one_device_lane(
        no_dense_host_design, ndev, nas):
    d1, X1 = _design(1, nas)
    dm, Xm = _design(ndev, nas)
    assert np.array_equal(d1.means, dm.means)
    assert np.array_equal(d1.stds, dm.stds)
    assert d1.col_means == dm.col_means and (
        not nas or d1.col_means["dist"] != 0.0)
    assert d1._transfer_groups == dm._transfer_groups == [1, 2]
    assert Xm.shape == (1008, P) and len(Xm.sharding.device_set) == ndev
    assert np.array_equal(np.asarray(Xm)[:N], np.asarray(X1))


def test_upload_span_says_devices_and_bytes(cloud4):
    tracing.clear()
    _fit(Frame(_columns()))
    up = [s for s in tracing.spans() if s["name"] == "design.upload"]
    # one int16 and one float32 numeric pack, two int32 code columns
    assert up and up[-1]["attrs"]["devices"] == 4
    assert up[-1]["attrs"]["bytes_h2d"] == N * (2 + 4 + 2 * 4)
    names = {s["name"] for s in tracing.spans()}
    assert {"design.stats", "design.codes", "design.groups", "design.pack",
            "fit.design", "fit.iterate", "fit.metrics"} <= names


def test_a_multi_process_cloud_keeps_its_own_statistics(cloud4, monkeypatch):
    frame = Frame(_columns())
    dinfo = DataInfo(frame, X_COLS, standardize=True)
    monkeypatch.setattr(distdata, "multiprocess", lambda: True)
    with pytest.raises(ValueError, match="fit_transform first"):
        dinfo.device_design(frame, fit=True, cloud=cloud4)
