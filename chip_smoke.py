#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU chip, the normal public API, seeded synthetic data,
nothing from the network. Drives the main path once at the flagship's full
width (`BASELINE.json` config 1: HIGGS-like 1,000,000 × 28, 100 trees, depth
6) and holds what comes out to the repo's own references:

  fit     H2OGradientBoostingEstimator.train → AUC, the recorded kernel plan
          (Pallas, no fallback), and a pallas_factored/segment pair
  score   model.predict on a fresh frame vs the offline MOJO scorer
  serve   REST train/poll + 32 concurrent predictions through the batcher
  engine  one GLM and one DeepLearning fit (models/estimator_engine.py)
  refit   a second fit of the flagship shape compiles nothing

    python chip_smoke.py            # one chip; exits nonzero with no TPU
    python chip_smoke.py --chips 4  # ONLY the sharded path + its comparator

Phases are functions of their sizes: `main()` calls them at full size on the
chip, tests/test_chip_smoke.py calls the same functions tiny on the forced
CPU. Any phase that raises ends the run with a nonzero exit code. Wall times
printed per phase are SMOKE TIMINGS (cold compile included), not results.
The last line of stdout is the contract's one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.parse
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def make_higgs_like(n_rows: int, n_feat: int = 28, seed: int = 0):
    """The flagship's synthetic stand-in: standard-normal features and a
    label drawn from a logistic of a few of them."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n_rows, n_feat)).astype(np.float32)
    logits = (
        1.2 * X[:, 0]
        - 0.8 * X[:, 1]
        + 1.5 * X[:, 2] * X[:, 3]
        + 0.7 * np.sin(3 * X[:, 4])
        + 0.5 * (X[:, 5] ** 2 - 1)
    )
    y = (rng.random(n_rows) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    return X, y


def higgs_frame(n_rows: int, seed: int, key: str | None = None):
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.runtime.dkv import DKV

    X, y = make_higgs_like(n_rows, seed=seed)
    names = [f"f{i}" for i in range(X.shape[1])] + ["label"]
    fr = Frame.from_numpy(np.column_stack([X, y]),
                          names=names).asfactor("label")
    if key is not None:
        fr.key = key
        DKV.put(key, fr)
    return fr


def say(phase: str, t0: float, **facts) -> None:
    facts = " ".join(f"{k}={v}" for k, v in facts.items())
    print(f"[smoke] {phase}: ok in {time.time() - t0:.1f}s (smoke timing, "
          f"not a result) {facts}", flush=True)


# -- built from committed files only -----------------------------------------

def build_native() -> None:
    """Rebuild libh2o3native.so from the .cpp sources — the tree may carry a
    stale or foreign-CPU binary (`*.so` is ignored by git, the chip tool
    copies the disk) — and fail if the build fails, so the MOJO reference
    is the C++ scorer built from what is committed."""
    t0 = time.time()
    subprocess.run(["make", "-B", "-C",
                    os.path.join(HERE, "h2o3_tpu", "native")],
                   check=True, capture_output=True, timeout=300)
    from h2o3_tpu.native import loader

    if not loader.available():
        raise RuntimeError("libh2o3native.so built but does not load")
    say("native", t0, mojo_scorer="C++ (rebuilt with make -B)")


# -- fit ----------------------------------------------------------------------

def _fit_plan(tag_prefix: str) -> dict:
    from h2o3_tpu.ops import histogram

    plans = [p for p in histogram.kernel_stats()["plans"]
             if p["tag"].startswith(tag_prefix)]
    assert plans, f"no recorded fit plan for {tag_prefix}"
    return plans[-1]


def _assert_plan(plan: dict, expect_method: str) -> None:
    bad = [lv for lv in plan["levels"]
           if lv["method"] != expect_method or lv["fallback"] is not None]
    assert not bad, (f"fit plan {plan['tag']} expected {expect_method} with "
                     f"no fallback on every level, got {bad}")


def phase_fit(n_rows: int, ntrees: int, max_depth: int, expect_method: str,
              min_auc: float, pair_rows: int, pair_trees: int,
              pair_methods: tuple, pair_auc_tol: float = 0.005):
    """The flagship fit + the kernel pair. Returns (estimator, frame)."""
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.models.metrics import auc_exact
    from h2o3_tpu.ops import histogram

    t0 = time.time()
    fr = higgs_frame(n_rows, seed=0)
    fallbacks0 = histogram.kernel_stats()["vmem_fallbacks"]
    gbm = H2OGradientBoostingEstimator(
        ntrees=ntrees, max_depth=max_depth, learn_rate=0.1,
        histogram_type="UniformAdaptive", seed=42)
    t_fit = time.time()
    gbm.train(y="label", training_frame=fr)
    fit_s = time.time() - t_fit
    auc = float(gbm.auc())
    assert np.isfinite(auc) and auc >= min_auc, (
        f"flagship AUC {auc} < {min_auc}")
    assert int(gbm.model.ntrees_built) == ntrees, gbm.model.ntrees_built
    plan = _fit_plan(f"gbm:1x{ntrees}t_d{max_depth}")
    _assert_plan(plan, expect_method)
    assert histogram.kernel_stats()["vmem_fallbacks"] == fallbacks0, (
        "h2o3_tree_hist_vmem_fallbacks moved during the flagship fit")
    say("fit", t0, rows=n_rows, trees=ntrees, depth=max_depth,
        auc=round(auc, 5), train_s=round(fit_s, 1),
        kernel=expect_method, pack_bits=plan["pack_bits"],
        row_chunks=sorted({lv["row_chunk"] for lv in plan["levels"]},
                          key=str),
        bins_padded=sorted({lv["bins_padded"] for lv in plan["levels"]},
                           key=str))

    # the kernel against the exact f32 scatter: the kernel's bf16 one-hot
    # weights are the only licensed difference
    t1 = time.time()
    pfr = higgs_frame(pair_rows, seed=1)
    yv = pfr.vec("label").numeric_np()
    got = {}
    for method in pair_methods:
        est = H2OGradientBoostingEstimator(
            ntrees=pair_trees, max_depth=max_depth, learn_rate=0.1,
            seed=42, hist_method=method)
        est.train(y="label", training_frame=pfr)
        p1 = est.predict(pfr).vec("1").numeric_np()
        tree0 = est.model.forest[0]
        got[method] = (auc_exact(yv, p1),
                       int(np.asarray(tree0.feat)[0, 0]),
                       int(np.asarray(tree0.bin)[0, 0]))
        _assert_plan(_fit_plan(f"gbm:1x{pair_trees}t_d{max_depth}"), method)
    (a_auc, a_f, a_b), (b_auc, b_f, b_b) = (got[m] for m in pair_methods)
    assert abs(a_auc - b_auc) <= pair_auc_tol, got
    assert (a_f, a_b) == (b_f, b_b), f"root split differs: {got}"
    say("fit-pair", t1, rows=pair_rows, trees=pair_trees,
        **{m: round(v[0], 5) for m, v in got.items()},
        root_split=(a_f, a_b))
    return gbm, fr


# -- score --------------------------------------------------------------------

def phase_score(gbm, n_rows: int, atol: float = 1e-6):
    """Device scorer on a fresh frame vs the offline MOJO scorer (numpy /
    C++, no jax) — the independent reference the repo ships."""
    import h2o3_tpu as h2o
    from h2o3_tpu.models.metrics import auc_exact

    t0 = time.time()
    fresh = higgs_frame(n_rows, seed=7)
    pred = gbm.predict(fresh)
    assert pred.names == ["predict", "0", "1"], pred.names
    p1 = pred.vec("1").numeric_np()
    assert p1.shape == (n_rows,) and np.isfinite(p1).all()
    with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as d:
        path = h2o.save_model(gbm, d)
        ref = h2o.load_model(path).predict(fresh).vec("1").numeric_np()
    err = float(np.max(np.abs(p1 - ref)))
    assert err <= atol, f"device vs MOJO scorer: max |Δp1| = {err} > {atol}"
    auc = auc_exact(fresh.vec("label").numeric_np(), p1)
    say("score", t0, rows=n_rows, max_abs_err_vs_mojo=f"{err:.2e}",
        holdout_auc=round(auc, 5))


# -- serve --------------------------------------------------------------------

def _get(port: int, path: str):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        assert r.status == 200, (path, r.status)
        return json.loads(r.read())


def _post(port: int, path: str, **params):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=urllib.parse.urlencode(params).encode(), method="POST")
    with urllib.request.urlopen(req) as r:
        assert r.status == 200, (path, r.status)
        return json.loads(r.read())


def phase_serve(train_rows: int, score_rows: int, n_requests: int,
                n_threads: int, expect_method: str, ntrees: int = 20,
                max_depth: int = 5):
    """REST in the same process (threads, not children): train over HTTP,
    poll the job, then concurrent predictions through the micro-batcher."""
    from h2o3_tpu.rest.server import start_server
    from h2o3_tpu.runtime.dkv import DKV

    t0 = time.time()
    srv = start_server(port=0)
    try:
        higgs_frame(train_rows, seed=3, key="smoke_rest_train")
        n_frames = 4
        for i in range(n_frames):
            higgs_frame(score_rows, seed=20 + i, key=f"smoke_rest_score{i}")
        r = _post(srv.port, "/3/ModelBuilders/gbm",
                  training_frame="smoke_rest_train", response_column="label",
                  ntrees=str(ntrees), max_depth=str(max_depth), seed="42",
                  model_id="smoke_rest_gbm")
        job = r["job"]["key"]["name"]
        deadline = time.time() + 600
        while True:
            j = _get(srv.port, f"/3/Jobs/{job}")["jobs"][0]
            if j["status"] in ("DONE", "FAILED", "CANCELLED"):
                break
            assert time.time() < deadline, f"job {job} still {j['status']}"
            time.sleep(0.2)
        assert j["status"] == "DONE", j
        mid = j["dest"]["name"]
        m = _get(srv.port, f"/3/Models/{mid}")["models"][0]
        auc = m["output"]["training_metrics"]["auc"]
        assert m["algo"] == "gbm" and auc > 0.7, auc
        tree_fold = _get(srv.port, "/3/Profiler")["tree"]
        rest_plans = [p for p in tree_fold["plans"]
                      if p["tag"] == f"gbm:1x{ntrees}t_d{max_depth}"]
        assert rest_plans, "REST fit left no plan in /3/Profiler `tree`"
        _assert_plan(rest_plans[-1], expect_method)

        def one(i: int):
            f = f"smoke_rest_score{i % n_frames}"
            out = _post(srv.port, f"/3/Predictions/models/{mid}/frames/{f}")
            pf = DKV.get(out["predictions_frame"]["name"])
            p1 = pf.vec("1").numeric_np()
            assert p1.shape == (score_rows,) and np.isfinite(p1).all(), f
            return float(p1.mean())

        with ThreadPoolExecutor(max_workers=n_threads) as pool:
            means = list(pool.map(one, range(n_requests)))
        snap = _get(srv.port, "/3/Serving/metrics")
        c = snap["models"][mid]["counters"]
        assert c["requests"] == n_requests and c["errors"] == 0, c
        assert c["rejections"] == 0, c
        assert c["batches"] >= 1 and c["batched_requests"] == n_requests, c
        assert c["cache_hits"] >= 1, f"compiled-scorer cache never warm: {c}"
        assert snap["cache"]["size"] >= 1, snap["cache"]
    finally:
        srv.stop()
    say("serve", t0, train_rows=train_rows, rest_auc=round(auc, 4),
        requests=n_requests, threads=n_threads, batches=c["batches"],
        scorer_compiles=c["compiles"], scorer_cache_hits=c["cache_hits"],
        mean_p1=round(float(np.mean(means)), 4))


# -- engine -------------------------------------------------------------------

def _logistic_mle_f64(X: np.ndarray, y: np.ndarray, iters: int = 25):
    """Plain float64 IRLS for the unpenalized logistic MLE — the reference
    the GLM fit is held to. `lstsq` because the full one-hot design is
    rank-deficient; the fitted probabilities are unique all the same."""
    beta = np.zeros(X.shape[1])
    for _ in range(iters):
        eta = X @ beta
        mu = 1 / (1 + np.exp(-eta))
        W = np.maximum(mu * (1 - mu), 1e-10)
        z = eta + (y - mu) / W
        XW = X * W[:, None]
        new = np.linalg.lstsq(XW.T @ X, XW.T @ z, rcond=None)[0]
        done = np.max(np.abs(new - beta)) < 1e-10
        beta = new
        if done:
            break
    return 1 / (1 + np.exp(-(X @ beta)))


def phase_engine(glm_rows: int, dl_rows: int, dl_width: int, dl_hidden: list,
                 dl_epochs: float, min_glm_auc: float = 0.75,
                 max_dl_logloss: float = 1.5, glm_p_atol: float = 1e-3):
    """The other compiled loop (models/estimator_engine.py): one binomial
    GLM on ~40 one-hot columns and one MNIST-width DeepLearning fit."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.deeplearning import H2ODeepLearningEstimator
    from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator

    t0 = time.time()
    rng = np.random.default_rng(11)
    dep = rng.integers(0, 2400, glm_rows).astype(np.float64)
    dist = np.abs(rng.normal(800, 500, glm_rows))
    month = rng.integers(0, 12, glm_rows)
    dow = rng.integers(0, 7, glm_rows)
    carrier = rng.integers(0, 20, glm_rows)
    eff = (0.002 * (dep - 1200) + 0.8 * (carrier % 5 == 0)
           - 0.6 * (month % 4 == 0) + 0.5 * (dow >= 5) - 0.0004 * dist)
    y = (rng.random(glm_rows) < 1 / (1 + np.exp(-eff))).astype(int)

    def enum(prefix, codes, k):
        return np.asarray([f"{prefix}{v}" for v in range(k)],
                          dtype=object)[codes]

    gfr = Frame.from_dict(
        {"DepTime": dep, "Distance": dist, "Month": enum("M", month, 12),
         "DayOfWeek": enum("D", dow, 7), "Carrier": enum("C", carrier, 20),
         "IsDepDelayed": np.asarray(["NO", "YES"], dtype=object)[y]},
        column_types={"Month": "enum", "DayOfWeek": "enum",
                      "Carrier": "enum", "IsDepDelayed": "enum"})
    glm = H2OGeneralizedLinearEstimator(family="binomial", solver="IRLSM",
                                        lambda_=0.0)
    glm.train(y="IsDepDelayed", training_frame=gfr)
    gauc = float(glm.auc())
    ncoef = len(glm.coef())
    assert np.isfinite(gauc) and gauc >= min_glm_auc, gauc
    assert all(np.isfinite(v) for v in glm.coef().values())
    # against the float64 MLE on the same design (any full-rank or one-hot
    # parametrization spans the same space: the probabilities must agree)
    onehot = [np.eye(k)[c] for c, k in ((month, 12), (dow, 7), (carrier, 20))]
    Xref = np.column_stack([(dep - dep.mean()) / dep.std(),
                            (dist - dist.mean()) / dist.std(),
                            *onehot])
    p_ref = _logistic_mle_f64(Xref, y.astype(np.float64))
    p_dev = glm.predict(gfr).vec("YES").numeric_np()
    p_err = float(np.max(np.abs(p_dev - p_ref)))
    assert p_err <= glm_p_atol, (
        f"GLM probabilities vs float64 MLE: max |Δp| = {p_err}")
    say("engine-glm", t0, rows=glm_rows, coefficients=ncoef,
        auc=round(gauc, 5), max_abs_dp_vs_f64_mle=f"{p_err:.2e}")

    t1 = time.time()
    X = np.floor(rng.random((dl_rows, dl_width)) * 256).astype(np.float32)
    proto = rng.normal(size=(10, dl_width)).astype(np.float32)
    yl = ((X / 255.0) @ proto.T).argmax(axis=1)
    d = {f"p{i}": X[:, i] for i in range(dl_width)}
    d["label"] = np.asarray([str(v) for v in range(10)], dtype=object)[yl]
    dfr = Frame.from_dict(d, column_types={"label": "enum"})
    dl = H2ODeepLearningEstimator(hidden=list(dl_hidden),
                                  activation="Rectifier", epochs=dl_epochs,
                                  seed=1)
    dl.train(y="label", training_frame=dfr)
    ll = float(dl.logloss())
    # ln(10) = 2.303 is the untrained 10-class logloss
    assert np.isfinite(ll) and ll <= max_dl_logloss, ll
    say("engine-dl", t1, rows=dl_rows, width=dl_width, hidden=dl_hidden,
        epochs=dl_epochs, logloss=round(ll, 4))


# -- refit --------------------------------------------------------------------

def phase_refit(fr, ntrees: int, max_depth: int):
    """A second fit of the flagship shape: every program is already in the
    process, so the compile pipeline must not run at all."""
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.runtime import phases

    t0 = time.time()
    x0 = phases.xla_counts()
    gbm = H2OGradientBoostingEstimator(
        ntrees=ntrees, max_depth=max_depth, learn_rate=0.1,
        histogram_type="UniformAdaptive", seed=42)
    gbm.train(y="label", training_frame=fr)
    x1 = phases.xla_counts()
    delta = {k: x1[k] - x0[k] for k in x1}
    assert delta["compiles"] == 0 and delta["traces"] == 0, (
        f"second fit of the same shape ran the compile pipeline: {delta}")
    say("refit", t0, compiles=delta["compiles"], traces=delta["traces"],
        auc=round(float(gbm.auc()), 5))


# -- the sharded path (--chips 4) ---------------------------------------------

def phase_sharded(devices, n_rows: int, ntrees: int, max_depth: int,
                  expect_method: str, glm_rows: int, check_memory: bool):
    """The same GBM fit over a `hosts` mesh of `devices` (shared_tree mesh
    mode, ordered_axis_fold) against the 1-device fit forced through the
    same block count — pinned bit-identical on CPU by
    tests/test_tree_sharded.py — plus one sharded GLM fit (the Gram step of
    models/glm.py under shard_map) against its 1-device fit."""
    import math

    import jax

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models import dataset_cache
    from h2o3_tpu.models import tree as treelib
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator
    from h2o3_tpu.parallel import mesh as cloudlib

    ndev = len(devices)
    t0 = time.time()
    fr = higgs_frame(n_rows, seed=0)
    rng = np.random.default_rng(5)
    Xg = rng.normal(size=(glm_rows, 8))
    cat = rng.integers(0, 30, glm_rows)
    yg = (rng.random(glm_rows) < 1 / (1 + np.exp(
        -(Xg[:, 0] - 0.7 * Xg[:, 1] + 0.6 * (cat % 3 == 0))))).astype(int)
    gd = {f"x{i}": Xg[:, i] for i in range(8)}
    gd["c"] = np.asarray([f"c{v}" for v in range(30)], dtype=object)[cat]
    gd["y"] = np.asarray(["n", "p"], dtype=object)[yg]
    gfr = Frame.from_dict(gd, column_types={"c": "enum", "y": "enum"})

    def gbm_fit():
        dataset_cache.clear()
        est = H2OGradientBoostingEstimator(
            ntrees=ntrees, max_depth=max_depth, learn_rate=0.1, seed=42,
            score_tree_interval=max(ntrees // 4, 1))
        est.train(y="label", training_frame=fr)
        return est, est.predict(fr).vec("1").numeric_np()

    def glm_fit():
        glm = H2OGeneralizedLinearEstimator(family="binomial", lambda_=0.0)
        glm.train(y="y", training_frame=gfr)
        return glm

    s_mesh = 8 * ndev // math.gcd(8, ndev)
    keep = {k: os.environ.get(k) for k in
            ("H2O3_TREE_SHARD", "H2O3_TREE_SHARD_BLOCKS")}
    os.environ["H2O3_TREE_SHARD_BLOCKS"] = str(s_mesh)
    os.environ.pop("H2O3_TREE_SHARD", None)
    try:
        cloudlib.reset()
        cloud = cloudlib.init(list(devices))
        assert cloud.size == ndev
        lane0 = cloudlib.lane_seq()
        mesh_est, mesh_p = gbm_fit()
        plan = _fit_plan(f"gbm:1x{ntrees}t_d{max_depth}")
        _assert_plan(plan, expect_method)
        assert plan["n_devices"] == ndev and plan["n_shards"] == s_mesh, plan
        lanes = cloudlib.lane_summary(lane0)
        assert lanes.get("fences", 0) > 0, (
            "sharded fit recorded no instrumented collective fence — the "
            "io_callback of mesh.lane_mark did not run under shard_map")
        assert len(lanes["per_lane_max_ms"]) == ndev, lanes
        # code that has only seen virtual devices may put everything on
        # the first: the row-sharded arrays must really span the devices
        spans = [a.shape for a in jax.live_arrays()
                 if a.ndim >= 1 and len(a.sharding.device_set) == ndev
                 and not a.sharding.is_fully_replicated]
        assert spans, "no live row-sharded array spans all devices"
        in_use = []
        if check_memory:
            in_use = [int(d.memory_stats()["bytes_in_use"]) for d in devices]
            assert min(in_use) > 0 and max(in_use) <= 10 * min(in_use), (
                f"per-device bytes_in_use not of one order: {in_use}")
        mesh_glm = glm_fit()
        say("sharded-mesh", t0, devices=ndev, n_shards=s_mesh,
            kernel=expect_method, auc=round(float(mesh_est.auc()), 5),
            fences=lanes["fences"], sharded_arrays=len(spans),
            bytes_in_use=in_use)

        t1 = time.time()
        cloudlib.reset()
        cloudlib.init(list(devices)[:1])
        os.environ["H2O3_TREE_SHARD"] = "1"
        one_est, one_p = gbm_fit()
        _assert_plan(_fit_plan(f"gbm:1x{ntrees}t_d{max_depth}"),
                     expect_method)
        one_glm = glm_fit()
    finally:
        for k, v in keep.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        cloudlib.reset()

    # the comparison, measured before it is judged
    diffs = {}
    for k in range(len(mesh_est.model.forest)):
        for fld in treelib.Tree._fields:
            a = np.asarray(getattr(mesh_est.model.forest[k], fld))
            b = np.asarray(getattr(one_est.model.forest[k], fld))
            if not np.array_equal(a, b):
                diffs[f"forest{k}.{fld}"] = int((a != b).sum())
    p_err = float(np.max(np.abs(mesh_p - one_p)))
    hist_m = [e.get("logloss") for e in mesh_est.model.scoring_history]
    hist_1 = [e.get("logloss") for e in one_est.model.scoring_history]
    cm, c1 = mesh_glm.coef(), one_glm.coef()
    g_err = max(abs(cm[k] - c1[k]) for k in cm)
    print(f"[smoke] sharded-compare: forest_fields_differing={diffs} "
          f"max|Δp1|={p_err:.3e} scoring_history_equal={hist_m == hist_1} "
          f"glm_max|Δcoef|={g_err:.3e}", flush=True)
    assert not diffs, f"sharded forest not bit-identical to 1-device: {diffs}"
    assert p_err == 0.0, f"sharded predictions differ: {p_err}"
    assert hist_m == hist_1, (hist_m, hist_1)
    assert g_err <= 1e-4, f"sharded GLM coefficients differ: {g_err}"
    say("sharded-compare", t1, bit_identical=True, glm_coef_err=f"{g_err:.1e}")


# -- main ---------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the whole one-chip smoke; 4: only the sharded "
                         "path and what it is compared with")
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU — jax reports {len(devs)} "
              f"{devs[0].platform} device(s); refusing to run",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, jax reports {len(devs)}", file=sys.stderr)
        return 2
    import jaxlib

    import h2o3_tpu as h2o
    from h2o3_tpu.runtime import phases

    from importlib.metadata import version

    libtpu = version("libtpu")
    print(f"[smoke] device: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu} compile_cache={h2o.compile_cache_dir()}",
          flush=True)
    phases.install_listener()
    t_all = time.time()
    build_native()
    if args.chips == 4:
        phase_sharded(devs[:4], n_rows=1_000_000, ntrees=100, max_depth=6,
                      expect_method="pallas_factored", glm_rows=1_000_000,
                      check_memory=True)
        count = 4
    else:
        h2o.init()
        gbm, fr = phase_fit(
            n_rows=1_000_000, ntrees=100, max_depth=6,
            expect_method="pallas_factored", min_auc=0.84,
            pair_rows=100_000, pair_trees=10,
            pair_methods=("pallas_factored", "segment"))
        phase_score(gbm, n_rows=1_000_000)
        phase_refit(fr, ntrees=100, max_depth=6)
        phase_serve(train_rows=100_000, score_rows=2_000, n_requests=32,
                    n_threads=8, expect_method="pallas_factored")
        phase_engine(glm_rows=1_000_000, dl_rows=60_000, dl_width=784,
                     dl_hidden=[200, 200], dl_epochs=3)
        count = len(devs)
    x = phases.xla_counts()
    print(f"[smoke] all phases ok in {time.time() - t_all:.1f}s (smoke "
          f"timing) xla: compiles={x['compiles']} traces={x['traces']} "
          f"retraces={x['retraces']} "
          f"persistent_cache_hits={x['persistent_cache_hits']} "
          f"persistent_cache_misses={x['persistent_cache_misses']}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
