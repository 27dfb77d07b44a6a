#!/usr/bin/env python
"""Benchmark driver — HIGGS-like GBM training wall-clock (the BASELINE.json
flagship config: H2OGradientBoostingEstimator, 100 trees,
histogram_type=UniformAdaptive, binary response).

Prints ONE JSON line: {"metric", "value", "unit", "platform", ...}.
The real HIGGS csv is not shipped in this image; the synthetic generator
reproduces its shape (11M rows × 28 numeric features in the full set; we
default to 1M rows to keep the bench under control) with an XOR-ish nonlinear
response so the trees actually learn. Each config runs BENCH_REPEATS times
(per-config defaults below) and the best run is reported, with all runs in
the `runs` field.

A lane that targets the chip runs on whatever accelerator jax finds and
FAILS (nonzero exit, one error line) when it finds none or when the run
raises — there is no CPU fallback and no number from a CPU run under a
device metric's name. Lanes that are CPU by design (`CPU_LANES`) pin the
CPU themselves and say `"platform": "cpu"` in their record.
"""

import json
import os
import sys
import threading
import time

import numpy as np


def _note_devices() -> int:
    """Record the device count the training path actually spans — the
    `n_devices` bench axis (ISSUE 12): 1 on a lone chip or under the
    H2O3_TREE_SHARD=0 escape hatch, N on a mesh running sharded fits.
    Comparing a `higgs_gbm` line across rounds without this axis conflates
    chip speed with scale-out. Called from the bench fns (main thread,
    backend known-good) and CACHED so `_n_devices` readers — notably the
    watchdog thread escaping a HUNG backend — never call into jax, whose
    backend-init lock may be held by the stuck main thread."""
    try:
        import jax

        nd = (1 if os.environ.get("H2O3_TREE_SHARD", "").strip() == "0"
              else int(jax.device_count()))
    except Exception:
        nd = 1
    _RUN_STATE["n_devices"] = nd
    return nd


def _n_devices() -> int:
    """The cached device count (`_note_devices`); 1 before any bench fn
    has observed the backend. NEVER initializes or queries jax — safe
    from the watchdog thread while the main thread hangs in the
    backend."""
    return int(_RUN_STATE.get("n_devices") or 1)


def _note_ranks():
    """`n_ranks` bench axis (ISSUE 18): process count of the pod this fit
    spanned, None (dropped from the record) on single-process clouds —
    a pod record is distinguishable from an N-virtual-device one."""
    try:
        import jax

        nr = int(jax.process_count())
    except Exception:
        nr = 1
    _RUN_STATE["n_ranks"] = nr
    return nr if nr > 1 else None


def make_higgs_like(n_rows: int, n_feat: int = 28, seed: int = 0):
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


def bench_gbm():
    """Flagship: HIGGS-like GBM (BASELINE.json config 1)."""
    n_rows = int(os.environ.get("BENCH_ROWS", 1_000_000))
    ntrees = int(os.environ.get("BENCH_TREES", 100))
    max_depth = int(os.environ.get("BENCH_DEPTH", 6))
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    X, y = make_higgs_like(n_rows)
    names = [f"f{i}" for i in range(X.shape[1])] + ["label"]
    fr = Frame.from_numpy(np.column_stack([X, y]), names=names).asfactor("label")
    gbm = H2OGradientBoostingEstimator(
        ntrees=ntrees, max_depth=max_depth, learn_rate=0.1,
        histogram_type="UniformAdaptive", seed=42,
    )
    lane_seq0 = _lane_seq()
    t0 = time.time()
    gbm.train(y="label", training_frame=fr)
    wall = time.time() - t0
    # roofline-style utilization: the hist kernel streams 1 byte of bin
    # code per (row, feature, level, tree) update — updates/s and the
    # implied code-read GB/s make "fast" auditable against chip peak
    from h2o3_tpu.runtime import phases as _phz

    comp = _phz.snapshot().get("compute_s") or wall
    updates = n_rows * X.shape[1] * max_depth * ntrees
    return (f"higgs_gbm_{n_rows//1000}k_{ntrees}trees_wall_s", wall,
            {"auc": round(float(gbm.auc()), 5),
             "n_devices": _note_devices(),
             "n_ranks": _note_ranks(),
             "collective_skew_ms": _skew_embed(lane_seq0),
             "hist_updates_per_s": round(updates / comp),
             "hist_stream_gbps": round(updates / comp / 1e9, 3),
             # present when the fit auto-streamed (device or host budget
             # exceeded): block/spill counters beside the memory embeds
             "stream": getattr(gbm.model, "_stream_stats", None) or None})


def bench_glm():
    """Airlines-like logistic GLM, IRLS (BASELINE.json config 2): mixed
    numeric + high-cardinality categoricals, like Year/Month/Origin/Dest."""
    n_rows = int(os.environ.get("BENCH_ROWS", 1_000_000))
    rng = np.random.default_rng(0)
    import h2o3_tpu as h2o
    from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator

    dep = rng.integers(0, 2400, n_rows).astype(np.float64)
    dist = np.abs(rng.normal(800, 500, n_rows))
    origin = rng.integers(0, 100, n_rows)
    dest = rng.integers(0, 100, n_rows)
    month = rng.integers(0, 12, n_rows)
    dow = rng.integers(0, 7, n_rows)
    eff = (0.002 * (dep - 1200) + 0.4 * (origin % 7 == 0)
           - 0.3 * (dest % 11 == 0) + 0.1 * (dow >= 5))
    y = (rng.random(n_rows) < 1 / (1 + np.exp(-eff))).astype(int)
    fr = h2o.H2OFrame_from_python(
        {"DepTime": dep, "Distance": dist,
         "Origin": np.char.add("O", origin.astype(str)),
         "Dest": np.char.add("D", dest.astype(str)),
         "Month": month.astype(str), "DayOfWeek": dow.astype(str),
         "IsDepDelayed": np.where(y == 1, "YES", "NO")},
        column_types={"Origin": "enum", "Dest": "enum", "Month": "enum",
                      "DayOfWeek": "enum", "IsDepDelayed": "enum"})
    glm = H2OGeneralizedLinearEstimator(family="binomial", solver="IRLSM",
                                        lambda_=0.0)
    t0 = time.time()
    glm.train(y="IsDepDelayed", training_frame=fr)
    wall = time.time() - t0
    return (f"airlines_glm_{n_rows//1000}k_wall_s", wall,
            {"auc": round(float(glm.auc()), 5)})


def bench_dl():
    """MNIST-like DeepLearning (BASELINE.json config 3): 784→200→200→10
    rectifier MLP, sync-DP SGD replacing Hogwild; reports samples/sec."""
    n_rows = int(os.environ.get("BENCH_ROWS", 60_000))
    epochs = float(os.environ.get("BENCH_EPOCHS", 5))
    rng = np.random.default_rng(0)
    import h2o3_tpu as h2o
    from h2o3_tpu.models.deeplearning import H2ODeepLearningEstimator

    # MNIST is uint8 pixel intensities — integer-valued features, like the
    # real benchmark input (the DL path uploads them at 1 byte/value, the
    # C1Chunk-compression analog)
    X = np.floor(rng.random((n_rows, 784)) * 256).astype(np.float32)
    proto = rng.normal(size=(10, 784)).astype(np.float32)
    y = ((X / 255.0) @ proto.T
         + 0.5 * rng.normal(size=(n_rows, 10))).argmax(axis=1)
    d = {f"p{i}": X[:, i] for i in range(784)}
    d["label"] = y.astype(str)
    fr = h2o.H2OFrame_from_python(d, column_types={"label": "enum"})
    dl = H2ODeepLearningEstimator(hidden=[200, 200], activation="Rectifier",
                                  epochs=epochs, seed=1)
    t0 = time.time()
    dl.train(y="label", training_frame=fr)
    wall = time.time() - t0
    sps = n_rows * epochs / wall
    # fwd+bwd ≈ 3× the forward matmul FLOPs of the 784→200→200→10 MLP
    flops_per_sample = 3 * 2 * (784 * 200 + 200 * 200 + 200 * 10)
    return (f"mnist_dl_{n_rows//1000}k_samples_per_s", sps,
            {"wall_s": round(wall, 3), "unit_override": "samples/s",
             "gflops": round(sps * flops_per_sample / 1e9, 2)})


def bench_xgb_rank():
    """MSLR-like lambdarank XGBoost (BASELINE.json config 4):
    tree_method=tpu_hist, NDCG@10 objective over query groups."""
    n_rows = int(os.environ.get("BENCH_ROWS", 200_000))
    ntrees = int(os.environ.get("BENCH_TREES", 50))
    rng = np.random.default_rng(0)
    import h2o3_tpu as h2o
    from h2o3_tpu.models.xgboost import H2OXGBoostEstimator

    nq = n_rows // 100
    qid = np.sort(rng.integers(0, nq, n_rows))
    X = rng.normal(size=(n_rows, 40)).astype(np.float32)
    rel = np.clip((X[:, 0] + 0.5 * X[:, 1] + rng.normal(scale=0.5, size=n_rows)
                   ) * 1.2 + 1.5, 0, 4).astype(int)
    d = {f"f{i}": X[:, i] for i in range(40)}
    d["qid"] = qid.astype(np.float64)
    d["rel"] = rel.astype(np.float64)
    fr = h2o.H2OFrame_from_python(d)
    xgb = H2OXGBoostEstimator(ntrees=ntrees, max_depth=6, seed=1,
                              objective="rank:ndcg", group_column="qid")
    t0 = time.time()
    xgb.train(x=[f"f{i}" for i in range(40)], y="rel", training_frame=fr)
    wall = time.time() - t0
    ndcg = xgb.ndcg(fr)
    return (f"mslr_xgb_rank_{n_rows//1000}k_{ntrees}trees_wall_s", wall,
            {"ndcg10": round(float(ndcg), 5)})


def bench_score():
    """Deep-forest scoring on a FRESH frame: DRF 50 trees
    depth-20 on 50k rows, then warm `model_performance(new_frame)` — the
    path that taxes AutoML leaderboard_frame, calibration, and REST
    Predictions. Uses the fused subtree-fetch scorer (models/tree.py
    `predict_forest_fused`)."""
    n_rows = int(os.environ.get("BENCH_ROWS", 50_000))
    ntrees = int(os.environ.get("BENCH_TREES", 50))
    import time as _t
    import h2o3_tpu as h2o
    from h2o3_tpu.models.drf import H2ORandomForestEstimator

    X, y = make_higgs_like(n_rows, n_feat=12)
    d = {f"f{i}": X[:, i] for i in range(12)}
    d["label"] = y.astype(int).astype(str)
    fr = h2o.H2OFrame_from_python(d, column_types={"label": "enum"})
    drf = H2ORandomForestEstimator(ntrees=ntrees, max_depth=20, seed=1)
    drf.train(y="label", training_frame=fr)
    Xs, ys = make_higgs_like(n_rows, n_feat=12, seed=7)
    ds = {f"f{i}": Xs[:, i] for i in range(12)}
    ds["label"] = ys.astype(int).astype(str)
    frs = h2o.H2OFrame_from_python(ds, column_types={"label": "enum"})
    perf = drf.model_performance(frs)      # first call: table build + compile
    best = float("inf")
    for _ in range(3):
        t0 = _t.time()
        perf = drf.model_performance(frs)
        best = min(best, _t.time() - t0)
    return (f"drf_score_{n_rows//1000}k_{ntrees}t_d20_wall_s", best,
            {"auc": round(float(perf.auc()), 5)})


def bench_oversubscription():
    """Out-of-core streaming lane (ISSUE 14): a GBM fit whose packed code
    matrix is ~10× the stream budget, measured three ways in one record —
    STREAMED (`H2O3_TREE_OOC=1`, blocked host↔device double buffering),
    the IN-CORE comparator (`H2O3_TREE_OOC=0` + the matching blocked
    reduction — the bit-identical baseline), and streamed with
    gradient-based SAMPLING on (`goss=True`: later trees stream a fraction
    of the bytes). Forced-CPU, so the lane stays
    comparable round over round; the budget is forced small
    (`H2O3_STREAM_BUDGET_MB` = matrix/10) so oversubscription is real on
    any host. The record embeds streamed bytes, the resident-block peak
    (asserted ≤ budget) and block counters next to the memory embeds."""
    n_rows = int(os.environ.get("BENCH_ROWS", 120_000))
    ntrees = int(os.environ.get("BENCH_TREES", 12))
    max_depth = int(os.environ.get("BENCH_DEPTH", 5))
    n_feat = 16
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.dataset_cache import clear as _cache_clear
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    X, y = make_higgs_like(n_rows, n_feat=n_feat)
    names = [f"f{i}" for i in range(n_feat)] + ["label"]
    # 5-bit pack at the default nbins=20 → ~n·F·5/8 packed bytes; force
    # the budget to a tenth of that so the fit is genuinely out of core
    budget_mb = max(n_rows * n_feat * 5 / 8 / 1e6 / 10, 0.05)
    keys = ("H2O3_TREE_OOC", "H2O3_STREAM_BUDGET_MB", "H2O3_TREE_SHARD",
            "H2O3_TREE_SHARD_BLOCKS", "H2O3_STREAM_BLOCKS",
            "H2O3_WARM_THREAD")

    def run(env, goss=False):
        _cache_clear()
        saved = {k: os.environ.pop(k, None) for k in keys}
        os.environ.update(env)
        try:
            fr = Frame.from_numpy(np.column_stack([X, y]),
                                  names=names).asfactor("label")
            gbm = H2OGradientBoostingEstimator(
                ntrees=ntrees, max_depth=max_depth, learn_rate=0.1,
                histogram_type="UniformAdaptive", seed=42,
                score_tree_interval=max(ntrees // 4, 1),
                **(dict(goss=True, goss_start_tree=max(ntrees // 4, 1))
                   if goss else {}))
            t0 = time.perf_counter()
            gbm.train(y="label", training_frame=fr)
            return time.perf_counter() - t0, gbm
        finally:
            for k in keys:
                os.environ.pop(k, None)
                if saved.get(k) is not None:
                    os.environ[k] = saved[k]

    budget = f"{budget_mb:.3f}"
    wall_stream, m_stream = run({"H2O3_TREE_OOC": "1",
                                 "H2O3_STREAM_BUDGET_MB": budget})
    st = getattr(m_stream.model, "_stream_stats", {}) or {}
    blocks = str(st.get("blocks", 8))
    # in-core comparator shares the streamed fit's block grid so the two
    # walls bracket the same bit-identical computation
    wall_incore, _ = run({"H2O3_TREE_OOC": "0", "H2O3_TREE_SHARD": "1",
                          "H2O3_TREE_SHARD_BLOCKS": blocks})
    wall_goss, m_goss = run({"H2O3_TREE_OOC": "1",
                             "H2O3_STREAM_BUDGET_MB": budget}, goss=True)
    gs = getattr(m_goss.model, "_stream_stats", {}) or {}
    return (f"oversub_{n_rows//1000}k_{ntrees}trees_wall_s", wall_stream,
            {"auc": round(float(m_stream.auc()), 5),
             "n_devices": _note_devices(),
             "stream_budget_mb": float(budget),
             "incore_wall_s": round(wall_incore, 3),
             "goss_wall_s": round(wall_goss, 3),
             "vs_incore": round(wall_incore / wall_stream, 3),
             "goss_vs_streamed": round(wall_stream / wall_goss, 3),
             "streamed_bytes": st.get("streamed_bytes"),
             "goss_streamed_bytes": gs.get("streamed_bytes"),
             "resident_block_peak": st.get("resident_block_peak"),
             "stream": st or None})


def bench_disk_oversubscription():
    """Three-tier disk-spill lane (round 19): a GBM fit whose packed code
    matrix exceeds BOTH a forced device budget and a forced HOST budget
    (matrix/10 each), measured four ways in one record — SPILLED (host
    blocks overflow to disk files and stream back through the resuming
    reader), HOST-STREAMED (same device budget, disk tier off — the PR 14
    two-tier shape), the IN-CORE comparator on the same block grid (the
    bit-identical baseline), and GOSS-ON-DISK (sampling on: later trees
    gather compact samples and read measurably fewer spill bytes). Forced
    CPU like the oversubscription lane, so the record stays comparable
    round over round and never emits a value-0.0 line. Embeds the spill
    counters (`spilled/restored` blocks+bytes), `disk_bytes`, and the
    host-resident watermark (asserted ≤ the forced host budget by the
    tier-1 pins)."""
    n_rows = int(os.environ.get("BENCH_ROWS", 120_000))
    ntrees = int(os.environ.get("BENCH_TREES", 12))
    max_depth = int(os.environ.get("BENCH_DEPTH", 5))
    n_feat = 16
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.dataset_cache import clear as _cache_clear
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    X, y = make_higgs_like(n_rows, n_feat=n_feat)
    names = [f"f{i}" for i in range(n_feat)] + ["label"]
    budget_mb = max(n_rows * n_feat * 5 / 8 / 1e6 / 10, 0.05)
    keys = ("H2O3_TREE_OOC", "H2O3_STREAM_BUDGET_MB",
            "H2O3_STREAM_HOST_BUDGET_MB", "H2O3_TREE_OOC_DISK",
            "H2O3_TREE_SHARD", "H2O3_TREE_SHARD_BLOCKS",
            "H2O3_STREAM_BLOCKS")

    def run(env, goss=False):
        _cache_clear()
        saved = {k: os.environ.pop(k, None) for k in keys}
        os.environ.update(env)
        try:
            fr = Frame.from_numpy(np.column_stack([X, y]),
                                  names=names).asfactor("label")
            gbm = H2OGradientBoostingEstimator(
                ntrees=ntrees, max_depth=max_depth, learn_rate=0.1,
                histogram_type="UniformAdaptive", seed=42,
                score_tree_interval=max(ntrees // 4, 1),
                **(dict(goss=True, goss_start_tree=max(ntrees // 4, 1))
                   if goss else {}))
            t0 = time.perf_counter()
            gbm.train(y="label", training_frame=fr)
            return time.perf_counter() - t0, gbm
        finally:
            for k in keys:
                os.environ.pop(k, None)
                if saved.get(k) is not None:
                    os.environ[k] = saved[k]

    budget = f"{budget_mb:.3f}"
    spill_env = {"H2O3_TREE_OOC": "1", "H2O3_STREAM_BUDGET_MB": budget,
                 "H2O3_STREAM_HOST_BUDGET_MB": budget}
    wall_spill, m_spill = run(spill_env)
    st = getattr(m_spill.model, "_stream_stats", {}) or {}
    blocks = str(st.get("blocks", 8))
    # same device budget, disk tier OFF — isolates the spill tier's cost
    # from the host↔device streaming it rides on
    wall_host, m_host = run({"H2O3_TREE_OOC": "1",
                             "H2O3_STREAM_BUDGET_MB": budget,
                             "H2O3_TREE_OOC_DISK": "0"})
    hs = getattr(m_host.model, "_stream_stats", {}) or {}
    wall_incore, _ = run({"H2O3_TREE_OOC": "0", "H2O3_TREE_SHARD": "1",
                          "H2O3_TREE_SHARD_BLOCKS": blocks})
    wall_goss, m_goss = run(spill_env, goss=True)
    gs = getattr(m_goss.model, "_stream_stats", {}) or {}
    return (f"disk_oversub_{n_rows//1000}k_{ntrees}trees_wall_s",
            wall_spill,
            {"auc": round(float(m_spill.auc()), 5),
             "n_devices": _note_devices(),
             "stream_budget_mb": float(budget),
             "host_budget_mb": float(budget),
             "host_streamed_wall_s": round(wall_host, 3),
             "incore_wall_s": round(wall_incore, 3),
             "goss_wall_s": round(wall_goss, 3),
             "vs_incore": round(wall_incore / wall_spill, 3),
             "vs_host_streamed": round(wall_host / wall_spill, 3),
             "spilled_bytes": st.get("spilled_bytes"),
             "restored_bytes": st.get("restored_bytes"),
             "goss_restored_bytes": gs.get("restored_bytes"),
             "disk_bytes": st.get("disk_bytes"),
             "resident_host_peak": st.get("resident_host_peak"),
             "host_streamed_spilled_bytes": hs.get("spilled_bytes"),
             "stream": st or None,
             "goss_stream": gs or None})


def bench_estimators():
    """Fused estimator-engine lane (ISSUE 15): GLM lambda path + K-Means +
    PCA on ONE cached frame, measured fused vs the `H2O3_EST_LEGACY=1`
    comparator (host per-iteration loops: per-λ/per-Lloyd-step dispatch +
    sync + host solves, re-extracting the float matrix per fit). Forced-CPU
    (CPU by design). Acceptance: vs_seed
    (legacy wall / fused wall over the combined three-fit sequence) ≥ 3 at
    equal results (the tier-1 parity matrix pins equality).

    Default shape: 8k×12 — the dispatch-bound small/medium-fit regime the
    engine targets (an AutoML sweep's non-tree candidates), where the
    per-iteration dispatch + sync + host-solve round-trips the fused
    programs eliminate ARE the wall. At ≥24k rows on a forced-CPU host the
    per-iteration einsum compute dominates both paths and the ratio
    compresses toward 1 (recorded in docs/perf.md §7); on an accelerator
    the ratio is not measured."""
    n_rows = int(os.environ.get("BENCH_ROWS", 8_000))
    kmeans_iters = int(os.environ.get("BENCH_KMEANS_ITERS", 120))
    nlambdas = int(os.environ.get("BENCH_NLAMBDAS", 30))
    n_feat = 12
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.dataset_cache import clear as _cache_clear
    from h2o3_tpu.models.dataset_cache import snapshot as _cache_snap
    from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator
    from h2o3_tpu.models.kmeans import H2OKMeansEstimator
    from h2o3_tpu.models.pca import H2OPrincipalComponentAnalysisEstimator
    from h2o3_tpu.runtime import phases as _phz_mod

    X, y = make_higgs_like(n_rows, n_feat=n_feat)
    names = [f"f{i}" for i in range(n_feat)] + ["label"]
    xcols = names[:-1]

    def run(legacy, reps):
        best = float("inf")
        walls = auc = None
        for _ in range(reps):
            _cache_clear()
            with _forced_env("H2O3_EST_LEGACY", legacy):
                fr = Frame.from_numpy(np.column_stack([X, y]),
                                      names=names).asfactor("label")
                t0 = time.perf_counter()
                glm = H2OGeneralizedLinearEstimator(
                    family="binomial", lambda_search=True,
                    nlambdas=nlambdas, alpha=0.5, seed=42)
                glm.train(x=xcols, y="label", training_frame=fr)
                t1 = time.perf_counter()
                km = H2OKMeansEstimator(k=8, max_iterations=kmeans_iters,
                                        init="PlusPlus", seed=42)
                km.train(x=xcols, training_frame=fr)
                t2 = time.perf_counter()
                pca = H2OPrincipalComponentAnalysisEstimator(
                    k=5, transform="STANDARDIZE", pca_method="Randomized",
                    seed=42)
                pca.train(x=xcols, training_frame=fr)
                t3 = time.perf_counter()
                if t3 - t0 < best:
                    best = t3 - t0
                    walls = {"glm_s": round(t1 - t0, 3),
                             "kmeans_s": round(t2 - t1, 3),
                             "pca_s": round(t3 - t2, 3)}
                    auc = round(float(glm.auc()), 5)
        return best, walls, auc

    # best-of-2 for BOTH paths (rep 1 absorbs each path's own trace +
    # compile, so vs_seed compares warm programs with warm programs)
    _phz_mod.reset()
    wall_fused, walls_fused, auc = run(False, reps=2)
    fused_phases = _phz_mod.snapshot()
    cache = _cache_snap()
    _phz_mod.reset()
    wall_seed, walls_seed, _ = run(True, reps=2)
    _phz_mod.reset()
    return (f"estimators_{n_rows//1000}k_glm_kmeans_pca_wall_s", wall_fused,
            {"auc": auc,
             "n_devices": _note_devices(),
             "seed_wall_s": round(wall_seed, 3),
             "vs_seed": round(wall_seed / wall_fused, 2),
             "walls": walls_fused,
             "seed_walls": walls_seed,
             "std_cache": {k: cache.get(k) for k in ("std_hits",
                                                     "std_misses")},
             "phases": fused_phases or None})


from contextlib import contextmanager


@contextmanager
def _forced_env(name: str, on: bool):
    """Force a legacy-comparator env flag on or OFF for one timed rep —
    a pre-exported value must not mislabel the non-legacy reps — then
    restore whatever the operator had set."""
    prior = os.environ.get(name)
    if on:
        os.environ[name] = "1"
    else:
        os.environ.pop(name, None)
    try:
        yield
    finally:
        if prior is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = prior


def _write_ingest_csv(path: str, target_mb: float, seed: int = 0) -> int:
    """Synthesize a mixed numeric/enum CSV of ~target_mb MB (16 numeric
    columns with NA holes + 4 enum columns, quoted cells in one — the
    HIGGS-like numeric-heavy shape the flagship GBM bench ingests) and
    return the row count. Built in vectorized blocks so generation stays a
    small fraction of the parse being measured."""
    rng = np.random.default_rng(seed)
    levels = np.asarray([f"lvl{i}" for i in range(40)])
    n_num = 16
    block = 50_000
    rows = 0
    with open(path, "w") as f:
        f.write(",".join([f"n{i}" for i in range(n_num)]
                         + ["e0", "e1", "e2", "e3"]) + "\n")
        while f.tell() < target_mb * 1e6:
            cols = []
            for j in range(n_num):
                c = rng.normal(scale=10.0 ** (j % 6), size=block) \
                    .round(4).astype(str)
                c[rng.random(block) < 0.03] = "NA"   # NA-token holes
                cols.append(c)
            cols.append(rng.integers(0, 7, block).astype(str))
            cols.append(rng.choice(levels, block))
            cols.append(np.char.add("city ", rng.integers(0, 200, block).astype(str)))
            # ~1% quoted cells carrying the separator — enough to exercise
            # the RFC-4180 fallback without drowning the bulk fast path
            e2 = np.char.add("tag", rng.integers(0, 9, block).astype(str))
            qm = rng.random(block) < 0.01
            e2 = np.where(qm, np.char.add(np.char.add('"q,', e2), '"'), e2)
            cols.append(e2)
            out = cols[0]
            for c in cols[1:]:
                out = np.char.add(np.char.add(out, ","), c)
            f.write("\n".join(out.tolist()) + "\n")
            rows += block
    return rows


def bench_ingest():
    """Chunked parallel CSV ingest (ISSUE 2): ~50 MB mixed numeric/enum CSV
    in tmp; reports rows/s of the N-thread chunked parse plus the speedups
    vs a 1-thread chunked run and vs the legacy per-line tokenizer
    (acceptance: chunked ≥ 3× legacy on a multi-core host)."""
    import shutil
    import tempfile

    mb = float(os.environ.get("BENCH_INGEST_MB", 50))
    from h2o3_tpu.frame.parse import parse_csv

    tmpdir = tempfile.mkdtemp(prefix="h2o3_ingest_bench_")
    path = os.path.join(tmpdir, "ingest_bench.csv")
    try:
        nrows = _write_ingest_csv(path, mb)

        def run(nthreads=None, legacy=False, reps=2):
            best = float("inf")
            for _ in range(reps):   # best-of-reps damps scheduler noise
                with _forced_env("H2O3_INGEST_LEGACY", legacy):
                    t0 = time.perf_counter()
                    fr = parse_csv(path, nthreads=nthreads)
                    best = min(best, time.perf_counter() - t0)
                assert fr.nrow == nrows, (fr.nrow, nrows)
            return nrows / best, best

        legacy_rps, legacy_s = run(legacy=True, reps=1)
        st_rps, st_s = run(nthreads=1)
        par_rps, par_s = run(nthreads=os.cpu_count() or 1)
        size_mb = os.path.getsize(path) / 1e6
        return (f"csv_ingest_{int(round(size_mb))}mb_rows_per_s", par_rps,
                {"unit_override": "rows/s",
                 "wall_s": round(par_s, 3),
                 "rows": nrows,
                 "mb": round(size_mb, 1),
                 "mb_per_s": round(size_mb / par_s, 1),
                 "nthreads": os.cpu_count() or 1,
                 "speedup_vs_legacy": round(par_rps / legacy_rps, 2),
                 "speedup_vs_1thread": round(par_rps / st_rps, 2),
                 "legacy_rows_per_s": round(legacy_rps),
                 "onethread_rows_per_s": round(st_rps)})
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def bench_munge():
    """Vectorized munging engine (ISSUE 3): radix join + group-by + pivot
    over a ~1M-row two-key frame; reports rows/s of the vectorized merge
    plus the speedups vs the seed per-row paths (H2O3_MUNGE_LEGACY=1;
    acceptance: merge ≥ 5× legacy rows/s on a 2-core host). Pure host
    numpy (CPU by design)."""
    n_rows = int(os.environ.get("BENCH_MUNGE_ROWS",
                                os.environ.get("BENCH_ROWS", 1_000_000)))
    from h2o3_tpu.frame import rapids as R
    from h2o3_tpu.frame.frame import Frame

    rng = np.random.default_rng(0)
    levels = np.asarray([f"L{i}" for i in range(1000)])
    left = Frame.from_dict(
        {"k1": rng.choice(levels, n_rows).astype(object),
         "k2": rng.integers(0, 100, n_rows).astype(float),
         "x": rng.random(n_rows)},
        column_types={"k1": "enum"})
    m = max(n_rows // 5, 1)
    rlevels = np.asarray([f"L{i}" for i in range(1200)])
    right = Frame.from_dict(
        {"k1": rng.choice(rlevels, m).astype(object),
         "k2": rng.integers(0, 110, m).astype(float),
         "y": rng.random(m)},
        column_types={"k1": "enum"})
    plong = Frame.from_dict(
        {"i": rng.integers(0, 2000, n_rows).astype(float),
         "c": rng.integers(0, 12, n_rows).astype(float),
         "v": rng.random(n_rows)})

    def best(fn, reps=2, legacy=False):
        t_best = float("inf")
        for _ in range(reps):
            with _forced_env("H2O3_MUNGE_LEGACY", legacy):
                t0 = time.perf_counter()
                fn()
                t_best = min(t_best, time.perf_counter() - t0)
        return t_best

    do_merge = lambda: R.merge(left, right, by=["k1", "k2"], all_x=True)  # noqa: E731
    do_gb = lambda: left.group_by(["k1", "k2"]).mean("x").sum("x").get_frame()  # noqa: E731
    do_pivot = lambda: plong.pivot("i", "c", "v")  # noqa: E731
    t_merge = best(do_merge)
    t_merge_legacy = best(do_merge, reps=1, legacy=True)
    t_gb = best(do_gb)
    t_pivot = best(do_pivot)
    t_pivot_legacy = best(do_pivot, reps=1, legacy=True)
    rps = n_rows / t_merge
    legacy_rps = n_rows / t_merge_legacy
    return (f"munge_merge_{n_rows//1000}k_rows_per_s", rps,
            {"unit_override": "rows/s",
             "wall_s": round(t_merge, 3),
             "rows": n_rows,
             "vs_seed": round(rps / legacy_rps, 2),
             "legacy_rows_per_s": round(legacy_rps),
             "groupby_rows_per_s": round(n_rows / t_gb),
             "pivot_rows_per_s": round(n_rows / t_pivot),
             "pivot_vs_seed": round(t_pivot_legacy / t_pivot, 2)})


_SCALING_CHILD = r"""
import json, os, time, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", {nd})
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
sys.path.insert(0, {repo!r})
from h2o3_tpu.frame.binning import build_bins
from h2o3_tpu.models import tree as treelib
from h2o3_tpu.parallel import mesh as cloudlib

nd = {nd}
cloud = cloudlib.init(jax.devices()[:nd])
rng = np.random.default_rng(0)
N, F, B, D = {rows}, 28, 64, 6
X = rng.normal(size=(N, F)).astype(np.float32)
y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
bm = build_bins(X, nbins=B)
edges = np.full((F, B - 2), np.inf, np.float32)
for j, e in enumerate(bm.edges):
    edges[j, : len(e)] = e
rspec = P(cloudlib.ROWS_AXIS)
codes = jax.device_put(jnp.asarray(bm.codes), cloud.row_sharding())
yj = jax.device_put(jnp.asarray(y), cloud.row_sharding())
margin = jax.device_put(jnp.zeros(N, jnp.float32), cloud.row_sharding())
edges_j = jax.device_put(jnp.asarray(edges), cloud.replicated())

def train_step(codes, margin, y, edges):
    p = jax.nn.sigmoid(margin)
    g, h = p - y, p * (1 - p)
    tree, leaf_idx, _, _ = treelib.build_tree(
        codes, g, h, jnp.ones_like(g), jnp.ones(F, jnp.float32), edges,
        max_depth=D, nbins=B, min_rows=1.0, axis_name=cloudlib.ROWS_AXIS)
    return margin + 0.1 * tree.value[leaf_idx]

fn = jax.jit(shard_map(train_step, mesh=cloud.mesh,
                       in_specs=(rspec, rspec, rspec, P()),
                       out_specs=rspec))
m = fn(codes, margin, yj, edges_j)
jax.block_until_ready(m)            # compile absorb (real barrier on CPU)
reps = {reps}
t0 = time.perf_counter()
for _ in range(reps):
    m = fn(codes, m, yj, edges_j)
jax.block_until_ready(m)
print(json.dumps(dict(nd=nd, step_ms=(time.perf_counter() - t0) / reps * 1e3)))
"""


def bench_scaling():
    """1/2/4/8-virtual-device scaling curve (the BASELINE.json "1→8 host"
    metric's measurable analog here): the
    flagship GBM tree-build step over a row-sharded CPU mesh at FIXED
    global rows. The virtual devices share one host's cores, so the curve
    bounds collective/sharding overhead rather than demonstrating chip
    speedup — bit-identity across cloud sizes is pinned separately by
    tests/test_multiprocess.py."""
    import json as _json
    import subprocess
    import sys as _sys

    rows = int(os.environ.get("BENCH_ROWS", 131_072))
    reps = int(os.environ.get("BENCH_REPEATS_STEPS", 5))
    repo = os.path.dirname(os.path.abspath(__file__))
    # CPU children by construction, pinned explicitly: a parent that holds
    # an accelerator never has a child reach for it (one process per chip)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    times = {}
    for nd in (1, 2, 4, 8):
        src = _SCALING_CHILD.format(nd=nd, rows=rows, reps=reps, repo=repo)
        # own session + registered pgid so the watchdog can reap the child
        # instead of orphaning a core-burning subprocess on _exit
        p = subprocess.Popen([_sys.executable, "-c", src], env=env,
                             stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                             text=True, start_new_session=True)
        _LIVE_CHILD_PGIDS.add(p.pid)
        try:
            stdout, stderr = p.communicate(timeout=1200)
        except subprocess.TimeoutExpired:
            import signal

            try:
                os.killpg(p.pid, signal.SIGKILL)
            except OSError:
                pass
            p.communicate()
            raise RuntimeError(f"scaling child nd={nd} timed out") from None
        finally:
            _LIVE_CHILD_PGIDS.discard(p.pid)
        line = [ln for ln in stdout.splitlines() if ln.startswith("{")]
        if not line:
            raise RuntimeError(f"scaling child nd={nd} failed: {stderr[-2000:]}")
        times[nd] = _json.loads(line[-1])["step_ms"]
    ratio = times[1] / max(times[8], 1e-9)
    return ("scaling_1to8dev_step_speedup", ratio,
            {"step_ms": {str(k): round(v, 1) for k, v in times.items()},
             "rows": rows, "unit_override": "x"})


def bench_grid():
    """Parallel multi-model training (ISSUE 4): a small GBM grid with
    5-fold CV, reporting rows-trained/s of the pooled path (shared
    dataset-artifact cache + CV fold reuse + parallelism) and the speedup
    vs the sequential seed walk (H2O3_TRAIN_LEGACY=1: no cache, per-fold
    re-bin, no pool). Works forced-CPU (BENCH_PLATFORM=cpu); acceptance
    floor: vs_seed ≥ 2 on a 2-core host."""
    n_rows = int(os.environ.get("BENCH_ROWS", 20_000))
    ntrees = int(os.environ.get("BENCH_TREES", 20))
    nfolds = int(os.environ.get("BENCH_FOLDS", 5))
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.dataset_cache import clear as _cache_clear
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.models.grid import H2OGridSearch

    X, y = make_higgs_like(n_rows, n_feat=12)
    names = [f"f{i}" for i in range(12)] + ["label"]
    fr = Frame.from_numpy(np.column_stack([X, y]), names=names) \
        .asfactor("label")
    hyper = {"max_depth": [3, 4], "learn_rate": [0.1, 0.2]}
    n_combos = 4
    # oversubscribe the cores: candidates spend real wall in host python /
    # dispatch gaps, so 4 in flight beat cpu_count on a 2-core box
    par = 4
    # the per-chunk phase-accounting sync barriers serialize exactly the
    # overlap this bench measures — time both paths without them
    from h2o3_tpu.runtime import phases as _phz_mod

    acct_prior = _phz_mod.ENABLED
    _phz_mod.ENABLED = False

    def run(parallelism, legacy, reps=1):
        best = float("inf")
        for _ in range(reps):
            _cache_clear()
            with _forced_env("H2O3_TRAIN_LEGACY", legacy):
                grid = H2OGridSearch(
                    H2OGradientBoostingEstimator(
                        ntrees=ntrees, nfolds=nfolds, seed=42,
                        histogram_type="UniformAdaptive"),
                    hyper, parallelism=parallelism)
                t0 = time.perf_counter()
                grid.train(y="label", training_frame=fr)
                best = min(best, time.perf_counter() - t0)
            assert len(grid.models) == n_combos, grid.failed
        return best

    # pooled reps first (rep 1 absorbs compile into the shared cache), the
    # legacy comparator last — both measure compile-warm walls
    try:
        wall_new = run(par, legacy=False, reps=2)
        wall_seq = run(1, legacy=True, reps=1)
    finally:
        _phz_mod.ENABLED = acct_prior
    # the phase buckets accumulated across both comparator paths and all
    # reps (and without the accounting barriers) — meaningless as a
    # decomposition of the reported wall; drop them from this config
    _phz_mod.reset()
    # every candidate trains the parent fit + nfolds fold fits
    rows_trained = n_combos * (nfolds + 1) * n_rows
    rps = rows_trained / wall_new
    return (f"grid_gbm_{n_rows//1000}k_{n_combos}combo_{nfolds}cv_rows_per_s",
            rps,
            {"unit_override": "rows/s",
             "wall_s": round(wall_new, 3),
             "seq_seed_wall_s": round(wall_seq, 3),
             "vs_seed": round(wall_seq / wall_new, 2),
             "rows": n_rows, "n_models": n_combos, "nfolds": nfolds,
             "parallelism": par,
             "seed_rows_per_s": round(rows_trained / wall_seq)})


def bench_chaos():
    """Chaos smoke (ISSUE 5): loadgen against a live REST serving engine
    with 1% injected scorer device-faults (`serving.scorer`, seeded). The
    quarantine → rebuild → CPU-fallback failover path must keep p99 finite
    and the hard-error rate at zero — a crashing scorer degrades to
    latency, never to a 5xx storm. Reports p99 under fault injection plus
    the failover counters."""
    n_rows = int(os.environ.get("BENCH_ROWS", 5_000))
    threads = int(os.environ.get("BENCH_CHAOS_THREADS", 6))
    requests = int(os.environ.get("BENCH_CHAOS_REQUESTS", 40))
    fault_rate = float(os.environ.get("BENCH_CHAOS_FAULT_RATE", 0.01))
    import sys as _sys

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "deploy"))
    from loadgen import run_load

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.rest.server import start_server
    from h2o3_tpu.runtime import faults
    from h2o3_tpu.runtime.dkv import DKV
    from h2o3_tpu.serving import get_engine

    X, y = make_higgs_like(n_rows, n_feat=8)
    names = [f"f{i}" for i in range(8)] + ["label"]
    fr = Frame.from_numpy(np.column_stack([X, y]), names=names) \
        .asfactor("label")
    gbm = H2OGradientBoostingEstimator(ntrees=10, max_depth=4, seed=42)
    gbm.train(y="label", training_frame=fr)
    DKV.put("chaos_gbm", gbm.model)
    score_fr = Frame({n: fr.vec(n) for n in names[:-1]})
    score_fr.key = "chaos_frame"
    DKV.put(score_fr.key, score_fr)
    srv = start_server(port=0)
    try:
        # warm the serving path before arming faults so the measured run
        # exercises failover, not first-compile
        run_load("127.0.0.1", srv.port, "chaos_gbm", "chaos_frame",
                 threads=2, requests=2)
        faults.arm("serving.scorer", error="device", rate=fault_rate,
                   seed=int(os.environ.get("BENCH_CHAOS_SEED", 1)))
        t0 = time.time()
        stats = run_load("127.0.0.1", srv.port, "chaos_gbm", "chaos_frame",
                         threads=threads, requests=requests)
        wall = time.time() - t0
        eng = get_engine().snapshot()["totals"]
    finally:
        faults.reset()
        srv.stop()
    total = threads * requests
    err_rate = stats["errors"] / max(total, 1)
    p99 = stats["p99_ms"]
    assert p99 is not None and np.isfinite(p99), "p99 must stay finite"
    assert err_rate <= 0.01, f"error rate {err_rate} above bound"
    return (f"chaos_serving_{n_rows//1000}k_p99_ms", p99,
            {"unit_override": "ms", "wall_s": round(wall, 3),
             "completed": stats["completed"], "errors": stats["errors"],
             "shed_429": stats["shed_429"],
             "error_rate": round(err_rate, 4),
             "fault_rate": fault_rate,
             "throughput_rps": stats["throughput_rps"],
             "p50_ms": stats["p50_ms"],
             "scorer_faults": eng.get("scorer_faults", 0),
             "quarantines": eng.get("quarantines", 0),
             "fallback_scores": eng.get("fallback_scores", 0),
             "breaker_opens": eng.get("breaker_opens", 0)})


_POD_CHAOS_WORKER = """
import os, sys, json, time
sys.path.insert(0, {repo!r})
import jax
jax.distributed.initialize(
    coordinator_address=os.environ["H2O3_POD_COORD"],
    num_processes=int(os.environ["H2O3_POD_NPROCS"]),
    process_id=int(os.environ["H2O3_POD_RANK"]),
)
import numpy as np
import h2o3_tpu as h2o
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
from h2o3_tpu.runtime import supervisor
h2o.init()
fr = h2o.import_file({csv!r})
fr["y"] = fr["y"].asfactor()
g = H2OGradientBoostingEstimator(ntrees=20, max_depth=3, seed=5,
                                 score_tree_interval=5)
t0 = time.time()
err = None
try:
    g.train(x=[f"x{{i}}" for i in range(6)], y="y", training_frame=fr)
except BaseException as e:
    err = f"{{type(e).__name__}}: {{e}}"
snap = supervisor.snapshot()
info = dict(rank=jax.process_index(), error=err, wall_s=time.time() - t0,
            aborts=snap["totals"]["aborts"], last_abort=snap["last_abort"],
            last_resume=snap["last_resume"],
            resumes=snap["totals"]["resumes"])
if jax.process_index() == 0:
    with open({info!r}, "w") as f:
        json.dump(info, f, default=str)
    if err is None:
        m = g.model
        np.savez({out!r},
                 feat=np.stack([np.asarray(t.feat) for t in m.forest]),
                 bins=np.stack([np.asarray(t.bin) for t in m.forest]),
                 thr=np.stack([np.asarray(t.thr) for t in m.forest]),
                 val=np.stack([np.asarray(t.value) for t in m.forest]),
                 ntrees=m.ntrees_built,
                 sh_ll=np.asarray([ev.get("logloss")
                                   for ev in m.scoring_history], np.float64),
                 vi_gain=np.asarray([r[1] for r in m.varimp_table],
                                    np.float64))
print("rank", jax.process_index(), "done err=", err)
"""


def _pod_chaos_spawn(nproc, csv, out, info, extra_env=None, rank_env=None,
                     timeout=600):
    """Spawn an n-rank loopback pod running the pod_chaos worker. Unlike a
    test harness this does NOT assert rc==0 — rank death (rc 43) is the
    scenario. Returns per-rank (rc, output)."""
    import socket
    import subprocess

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    repo = os.path.dirname(os.path.abspath(__file__))
    script = _POD_CHAOS_WORKER.format(repo=repo, csv=csv, out=out, info=info)
    env = dict(os.environ)
    # one CPU device per rank, pinned explicitly (one process per chip:
    # the pod ranks never reach for an accelerator the parent may hold)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    env["H2O3_POD_COORD"] = coord
    env["H2O3_POD_NPROCS"] = str(nproc)
    env.update(extra_env or {})
    procs = []
    for rank in range(nproc):
        e = dict(env)
        e["H2O3_POD_RANK"] = str(rank)
        e.update((rank_env or {}).get(rank, {}))
        p = subprocess.Popen([sys.executable, "-c", script], env=e,
                             stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True,
                             start_new_session=True)
        _LIVE_CHILD_PGIDS.add(p.pid)
        procs.append(p)
    results = []
    for rank, p in enumerate(procs):
        try:
            outp, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            import signal

            for q in procs:
                try:
                    os.killpg(q.pid, signal.SIGKILL)
                except OSError:
                    pass
            raise RuntimeError(
                f"pod_chaos rank {rank} exceeded {timeout}s — the abort "
                "deadline did not fire (the hang this lane exists to "
                "catch)") from None
        finally:
            _LIVE_CHILD_PGIDS.discard(p.pid)
        results.append((p.returncode, outp))
    return results


def bench_pod_chaos():
    """Pod chaos lane (ISSUE 20): a 2-process pod GBM fit loses one rank
    mid-fit (armed ``mesh.rank_kill`` hard-exits it at a collective
    arrival), the survivor's deadline'd fence aborts within
    H2O3_FENCE_DEADLINE_S instead of hanging (never a silent rc:124), and
    a degraded single-host resume (H2O3_TREE_SHARD=1, same shard plan S)
    restores the rank-sharded checkpoints and completes BIT-IDENTICAL to
    an undisturbed comparator fit. Reports detection latency, abort
    count, and trees retrained after the kill."""
    import csv as _csv
    import json as _json
    import tempfile

    deadline_s = float(os.environ.get("BENCH_POD_DEADLINE_S", 15))
    # the rank_kill point is checked at the ONE instrumented fence per
    # scoring interval (ops/histogram ordered_axis_fold's event-loss tag),
    # so a 20-tree fit at score_tree_interval=5 sees only ~4 arrivals per
    # rank: after=2 lands the kill at the 3rd arrival (~tree 15), with the
    # tree-5/10 checkpoints already committed
    kill_after = int(os.environ.get("BENCH_POD_KILL_AFTER", 2))
    tmp = tempfile.mkdtemp(prefix="pod_chaos_")
    ckpt_dir = os.path.join(tmp, "ckpt")
    csv_p = os.path.join(tmp, "data.csv")
    rng = np.random.default_rng(7)
    Xc = rng.normal(size=(5000, 6))
    yc = (Xc[:, 0] + 0.8 * Xc[:, 1] * Xc[:, 2]
          + 0.3 * rng.normal(size=5000) > 0).astype(int)
    with open(csv_p, "w", newline="") as f:
        w = _csv.writer(f)
        w.writerow([f"x{i}" for i in range(6)] + ["y"])
        for i in range(5000):
            w.writerow([f"{v:.6f}" for v in Xc[i]] + [int(yc[i])])

    shared = {"H2O3_CKPT_DIR": ckpt_dir, "H2O3_CKPT_TREES": "5"}
    # A: undisturbed 1-process forced-shard comparator (same S as the pod)
    ref_out = os.path.join(tmp, "ref.npz")
    res = _pod_chaos_spawn(1, csv_p, ref_out, os.path.join(tmp, "ref.json"),
                           extra_env={"H2O3_TREE_SHARD": "1",
                                      "H2O3_CKPT": "0"})
    if res[0][0] != 0 or not os.path.exists(ref_out):
        raise RuntimeError(f"comparator fit failed: {res[0][1][-2000:]}")
    # B: 2-rank pod; rank 1 dies at its (kill_after+1)-th collective
    # arrival; rank 0's fences run under the supervisor deadline. The
    # doomed pod gets its OWN compilation cache — a fixed sub-directory of
    # the resolved one, emptied first: os._exit mid-write would tear the
    # shared persistent cache and the resume leg then segfaults
    # deserializing the torn entry (observed once) — cache poisoning is a
    # different failure than the one this lane pins
    import shutil

    import h2o3_tpu

    doomed_cache = os.path.join(h2o3_tpu.compile_cache_dir(),
                                "pod_chaos_doomed")
    shutil.rmtree(doomed_cache, ignore_errors=True)
    info_p = os.path.join(tmp, "chaos.json")
    t_kill = time.time()
    res = _pod_chaos_spawn(
        2, csv_p, os.path.join(tmp, "pod.npz"), info_p,
        extra_env=dict(shared, H2O3_FENCE_DEADLINE_S=str(deadline_s),
                       JAX_COMPILATION_CACHE_DIR=doomed_cache),
        rank_env={1: {"H2O3_FAULT_MESH_RANK_KILL":
                      f"error=crash,count=1,after={kill_after}"}},
        timeout=max(deadline_s * 8, 240))
    detect_wall = time.time() - t_kill
    assert res[1][0] == 43, (
        f"rank 1 should have been hard-killed (rc 43), got {res[1][0]}:"
        f"\n{res[1][1][-2000:]}")
    chaos = _json.loads(open(info_p).read()) if os.path.exists(info_p) \
        else {}
    assert chaos.get("error"), (
        "rank 0 completed despite a dead peer — the kill never landed:"
        f"\n{res[0][1][-2000:]}")
    ckpts = [f for f in os.listdir(ckpt_dir)] if os.path.isdir(ckpt_dir) \
        else []
    assert ckpts, "no fit checkpoints were committed before the kill"
    # C: degraded single-host resume on the SAME shard plan S — restores
    # the rank-sharded snapshots (rank-ordered concat) and completes
    res_out = os.path.join(tmp, "resumed.npz")
    res_info = os.path.join(tmp, "resumed.json")
    res = _pod_chaos_spawn(1, csv_p, res_out, res_info,
                           extra_env=dict(shared, H2O3_TREE_SHARD="1"))
    if res[0][0] != 0 or not os.path.exists(res_out):
        raise RuntimeError(f"degraded resume failed: {res[0][1][-2000:]}")
    rinfo = _json.loads(open(res_info).read())
    restored = int((rinfo.get("last_resume") or {}).get("restored") or 0)
    assert restored > 0, f"resume did not restore a checkpoint: {rinfo}"
    ref, got = np.load(ref_out), np.load(res_out)
    assert int(got["ntrees"]) == int(ref["ntrees"])
    for k in ("feat", "bins", "thr", "val", "vi_gain", "sh_ll"):
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
    abort = chaos.get("last_abort") or {}
    detect_s = abort.get("latency_s", None)
    return ("pod_chaos_detect_s",
            float(detect_s if detect_s is not None else detect_wall),
            {"unit_override": "s",
             "aborts": int(chaos.get("aborts") or 0),
             "abort_error": str(chaos.get("error"))[:160],
             "suspect_ranks": (abort.get("suspect_ranks") if abort
                               else None),
             "detect_wall_s": round(detect_wall, 2),
             "deadline_s": deadline_s,
             "restored_at_tree": restored,
             "trees_retrained": int(got["ntrees"]) - restored,
             "ckpt_files": len(ckpts),
             "resumed_mid_fit": int(rinfo.get("resumes") or 0),
             "bitexact": True})


def bench_serving():
    """Serving-SLO lane (ROADMAP item 4 groundwork): open-loop loadgen at
    a FIXED arrival rate against a live REST serving engine — queueing
    delay shows up as latency instead of reduced offered load, so p99 is
    an SLO verdict rather than a throughput echo. Percentiles come from
    the shared fixed latency buckets (runtime/metrics_registry
    LATENCY_MS_BOUNDS), bucket-comparable with GET /3/Metrics. Forced-CPU
    like the chaos lane (the failure-era alternative was a value-0.0
    line): the micro-batcher + admission behavior under load is
    backend-representative on CPU."""
    n_rows = int(os.environ.get("BENCH_ROWS", 2_000))
    rate = float(os.environ.get("BENCH_SERVING_RATE", 25))
    duration = float(os.environ.get("BENCH_SERVING_S", 10))
    import sys as _sys

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "deploy"))
    from loadgen import run_load, run_load_open

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.rest.server import start_server
    from h2o3_tpu.runtime import phases as _phz
    from h2o3_tpu.runtime.dkv import DKV

    X, y = make_higgs_like(n_rows, n_feat=8)
    names = [f"f{i}" for i in range(8)] + ["label"]
    fr = Frame.from_numpy(np.column_stack([X, y]), names=names) \
        .asfactor("label")
    gbm = H2OGradientBoostingEstimator(ntrees=10, max_depth=4, seed=42)
    gbm.train(y="label", training_frame=fr)
    DKV.put("slo_gbm", gbm.model)
    score_fr = Frame({n: fr.vec(n) for n in names[:-1]})
    score_fr.key = "slo_frame"
    DKV.put(score_fr.key, score_fr)
    srv = start_server(port=0)
    try:
        # closed-loop warm-up: the measured open-loop window must exercise
        # steady-state batching, not first-compile of the scorer buckets
        run_load("127.0.0.1", srv.port, "slo_gbm", "slo_frame",
                 threads=2, requests=2)
        xla0 = _phz.xla_counts()
        stats = run_load_open("127.0.0.1", srv.port, "slo_gbm",
                              "slo_frame", rate=rate, duration_s=duration)
        xla1 = _phz.xla_counts()
    finally:
        srv.stop()
    p99 = stats["p99_ms"]
    assert p99 is not None and np.isfinite(p99), "p99 must be measurable"
    err_rate = stats["errors"] / max(stats["offered"], 1)
    assert err_rate <= 0.01, f"hard errors under open load: {stats}"
    # the warm-path pin, in the artifact: a steady-state serving window
    # must not trace a single new program
    new_traces = xla1["traces"] - xla0["traces"]
    # leak canary (ISSUE 8): per-decile RSS/ledger samples → growth slope;
    # past the floor the record is TAGGED (soft fail — a leak verdict must
    # not erase the latency measurement it rode along with)
    growth = stats.get("mem_growth_bytes_per_min")
    floor_mb = float(os.environ.get("BENCH_MEM_GROWTH_FLOOR_MB_MIN", 64))
    exceeded = growth is not None and growth > floor_mb * 1e6
    # router self-description (ISSUE 16): every serving-path record embeds
    # the router's shed counters next to the memory canary — zeros when no
    # router ran in this process (peek, never instantiate)
    from h2o3_tpu.serving import peek_router

    rt = peek_router()
    rt_totals = rt.snapshot(probe=False)["totals"] if rt is not None \
        else {}
    return (f"serving_openloop_{int(rate)}rps_p99_ms", p99,
            {"unit_override": "ms",
             "rate_rps": rate, "duration_s": duration,
             "offered": stats["offered"], "completed": stats["completed"],
             "shed_429": stats["shed_429"], "dropped": stats["dropped"],
             "errors": stats["errors"],
             "achieved_rps": stats["achieved_rps"],
             "drain_s": stats["drain_s"],
             "p50_ms": stats["p50_ms"], "p95_ms": stats["p95_ms"],
             "steady_state_new_traces": new_traces,
             "mem_growth_bytes_per_min": growth,
             "ledger_growth_bytes_per_min":
                 stats.get("ledger_growth_bytes_per_min"),
             "mem_growth_exceeded": True if exceeded else None,
             "router_shed": rt_totals.get("shed", 0),
             "router_rollbacks": rt_totals.get("rollbacks", 0),
             "router_failovers": rt_totals.get("failovers", 0)})


def bench_qos():
    """Multi-tenant QoS lane (ISSUE 19, ROADMAP item 5): serving-shaped
    open-loop load CONCURRENTLY with a 4-candidate GBM grid sweep on the
    same device, three windows in one record:

      1. idle — open-loop against a quiet server: the near-idle SLO p99
      2. contended, QoS OFF — the same load while the sweep trains with
         the gate disarmed: the unbounded-blowup comparator
      3. contended, QoS ON — gate armed, SLO knob set to the idle p99:
         the headline; acceptance wants p99_on ≲ ~2× idle

    The headline metric is the QoS-ON contended p99; the record embeds
    the idle baseline, the QoS-OFF comparator, both ratios, the sweep
    walls and the qos yield/wait totals — never a value-0.0 line.
    Forced-CPU like the chaos/serving lanes. Candidates use
    score_tree_interval=1 (per-tree chunks → densest yield cadence)."""
    n_rows = int(os.environ.get("BENCH_ROWS", 2_000))
    rate = float(os.environ.get("BENCH_QOS_RATE", 15))
    window = float(os.environ.get("BENCH_QOS_WINDOW_S", 6))
    sweep_rows = int(os.environ.get("BENCH_QOS_SWEEP_ROWS", 20_000))
    candidates = int(os.environ.get("BENCH_QOS_CANDIDATES", 4))
    sweep_trees = int(os.environ.get("BENCH_QOS_SWEEP_TREES", 10))
    import sys as _sys

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "deploy"))
    from loadgen import run_concurrent_sweep, run_load, run_load_open

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.rest.server import start_server
    from h2o3_tpu.runtime import qos as _qos
    from h2o3_tpu.runtime.dkv import DKV

    X, y = make_higgs_like(n_rows, n_feat=8)
    names = [f"f{i}" for i in range(8)] + ["label"]
    fr = Frame.from_numpy(np.column_stack([X, y]), names=names) \
        .asfactor("label")
    gbm = H2OGradientBoostingEstimator(ntrees=10, max_depth=4, seed=42)
    gbm.train(y="label", training_frame=fr)
    DKV.put("qos_gbm", gbm.model)
    score_fr = Frame({n: fr.vec(n) for n in names[:-1]})
    score_fr.key = "qos_frame"
    DKV.put(score_fr.key, score_fr)
    qos_env = {k: v for k, v in os.environ.items()
               if k.startswith("H2O3_QOS")}
    srv = start_server(port=0)
    try:
        # closed-loop warm-up: the measured windows must exercise
        # steady-state batching, not first-compile of the scorer buckets
        run_load("127.0.0.1", srv.port, "qos_gbm", "qos_frame",
                 threads=2, requests=2)
        # window 1: idle SLO baseline
        os.environ.pop("H2O3_QOS", None)
        idle = run_load_open("127.0.0.1", srv.port, "qos_gbm", "qos_frame",
                             rate=rate, duration_s=window)
        idle_p99 = idle["p99_ms"]
        assert idle_p99 is not None and np.isfinite(idle_p99), \
            "idle p99 must be measurable"
        # window 2: contended with the gate DISARMED — the comparator
        off = run_concurrent_sweep(
            "127.0.0.1", srv.port, "qos_gbm", "qos_frame", rate=rate,
            window_s=window, candidates=candidates, sweep_rows=sweep_rows,
            sweep_ntrees=sweep_trees, idle=False)
        # window 3: contended with the gate ARMED, SLO = the measured
        # idle p99 (the admission throttle's hysteresis baseline)
        os.environ["H2O3_QOS"] = "1"
        os.environ.setdefault("H2O3_QOS_SLO_MS", str(idle_p99))
        _qos.reset()
        on = run_concurrent_sweep(
            "127.0.0.1", srv.port, "qos_gbm", "qos_frame", rate=rate,
            window_s=window, candidates=candidates, sweep_rows=sweep_rows,
            sweep_ntrees=sweep_trees, idle=False)
        qos_totals = _qos.totals()
    finally:
        srv.stop()
        for k in list(os.environ):
            if k.startswith("H2O3_QOS") and k not in qos_env:
                del os.environ[k]
        os.environ.update(qos_env)
    p99_off = off["contended"]["p99_ms"]
    p99_on = on["contended"]["p99_ms"]
    assert p99_off is not None and np.isfinite(p99_off), \
        f"QoS-off contended p99 must be measurable: {off['contended']}"
    assert p99_on is not None and np.isfinite(p99_on), \
        f"QoS-on contended p99 must be measurable: {on['contended']}"
    assert off["sweep"].get("done") == candidates, \
        f"QoS-off sweep must complete: {off['sweep']}"
    assert on["sweep"].get("done") == candidates, \
        f"sweep must complete under QoS (anti-starvation): {on['sweep']}"
    assert qos_totals["yields"] > 0, \
        f"gate never engaged — no yield points visited: {qos_totals}"
    err = (on["contended"]["errors"] + off["contended"]["errors"])
    offered = (on["contended"]["offered"] + off["contended"]["offered"])
    assert err / max(offered, 1) <= 0.01, \
        f"hard errors under contended load: off={off}, on={on}"
    ratio_on = p99_on / idle_p99
    ratio_off = p99_off / idle_p99
    # the ~2× SLO verdict is TAGGED, not hard-asserted: a noisy CI box
    # must not erase the measurement the verdict is ABOUT
    slo_target = float(os.environ.get("BENCH_QOS_SLO_RATIO", 2.0))
    return (f"qos_contended_{int(rate)}rps_p99_ms", p99_on,
            {"unit_override": "ms",
             "rate_rps": rate, "window_s": window,
             "candidates": candidates, "sweep_rows": sweep_rows,
             "idle_p99_ms": idle_p99,
             "idle_p50_ms": idle["p50_ms"], "idle_p95_ms": idle["p95_ms"],
             "p99_qos_off_ms": p99_off, "p99_qos_on_ms": p99_on,
             "p50_qos_on_ms": on["contended"]["p50_ms"],
             "p95_qos_on_ms": on["contended"]["p95_ms"],
             "p99_contended_over_idle_qos_on": round(ratio_on, 3),
             "p99_contended_over_idle_qos_off": round(ratio_off, 3),
             "qos_off_sweep_wall_s": off["sweep"].get("wall_s"),
             "qos_on_sweep_wall_s": on["sweep"].get("wall_s"),
             "qos_slo_ratio_target": slo_target,
             "qos_slo_exceeded": (True if ratio_on > slo_target else None),
             "qos_yields": qos_totals["yields"],
             "qos_waits_ms": qos_totals["waits_ms"],
             "qos_throttle_transitions":
                 qos_totals["throttle_transitions"],
             "completed": (on["contended"]["completed"]
                           + off["contended"]["completed"]),
             "shed_429": (on["contended"]["shed_429"]
                          + off["contended"]["shed_429"]),
             "errors": err})


# each fleet_serving replica is a real subprocess serving the same
# deterministic GBM: the router's failover claim is only meaningful across
# process boundaries (a thread-backed "replica" shares the scorer cache and
# the GIL with the router)
_FLEET_REPLICA_BODY = """
import sys, os, time
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["H2O3_REPLICA_NAME"] = {name!r}
import numpy as np
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
from h2o3_tpu.rest.server import start_server
from h2o3_tpu.runtime.dkv import DKV
rng = np.random.default_rng(7)
X = rng.normal(size=({rows}, 8))
w = rng.normal(size=8)
y = (X @ w + 0.5 * rng.normal(size={rows}) > 0).astype(float)
names = [f"f{{i}}" for i in range(8)] + ["label"]
fr = Frame.from_numpy(np.column_stack([X, y]), names=names) \\
    .asfactor("label")
gbm = H2OGradientBoostingEstimator(ntrees=10, max_depth=4, seed=42)
gbm.train(y="label", training_frame=fr)
DKV.put("fleet_gbm", gbm.model)
sf = Frame({{n: fr.vec(n) for n in names[:-1]}})
sf.key = "fleet_frame"
DKV.put(sf.key, sf)
srv = start_server(port={port})
import urllib.request
for _ in range(2):   # warm the scorer cache before the measured window
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/3/Predictions/models/fleet_gbm"
        "/frames/fleet_frame", data=b"")
    urllib.request.urlopen(req, timeout=120).read()
print("READY", flush=True)
time.sleep(600)
"""


def bench_fleet_serving():
    """Fleet-serving lane (ISSUE 16): open-loop loadgen through the
    serving ROUTER fronting 3 replica processes, with one replica killed
    mid-run via the fault registry (`serving.scorer` crash at rate 1.0 —
    every request it receives 500s deterministically). The router must
    drain the victim and retry its in-flight work on peers: USER errors
    stay 0, and the post-drain p99 is the headline. Reports the reroute
    latency blip (post/pre p99 ratio), router shed/failover/drain
    counters and the fleet-merged predict p99. Wired through the same
    watchdog/partial machinery as every lane — an assertion here raises,
    it never emits a value-0.0 line."""
    import socket
    import subprocess
    import sys as _sys
    import urllib.request

    n_rows = int(os.environ.get("BENCH_ROWS", 2_000))
    rate = float(os.environ.get("BENCH_FLEET_RATE", 15))
    window = float(os.environ.get("BENCH_FLEET_WINDOW_S", 6))
    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "deploy"))
    from loadgen import fleet_summary, run_load_open

    from h2o3_tpu.rest.server import start_server
    from h2o3_tpu.runtime import fleet
    from h2o3_tpu.serving import reset_router
    from h2o3_tpu.serving.router import RouterConfig

    repo = os.path.dirname(os.path.abspath(__file__))

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    ports = [free_port() for _ in range(3)]
    procs = []
    srv = None
    try:
        for i, port in enumerate(ports):
            procs.append(subprocess.Popen(
                [_sys.executable, "-c", _FLEET_REPLICA_BODY.format(
                    repo=repo, name=f"r{i + 1}", port=port, rows=n_rows)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=dict(os.environ, JAX_PLATFORMS="cpu")))
        for i, p in enumerate(procs):
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                line = p.stdout.readline()
                if "READY" in line:
                    break
                if p.poll() is not None:
                    raise AssertionError(
                        f"replica {i} died: {p.stdout.read()[-2000:]}")
            else:
                raise AssertionError(f"replica {i} never came up")
        fleet.reset()
        for i, port in enumerate(ports):
            fleet.register_peer(f"r{i + 1}", f"http://127.0.0.1:{port}")
        # long drain cooldown: the poisoned victim must STAY out of the
        # ring for the whole post-kill window, not resurface as a probe
        router = reset_router(RouterConfig(
            refresh_s=0.5, drain_errors=2, drain_cooldown_s=60.0,
            max_attempts=3))
        srv = start_server(port=0)
        t0 = time.time()
        pre = run_load_open("127.0.0.1", srv.port, "fleet_gbm",
                            "fleet_frame", rate=rate, duration_s=window,
                            router=True)
        # the mid-run kill, via the fault registry: every predict on the
        # victim now raises InjectedCrash (NOT a device error, so the
        # replica's CPU-fallback failover cannot mask it — it 500s)
        victim = f"http://127.0.0.1:{ports[-1]}/3/Faults"
        body = "point=serving.scorer&error=crash&rate=1.0".encode()
        with urllib.request.urlopen(urllib.request.Request(
                victim, data=body), timeout=30) as r:
            r.read()
        post = run_load_open("127.0.0.1", srv.port, "fleet_gbm",
                             "fleet_frame", rate=rate, duration_s=window,
                             router=True)
        wall = time.time() - t0
        totals = router.snapshot(probe=False)["totals"]
        fsum = fleet_summary("127.0.0.1", srv.port) or {}
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=30)
            except Exception:
                pass
        if srv is not None:
            srv.stop()
    errors = pre["errors"] + post["errors"]
    assert errors == 0, \
        f"router must hide the killed replica (pre={pre} post={post})"
    p99_pre, p99_post = pre["p99_ms"], post["p99_ms"]
    assert p99_post is not None and np.isfinite(p99_post), \
        "post-kill p99 must be measurable"
    assert totals["failovers"] >= 1 and totals["drains"] >= 1, \
        f"the kill must be visible in the router counters: {totals}"
    blip = (round(p99_post / p99_pre, 3)
            if p99_pre and p99_post is not None else None)
    return (f"fleet_serving_3rep_{int(rate)}rps_p99_ms", p99_post,
            {"unit_override": "ms", "wall_s": round(wall, 3),
             "rate_rps": rate, "window_s": window,
             "p99_pre_kill_ms": p99_pre, "p99_post_kill_ms": p99_post,
             "reroute_blip_ratio": blip,
             "offered": pre["offered"] + post["offered"],
             "completed": pre["completed"] + post["completed"],
             "errors": errors,
             "shed_429": pre["shed_429"] + post["shed_429"],
             "router_shed": totals.get("shed", 0),
             "router_retries": totals.get("retries", 0),
             "router_failovers": totals.get("failovers", 0),
             "router_drains": totals.get("drains", 0),
             "fleet_predict_p99_ms": fsum.get("predict_p99_ms"),
             "replicas_up": fsum.get("replicas_up")})


def bench_automl():
    """AutoML leaderboard (BASELINE.json config 5)."""
    n_rows = int(os.environ.get("BENCH_ROWS", 50_000))
    max_models = int(os.environ.get("BENCH_MODELS", 8))
    import h2o3_tpu as h2o
    from h2o3_tpu.automl.automl import H2OAutoML

    X, y = make_higgs_like(n_rows, n_feat=12)
    d = {f"f{i}": X[:, i] for i in range(12)}
    d["label"] = y.astype(int).astype(str)
    fr = h2o.H2OFrame_from_python(d, column_types={"label": "enum"})
    aml = H2OAutoML(max_models=max_models, seed=1, nfolds=3)
    t0 = time.time()
    aml.train(y="label", training_frame=fr)
    wall = time.time() - t0
    rows = aml.leaderboard.rows
    best_auc = (round(float(rows[0].get("auc", float("nan"))), 5)
                if rows else None)
    return (f"automl_{n_rows//1000}k_{max_models}models_wall_s", wall,
            {"n_models": len(rows), "best_auc": best_auc})


# Repeat each wall-clock config and report the BEST run (the first run also
# absorbs compilation / executable deserialization for the later ones).
DEFAULT_REPEATS = {"gbm": 3, "glm": 3, "xgb_rank": 2, "dl": 2, "automl": 2,
                   "scaling": 1, "ingest": 2, "munge": 2, "grid": 1,
                   "chaos": 1, "serving": 1, "estimators": 1,
                   "disk_oversubscription": 1, "fleet_serving": 1,
                   "qos": 1}


# lanes that are CPU by design: the scaling curve and the pod/fleet lanes run
# in CPU subprocesses, the munge bench is pure host numpy, the chaos/serving
# lanes measure FAILOVER/SLO behavior. They pin the CPU themselves and their
# record says so.
CPU_LANES = ("scaling", "munge", "chaos", "pod_chaos", "serving",
             "oversubscription", "disk_oversubscription", "estimators",
             "fleet_serving", "qos")


_EMITTED = threading.Event()
_EMIT_LOCK = threading.Lock()
# process groups the watchdog must kill before _exit (scaling-curve children)
_LIVE_CHILD_PGIDS = set()
# completed reps, shared with the watchdog: each entry is
# ((metric, value, extra), phase_snapshot, xla_delta). A watchdog that
# fires mid-round emits the best COMPLETED measurement tagged "partial"
# instead of a value-0.0 line — rounds 4–5 lost their headline number to
# exactly that silent-timeout/absent-line failure mode.
_DONE_RUNS: list = []
_RUN_STATE = {"cold": False}


def _emit(obj) -> None:
    """Print the single result JSON line exactly once (main vs watchdog)."""
    with _EMIT_LOCK:
        if not _EMITTED.is_set():
            _EMITTED.set()
            print(json.dumps(obj), flush=True)


def _observability_embed() -> dict:
    """Compile/retrace counters (runtime/phases XLA tracker) every emitted
    record carries — even a failure line attributes WHERE the wall went."""
    try:
        from h2o3_tpu.runtime import phases as _phz

        return dict(_phz.xla_counts())
    except Exception:
        return {}


def _lane_seq() -> int:
    """Fence-sequence cursor: capture before the measured fit(s) and pass
    to `_skew_embed` so the embed covers exactly the fences the
    measurement recorded — not warm-up fits or comparator reps."""
    try:
        from h2o3_tpu.parallel import mesh as _mesh

        return _mesh.lane_seq()
    except Exception:
        return 0


def _skew_embed(since_seq: int = 0):
    """Per-lane collective skew of the measured fit (ISSUE 13): p50/max
    fence skew + the worst lane, from the mesh lane-timing recorder. None
    when the fit recorded no instrumented fences (single-device lanes, or
    a fit that never ran a scoring event — the event-loss fence is the
    only instrumented collective) — like every other extra, a None embed
    is dropped from the record."""
    try:
        from h2o3_tpu.parallel import mesh as _mesh

        s = _mesh.lane_summary(since_seq)
        if s.get("fences"):
            return {"p50": s["skew_p50_ms"], "max": s["skew_max_ms"],
                    "fences": s["fences"], "worst_lane": s["worst_lane"]}
    except Exception:
        pass
    return None


def _lane_waits_embed():
    """Last observed per-lane fence waits — host-side dict only, safe
    from the watchdog thread while the backend hangs: a hung collective's
    partial/fail line names the suspect lane (the one MISSING from, or
    slowest in, the last recorded fence)."""
    try:
        from h2o3_tpu.parallel import mesh as _mesh

        return _mesh.lane_last_waits() or None
    except Exception:
        return None


def _hang_report_embed():
    """Multi-process hang attribution (ISSUE 18): the cached lane→rank
    topology plus the open fence's missing lanes name the suspect RANK of
    a hung pod collective — host dicts only, watchdog-thread safe. None
    on single-process clouds (the lane waits embed already covers those)."""
    try:
        from h2o3_tpu.parallel import mesh as _mesh

        rep = _mesh.lane_hang_report()
        if rep and rep.get("n_ranks", 1) > 1:
            return rep
    except Exception:
        pass
    return None


def _mark_suspects_down(hr) -> None:
    """Watchdog-fired pod hang (ISSUE 20 satellite): the hang report's
    suspect ranks flip their ``h2o3_fleet_peer_up`` series to 0 and a
    Timeline event names them — the failure the watchdog just attributed
    reaches the fleet scrape and the driver immediately, instead of
    waiting for the next failed peer scrape."""
    if not hr:
        return
    try:
        from h2o3_tpu.runtime import supervisor as _sup

        _sup.mark_ranks_down(list(hr.get("suspect_ranks") or []),
                             reason="bench_watchdog")
    except Exception:
        pass


def _memory_embed() -> dict:
    """Memory trajectory every emitted record carries (ISSUE 8): process
    peak RSS, the ledger's device high watermark, and the top-3 owners
    captured at the combined peak — a memory regression is attributable
    from the BENCH_*.json alone, like the phase/XLA embeds."""
    out = {}
    try:
        import resource

        out["peak_rss_bytes"] = int(resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss) * 1024   # Linux: KB
    except Exception:
        pass
    try:
        from h2o3_tpu.runtime import memory_ledger as _ml

        wm = _ml.peak()
        out["peak_device_bytes"] = int(wm["device_bytes"])
        out["peak_ledger_bytes"] = int(wm["total_bytes"])
        out["peak_owners"] = wm["top_owners"]
    except Exception:
        pass
    try:
        # out-of-core stream totals (ISSUE 14): ride next to the memory
        # embeds in every record when the streamed path ran this process
        import sys as _sys

        bs = _sys.modules.get("h2o3_tpu.models.block_store")
        if bs is not None:
            st = bs.process_totals()
            if st.get("streamed_bytes"):
                out["streamed_bytes"] = int(st["streamed_bytes"])
                out["resident_block_peak"] = int(st["resident_block_peak"])
    except Exception:
        pass
    return out


def _qos_embed() -> "dict | None":
    """Multi-tenant QoS totals every record embeds next to phases/memory
    (ISSUE 19): yields, time training waited for serving, and admission-
    throttle transitions — absent when the gate never saw traffic."""
    try:
        from h2o3_tpu.runtime import qos as _qos

        t = _qos.totals()
        if (t.get("yields") or t.get("serving_dispatches")
                or t.get("throttle_transitions")):
            return {"yields": t["yields"], "waits_ms": t["waits_ms"],
                    "throttle_transitions": t["throttle_transitions"],
                    "serving_dispatches": t["serving_dispatches"]}
    except Exception:
        pass
    return None


def _qos_gate_embed() -> "dict | None":
    """The gate-holder verdict for hang lines: which CLASS (serving or
    training) held the dispatch gate when the watchdog fired."""
    try:
        from h2o3_tpu.runtime import qos as _qos

        gs = _qos.gate_state()
        if gs.get("enabled") or gs.get("holder") != "idle":
            return gs
    except Exception:
        pass
    return None


def _fail_line(config: str, why: str) -> dict:
    nd = _n_devices()
    if nd > 1:
        # a multi-device rep that never completes is indistinguishable
        # from a hung collective (one participant never reached the
        # rendezvous) — name the suspect so the record is diagnosable
        why += (f" [n_devices={nd}: possible hung collective — "
                "H2O3_TREE_SHARD=0 forces the single-device path]")
    line = {"metric": f"{config}_unavailable", "value": 0.0, "unit": "s",
            "error": why, "platform": _RUN_STATE.get("platform"),
            "n_devices": nd}
    lw = _lane_waits_embed()
    if lw:
        # the last fence's per-lane waits: on a hung collective the lane
        # everyone was waiting on is the one with the largest wait here
        # (or the one missing from the dict entirely)
        line["lane_waits_ms"] = lw
    hr = _hang_report_embed()
    if hr:
        # pod runs: name the suspect RANK, not just the lane — the driver
        # reads `ranks.suspect_ranks` straight off the fail line
        line["ranks"] = hr
    xla = _observability_embed()
    if xla:
        line["xla"] = xla
    try:
        from h2o3_tpu.runtime import phases as _phz

        ph = _phz.snapshot()
        if ph:
            line["phases"] = ph
    except Exception:
        pass
    mem = _memory_embed()
    if mem:
        line["memory"] = mem
    qe = _qos_embed()
    if qe:
        line["qos"] = qe
    gs = _qos_gate_embed()
    if gs:
        # on a hang, name the class holding the gate — a stuck serving
        # dispatch reads very differently from a training loop that never
        # reached its next yield point
        line["qos_gate"] = gs
    return line


def _build_result(runs, snaps, xlas, partial: bool = False) -> dict:
    """Fold completed reps into the single result line: best run, its
    phase split, and its compile/trace/retrace delta (plus process
    totals) so a regression is attributable from the JSON alone."""
    metric = runs[0][0]
    higher_better = (metric.endswith(("samples_per_s", "rows_per_s"))
                     or metric.endswith("speedup"))
    values = [r[1] for r in runs]
    best_i = (max if higher_better else min)(
        range(len(values)), key=lambda i: values[i])
    metric, value, extra = runs[best_i]
    extra = dict(extra)
    result = {
        "metric": metric,
        "value": round(float(value), 3),
        "unit": extra.pop("unit_override", "s"),
        # the device the number was taken on, as jax reports it — a CPU
        # lane says "cpu" here, never a device metric from a CPU run
        "platform": _RUN_STATE.get("platform"),
        "device_kind": _RUN_STATE.get("device_kind"),
        "runs": [round(float(v), 3) for v in values],
    }
    if partial:
        result["partial"] = True
    if _RUN_STATE["cold"]:
        result["cold"] = True
    ph = snaps[best_i]
    if ph:
        # residual = wall not claimed by any accounted phase (dispatch,
        # host python between phases)
        wall = extra.get("wall_s") if "wall_s" in extra else (
            float(value) if result["unit"] == "s" else None)
        if wall is not None:
            known = sum(v for k, v in ph.items() if k.endswith("_s"))
            ph["residual_s"] = round(max(wall - known, 0.0), 3)
        result["phases"] = ph
    # per-best-rep compile-pipeline delta + monotone process totals — the
    # "compile/retrace counts from the registry" embed (ISSUE 6): a wall
    # regression is attributable (recompiled? retraced? cache-cold?)
    # without re-running anything
    if xlas and xlas[best_i]:
        result["xla"] = xlas[best_i]
    totals = _observability_embed()
    if totals:
        result["xla_process_totals"] = totals
    mem = _memory_embed()
    if mem:
        result["memory"] = mem
    qe = _qos_embed()
    if qe:
        result["qos"] = qe
    if partial:
        gs = _qos_gate_embed()
        if gs:
            result["qos_gate"] = gs
    result.update({k: v for k, v in extra.items() if v is not None})
    return result


def main():
    config = os.environ.get("BENCH_CONFIG", "gbm")
    # per-phase accounting: training drivers sync at phase boundaries and
    # record {h2d, compile, deserialize, compute, ...} so the JSON
    # decomposes wall-clock. Set before the package is first imported.
    os.environ.setdefault("H2O3_PHASE_ACCOUNTING", "1")
    # the watchdog turns a hang into ONE error line and a NONZERO exit
    # instead of the driver's silent timeout. A completed rep is reported
    # (tagged partial) with it — but the exit code still says the run did
    # not finish.
    watchdog_s = float(os.environ.get("BENCH_WATCHDOG_S", 1200))

    def _watchdog():
        if not _EMITTED.wait(timeout=watchdog_s):
            # a completed rep beats a value-0.0 line: emit the best
            # measurement so far, tagged partial, before killing anything
            if _DONE_RUNS:
                runs = [r for r, _ph, _x in _DONE_RUNS]
                snaps = [ph for _r, ph, _x in _DONE_RUNS]
                xlas = [x for _r, _ph, x in _DONE_RUNS]
                line = _build_result(runs, snaps, xlas, partial=True)
                err = (f"watchdog fired at {watchdog_s:.0f}s "
                       f"with {len(runs)} completed rep(s); "
                       "later reps abandoned")
                nd = _n_devices()
                if nd > 1:
                    # a hung COLLECTIVE rep is tagged exactly like any
                    # other hung rep: best completed measurement, partial
                    err += (f" [n_devices={nd}: possible hung collective]")
                line["error"] = err
                lw = _lane_waits_embed()
                if lw:
                    line["lane_waits_ms"] = lw
                hr = _hang_report_embed()
                if hr:
                    line["ranks"] = hr
                    _mark_suspects_down(hr)
                gs = _qos_gate_embed()
                if gs:
                    # name the class (serving/training) holding the QoS
                    # gate when the hang fired — `holder` is the verdict
                    line["qos_gate"] = gs
                _emit(line)
            else:
                _mark_suspects_down(_hang_report_embed())
                _emit(_fail_line(config,
                                 f"bench exceeded {watchdog_s:.0f}s "
                                 "watchdog with no completed rep"))
            import signal

            for pgid in list(_LIVE_CHILD_PGIDS):
                try:
                    os.killpg(pgid, signal.SIGKILL)
                except OSError:
                    pass
            os._exit(1)

    threading.Thread(target=_watchdog, daemon=True).start()
    forced = os.environ.get("BENCH_PLATFORM")  # e.g. "cpu" for local checks
    import jax

    if config in CPU_LANES or forced:
        jax.config.update("jax_platforms", forced or "cpu")
    dev0 = jax.devices()[0]
    _RUN_STATE["platform"] = dev0.platform
    _RUN_STATE["device_kind"] = dev0.device_kind
    if dev0.platform == "cpu" and config not in CPU_LANES and forced != "cpu":
        # a lane that targets the chip and finds none FAILS — a CPU timing
        # is never written under a device metric's name
        _emit(_fail_line(config, "no accelerator: jax reports only "
                                 f"{dev0.platform} devices"))
        sys.exit(1)
    # importing the package resolves the compile cache
    # (h2o3_tpu.compile_cache_dir). BENCH_COLD: the cache is disabled for
    # this run, so every program goes through trace+compile — the cold
    # start a first-time user pays.
    from h2o3_tpu.runtime import phases as _phz

    cold = os.environ.get("BENCH_COLD") == "1"
    if cold:
        jax.config.update("jax_enable_compilation_cache", False)

    _phz.install_listener()
    fn = {"gbm": bench_gbm, "glm": bench_glm, "dl": bench_dl,
          "xgb_rank": bench_xgb_rank, "automl": bench_automl,
          "score": bench_score, "scaling": bench_scaling,
          "ingest": bench_ingest, "munge": bench_munge,
          "grid": bench_grid, "chaos": bench_chaos,
          "pod_chaos": bench_pod_chaos,
          "serving": bench_serving,
          "oversubscription": bench_oversubscription,
          "disk_oversubscription": bench_disk_oversubscription,
          "estimators": bench_estimators,
          "fleet_serving": bench_fleet_serving,
          "qos": bench_qos}[config]
    # cold is strictly one run: repeats within a process share the live
    # executable cache, so any second run would be warm yet labeled cold
    repeats = 1 if cold else int(os.environ.get(
        "BENCH_REPEATS", DEFAULT_REPEATS.get(config, 1)))
    _RUN_STATE["cold"] = cold
    runs, snaps, xlas = [], [], []
    try:
        for _ in range(max(repeats, 1)):
            _phz.reset()
            xla0 = _phz.xla_counts()
            run = fn()
            xla1 = _phz.xla_counts()
            runs.append(run)
            snaps.append(_phz.snapshot())
            xlas.append({k: xla1[k] - xla0.get(k, 0) for k in xla1})
            # watchdog-visible progress: a timeout after this point emits
            # this rep instead of a value-0.0 line
            _DONE_RUNS.append((runs[-1], snaps[-1], xlas[-1]))
    except Exception as e:
        import traceback

        traceback.print_exc(file=sys.stderr)
        if runs:
            # completed reps ARE a measurement: report the best of them,
            # tagged partial, with the error — and still exit nonzero
            line = _build_result(runs, snaps, xlas, partial=True)
            line["error"] = (f"rep {len(runs) + 1} raised: {e!r}; "
                             "earlier rep(s) reported")
        else:
            line = _fail_line(config, f"bench raised: {e!r}")
        _emit(line)
        sys.exit(1)
    _emit(_build_result(runs, snaps, xlas))


if __name__ == "__main__":
    main()
