"""Sharded end-to-end tree training (ISSUE 12) — shard-boundary
correctness and the N-device bit-stability contract.

The design under test: every row reduction of the fused tree path
(histograms, final leaf totals, scoring-event loss) runs as S ordered
block partials merged by `ops.histogram.ordered_axis_fold` (all_gather +
left-to-right fold), so the reduction tree is a function of S alone — an
8-device `shard_map` fit and a 1-device fit forced through the same
structure (``H2O3_TREE_SHARD=1``) are BIT-IDENTICAL, and the forced-CPU
lane exercises the identical sharded code path via the t5x-style
`mesh.shard_call` wrapper (plain call at 1 device, shard_map on a mesh).

Tier-1 section: kernel-level pins on the 8-virtual-device CPU mesh the
conftest provides (cheap — no estimator-driver compiles). The whole-fit
estimator parity matrix (GBM early-stop discard, DRF OOB/mtries,
monotone, CV fold reuse, escape hatch, observability surfaces) runs as
``slow`` — and the MULTICHIP lane (`__graft_entry__.dryrun_multichip`)
independently pins a complete sharded fit bit-stable every round.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from h2o3_tpu.models import shared_tree
from h2o3_tpu.models import tree as treelib
from h2o3_tpu.ops import histogram, packing
from h2o3_tpu.parallel import mesh as cloudlib

from conftest import make_classification


@pytest.fixture()
def _shard_env():
    """Isolate the sharding env knobs per test."""
    keys = ("H2O3_TREE_SHARD", "H2O3_TREE_SHARD_BLOCKS",
            "H2O3_HIST_METHOD")
    prior = {k: os.environ.pop(k, None) for k in keys}
    yield
    for k, v in prior.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


# -- shard plan rules (pure host logic) -------------------------------------

def test_shard_plan_rules(_shard_env):
    tp = {}
    assert shared_tree._shard_plan(8, False, tp) == ("mesh", 8)
    assert shared_tree._shard_plan(1, False, tp) == ("off", 0)
    # ndev must divide S: non-power-of-two meshes raise S to the lcm
    assert shared_tree._shard_plan(6, False, tp) == ("mesh", 24)
    os.environ["H2O3_TREE_SHARD"] = "0"          # escape hatch: never shard
    assert shared_tree._shard_plan(8, False, tp) == ("off", 0)
    os.environ["H2O3_TREE_SHARD"] = "1"          # forced blocks at 1 device
    assert shared_tree._shard_plan(1, False, tp) == ("blocks", 8)
    os.environ.pop("H2O3_TREE_SHARD", None)
    os.environ["H2O3_TREE_SHARD_BLOCKS"] = "16"
    assert shared_tree._shard_plan(4, False, tp) == ("mesh", 16)
    os.environ.pop("H2O3_TREE_SHARD_BLOCKS", None)
    # lossguide keeps the psum path, but the escape hatch overrides it (a
    # broken mesh must not run ITS collectives either)...
    os.environ["H2O3_TREE_SHARD"] = "0"
    assert shared_tree._shard_plan(8, False, tp) == ("off", 0)
    assert shared_tree._shard_plan(
        8, False, {"grow_policy": "lossguide"}) == ("off", 0)
    # ...while multi-process clouds ignore it (their rows live on other
    # processes — "one device" is not an option)
    assert shared_tree._shard_plan(8, True, tp)[0] == "mesh_psum"
    os.environ.pop("H2O3_TREE_SHARD", None)
    assert shared_tree._shard_plan(
        8, False, {"grow_policy": "lossguide"})[0] == "mesh_psum"


def test_fit_plan_records_shards(_shard_env):
    """The /3/Profiler tree fold's per-fit plans carry the shard geometry
    (n_shards / n_devices / pack_bits) — the ISSUE 12 observability
    satellite."""
    plan = histogram.record_fit_plan(
        "test:sharded", [("d0", 1), ("d1", 1)], 21, "auto",
        pack_bits=5, n_shards=8, n_devices=8)
    assert plan["n_shards"] == 8 and plan["n_devices"] == 8
    assert plan["pack_bits"] == 5
    from h2o3_tpu.runtime import profiler

    fold = profiler.tree_stats()
    assert fold["plans"][-1]["n_shards"] == 8


# -- kernel-level shard invariance ------------------------------------------

def test_blocked_histograms_shard_invariant(cloud8, _shard_env):
    """8 devices × 1 block/device == 1 device × 8 blocks, bitwise — for the
    in-graph segment kernel, packed and dense. The plain single-fold path stays
    untouched (last-ulp different), which is exactly why the sharded lane
    needs its own canonical reduction."""
    rng = np.random.default_rng(2)
    N, F, B, L, S = 256, 4, 16, 4, 8
    codes = rng.integers(0, B, (N, F)).astype(np.uint8)
    node = rng.integers(0, L, N).astype(np.int32)
    g = rng.normal(size=N).astype(np.float32)
    h = rng.random(N).astype(np.float32)
    w = (rng.random(N) > 0.1).astype(np.float32)
    bits = packing.pack_bits_for(B, N)
    pk = packing.pack_host(codes, bits)
    rspec = P(cloudlib.ROWS_AXIS)

    for codes_in, pb in ((codes, 0), (pk, bits)):
        def inner_mesh(c, n_, g_, h_, w_):
            return histogram.build_histograms(
                c, n_, g_, h_, w_, L, B, method="segment",
                axis_name=cloudlib.ROWS_AXIS, pack_bits=pb,
                n_shard_blocks=1)

        fn8 = jax.jit(cloudlib.shard_call(
            inner_mesh, cloud8, in_specs=(rspec,) * 5, out_specs=P(),
            check_vma=False))
        rs = cloud8.row_sharding()
        h8 = np.asarray(fn8(
            jax.device_put(jnp.asarray(codes_in), rs),
            jax.device_put(jnp.asarray(node), rs),
            jax.device_put(jnp.asarray(g), rs),
            jax.device_put(jnp.asarray(h), rs),
            jax.device_put(jnp.asarray(w), rs)))
        got = np.asarray(jax.jit(
            lambda c, n_, g_, h_, w_: histogram.build_histograms(
                c, n_, g_, h_, w_, L, B, method="segment", pack_bits=pb,
                n_shard_blocks=S)
        )(jnp.asarray(codes_in), jnp.asarray(node), jnp.asarray(g),
          jnp.asarray(h), jnp.asarray(w)))
        assert np.array_equal(h8, got), pb


def test_build_tree_sharded_parity_combined(cloud8, _shard_env):
    """One packed fused `build_tree` under shard_map (8 devices) vs the
    identical call with 8 local blocks on one device: bit-equal trees,
    leaf assignment, gains and covers — with mtries column sampling,
    monotone constraints and elastic-net regularization ALL active, a
    zero-weight pad tail (rows not divisible by the mesh are padded
    result-neutral through the collective), and a shard whose weights
    leave a SINGLE live row (shard-boundary degenerate case). Weight
    patterns are data, not shape — one compiled program pair covers every
    case."""
    rng = np.random.default_rng(4)
    N, F, B, D, S = 512, 5, 16, 3, 8
    codes = rng.integers(0, B, (N, F)).astype(np.uint8)
    g = rng.normal(size=N).astype(np.float32)
    h = (rng.random(N).astype(np.float32) + 0.1)
    w = np.ones(N, np.float32)
    w[-40:] = 0.0              # "979 rows on a 64-row grid" pad tail
    w[448:512] = 0.0           # shard 7 of the 8-device layout...
    w[450] = 1.0               # ...holds exactly ONE live row
    fm = np.ones(F, np.float32)
    edges = np.sort(rng.normal(size=(F, B - 2)), axis=1).astype(np.float32)
    mono = np.zeros(F, np.float32)
    mono[0] = 1.0
    bits = packing.pack_bits_for(B, N)
    pk = packing.pack_host(codes, bits)
    key = np.asarray(jax.random.PRNGKey(9))

    def builder(axis, nblocks):
        def fn(c, g_, h_, w_, k_):
            return treelib.build_tree(
                c, g_, h_, w_, jnp.asarray(fm), jnp.asarray(edges), key=k_,
                max_depth=D, nbins=B, min_rows=2.0,
                reg_lambda=0.5, reg_alpha=0.25,
                mtries_rate=jnp.float32(0.6), monotone=jnp.asarray(mono),
                pack_bits=bits,
                axis_name=axis, n_shard_blocks=nblocks)
        return fn

    rspec = P(cloudlib.ROWS_AXIS)
    fn8 = jax.jit(cloudlib.shard_call(
        builder(cloudlib.ROWS_AXIS, 1), cloud8,
        in_specs=(rspec,) * 4 + (P(),),
        out_specs=(treelib.Tree(P(), P(), P(), P(), P()), rspec, P(), P()),
        check_vma=False))
    rs = cloud8.row_sharding()
    out8 = fn8(jax.device_put(jnp.asarray(pk), rs),
               jax.device_put(jnp.asarray(g), rs),
               jax.device_put(jnp.asarray(h), rs),
               jax.device_put(jnp.asarray(w), rs),
               jnp.asarray(key))
    out1 = jax.jit(builder(None, S))(
        jnp.asarray(pk), jnp.asarray(g), jnp.asarray(h), jnp.asarray(w),
        jnp.asarray(key))
    for a, b in zip(jax.tree.leaves(out8), jax.tree.leaves(out1)):
        assert np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)


# -- whole-fit estimator parity (slow lane; the MULTICHIP dryrun pins the
#    same contract every round) --------------------------------------------

_FIT_N, _FIT_F = 1000, 6
_FIT_X, _FIT_Y = make_classification(n=_FIT_N, f=_FIT_F, seed=7)
_FIT_NAMES = [f"f{i}" for i in range(_FIT_F)] + ["label"]


def _frame():
    from h2o3_tpu.frame.frame import Frame

    return Frame.from_numpy(np.column_stack([_FIT_X, _FIT_Y]),
                            names=_FIT_NAMES).asfactor("label")


def _fit(builder, ndev, shard=None):
    from h2o3_tpu.models import dataset_cache

    dataset_cache.clear()
    cloudlib.reset()
    if shard is None:
        os.environ.pop("H2O3_TREE_SHARD", None)
    else:
        os.environ["H2O3_TREE_SHARD"] = shard
    cloudlib.init(jax.devices()[:ndev])
    est = builder()
    est.train(y="label", training_frame=_frame())
    _ = est.model.forest          # host-materialize before the cloud resets
    os.environ.pop("H2O3_TREE_SHARD", None)
    return est


def _assert_bitexact(a, b):
    assert a.model.ntrees_built == b.model.ntrees_built
    for k in range(len(a.model.forest)):
        for f in treelib.Tree._fields:
            assert np.array_equal(
                np.asarray(getattr(a.model.forest[k], f)),
                np.asarray(getattr(b.model.forest[k], f))), (k, f)


@pytest.mark.slow
def test_sharded_gbm_fit_bitstable_with_early_stop(_shard_env):
    """The headline pin: a WHOLE 8-device GBM fit — packed codes, fused
    split search, overlapped chunk scoring, a FIRING early stop that
    discards the speculative chunk coherently across shards — is
    bit-identical to the 1-device fused path running the same canonical
    reduction (H2O3_TREE_SHARD=1): forests, scoring history, training
    metrics, predictions. 1000 rows on an 8×8-row grid also pins pad-row
    neutrality through the collective merge."""
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    mk = lambda: H2OGradientBoostingEstimator(  # noqa: E731
        ntrees=8, max_depth=4, seed=42, score_tree_interval=2,
        stopping_rounds=1, stopping_tolerance=0.5)
    g8 = _fit(mk, 8)
    p8 = g8.predict(_frame()).vec("1").numeric_np()
    g1 = _fit(mk, 1, shard="1")
    p1 = g1.predict(_frame()).vec("1").numeric_np()
    assert g8.model.ntrees_built < 8, "the stopper must fire for this pin"
    _assert_bitexact(g8, g1)
    assert np.array_equal(p8, p1)
    h8 = [e.get("logloss") for e in g8.model.scoring_history]
    h1 = [e.get("logloss") for e in g1.model.scoring_history]
    assert h8 == h1
    np.testing.assert_array_equal(g8.model.training_metrics.logloss(),
                                  g1.model.training_metrics.logloss())
    # and the default (unsharded) 1-device fused path agrees to float dust
    g0 = _fit(mk, 1)
    p0 = g0.predict(_frame()).vec("1").numeric_np()
    np.testing.assert_allclose(p0, p8, rtol=3e-5, atol=2e-6)


@pytest.mark.slow
def test_sharded_drf_and_monotone_fits_bitstable(_shard_env):
    """DRF (per-node mtries + row sampling + OOB scoring) and GBM monotone
    constraints through the sharded path match the forced-1-device lane
    bit-for-bit."""
    from h2o3_tpu.models.drf import H2ORandomForestEstimator
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    mkd = lambda: H2ORandomForestEstimator(  # noqa: E731
        ntrees=6, max_depth=4, seed=42, score_tree_interval=3)
    _assert_bitexact(_fit(mkd, 8), _fit(mkd, 1, shard="1"))
    mkm = lambda: H2OGradientBoostingEstimator(  # noqa: E731
        ntrees=5, max_depth=4, seed=42, monotone_constraints={"f0": 1})
    _assert_bitexact(_fit(mkm, 8), _fit(mkm, 1, shard="1"))


@pytest.mark.slow
def test_sharded_cv_fold_reuse_bitstable(_shard_env):
    """CV fold reuse composes with sharding: fold fits slice the parent's
    binned codes, inherit its padded row bucket, and train sharded — the
    cross-validated parent and the CV metrics are bit-identical across
    cloud sizes."""
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    mk = lambda: H2OGradientBoostingEstimator(  # noqa: E731
        ntrees=4, max_depth=4, seed=42, nfolds=2,
        keep_cross_validation_predictions=True)
    c8 = _fit(mk, 8)
    c1 = _fit(mk, 1, shard="1")
    _assert_bitexact(c8, c1)
    np.testing.assert_array_equal(
        c8.model.cross_validation_metrics.logloss(),
        c1.model.cross_validation_metrics.logloss())


@pytest.mark.slow
def test_shard_escape_hatch_and_observability(_shard_env):
    """H2O3_TREE_SHARD=0 on an 8-device cloud bypasses the mesh entirely —
    bit-identical to a plain 1-device fit (the broken-mesh escape hatch).
    A sharded fit's observability: the kernel plan records
    n_shards/n_devices/pack_bits, dispatch counters reach the Prometheus
    scrape, and collective wait time lands in the runtime/phases
    ``collective`` bucket."""
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.runtime import metrics_registry, phases

    mk = lambda: H2OGradientBoostingEstimator(  # noqa: E731
        ntrees=4, max_depth=4, seed=42)
    _assert_bitexact(_fit(mk, 8, shard="0"), _fit(mk, 1))
    phases.reset()
    _fit(mk, 8)
    stats = histogram.kernel_stats()
    plan = stats["plans"][-1]
    assert plan["n_shards"] == 8 and plan["n_devices"] == 8
    assert plan["pack_bits"] in (4, 5, 6)
    assert "h2o3_tree_hist_dispatch_total" in \
        metrics_registry.prometheus_text()
    # the collective bucket records fence wait time (unrounded: a tiny
    # CPU-mesh fit's waits are µs-scale and round to 0.0 in the snapshot)
    assert phases.totals(("collective",)) > 0.0, phases.snapshot()


@pytest.mark.slow
def test_sharded_device_codes_cached_per_shard_layout(_shard_env):
    """The dataset cache's device layer keys the shard layout: an 8-shard
    fit reuses the row-sharded packed artifact on a repeat candidate
    (device hit), and a 1-device consumer never shares it."""
    from h2o3_tpu.models import dataset_cache
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    dataset_cache.clear()
    cloudlib.reset()
    os.environ.pop("H2O3_TREE_SHARD", None)
    cloudlib.init(jax.devices())
    fr = _frame()
    for lr in (0.1, 0.2):         # same (frame, x, nbins): second fit hits
        est = H2OGradientBoostingEstimator(ntrees=2, max_depth=3, seed=1,
                                           learn_rate=lr)
        est.train(y="label", training_frame=fr)
    snap = dataset_cache.snapshot()
    assert snap["device_hits"] >= 1, snap


# -- per-lane collective skew + straggler detection (ISSUE 13) ---------------

def test_lane_recorder_flush_and_straggler_detection(monkeypatch):
    """Host-level contract of the lane-timing recorder: 8 concurrent
    arrival callbacks flush one fence record; a lane whose arrival is
    delayed by the `mesh.lane_delay` fault persistently past the median
    fires the straggler counter for EXACTLY that lane. Runs the real
    callback path (faults.check inside _lane_arrive_cb) without device
    programs — tier-1 cheap."""
    import threading

    from h2o3_tpu.runtime import faults, metrics_registry as registry

    # explicit 8-device cloud: the fence flushes when every lane of the
    # CURRENT cloud has reported (the session cloud8 fixture's global
    # cloud is reset between tests — init fresh, don't depend on order)
    cloudlib.init(jax.devices())
    cloudlib.lane_reset()
    monkeypatch.setenv("H2O3_STRAGGLER_FENCES", "2")
    faults.arm("mesh.lane_delay", error="none", latency_ms=150, lane=2)
    try:
        for _fence in range(3):
            ts = [threading.Thread(target=cloudlib._lane_arrive_cb,
                                   args=("t", lane)) for lane in range(8)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        st = cloudlib.lane_stats()
        assert st["fences"] == 3
        rec = st["records"][-1]
        assert len(rec["waits_ms"]) == 8
        worst = max(rec["waits_ms"], key=rec["waits_ms"].get)
        assert worst == "2", rec
        assert rec["skew_ms"] >= 100
        # fired once per streak (at the 2nd consecutive flagged fence),
        # for the delayed lane ONLY
        assert st["stragglers"] == {"2": 1}, st
        c = registry.get("h2o3_stragglers")
        assert c.value("2") >= 1
        # fence + skew surfaces reached the scrape
        text = registry.prometheus_text()
        assert 'h2o3_stragglers_total{lane="2"}' in text
        assert "h2o3_collective_skew_ms_bucket" in text
    finally:
        faults.reset()
        cloudlib.lane_reset()


def test_straggler_fires_on_two_lane_mesh(monkeypatch):
    """Lower-median threshold: with only 2 lanes the healthy lane sets
    the baseline — the upper middle would be the straggler's own wait
    (threshold = factor x itself, unfirable)."""
    import threading

    from h2o3_tpu.runtime import faults

    cloudlib.init(jax.devices()[:2])
    cloudlib.lane_reset()
    monkeypatch.setenv("H2O3_STRAGGLER_FENCES", "2")
    faults.arm("mesh.lane_delay", error="none", latency_ms=120, lane=1)
    try:
        for _fence in range(2):
            ts = [threading.Thread(target=cloudlib._lane_arrive_cb,
                                   args=("t", lane)) for lane in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        assert cloudlib.lane_stats()["stragglers"] == {"1": 1}
    finally:
        faults.reset()
        cloudlib.lane_reset()


def test_lane_summary_and_last_waits():
    """lane_summary folds only the fences after `since_seq` (the per-fit
    attribution window) and lane_last_waits is the watchdog's host-only
    read."""
    import threading

    cloudlib.init(jax.devices())
    cloudlib.lane_reset()
    try:
        def fence():
            ts = [threading.Thread(target=cloudlib._lane_arrive_cb,
                                   args=("t", lane)) for lane in range(8)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()

        fence()
        seq0 = cloudlib.lane_seq()
        assert seq0 == 1
        fence()
        s = cloudlib.lane_summary(seq0)
        assert s["fences"] == 1            # only the post-seq0 fence
        assert set(s["per_lane_max_ms"]) == {str(i) for i in range(8)}
        lw = cloudlib.lane_last_waits()
        assert len(lw) == 8 and all(isinstance(k, int) for k in lw)
        # a hung fence (lanes 6,7 never arrive) takes priority in the
        # watchdog read: the MISSING lanes are the suspects
        for lane in range(6):
            cloudlib._lane_arrive_cb("t", lane)
        hung = cloudlib.lane_last_waits()
        assert set(hung) == set(range(6)), hung
    finally:
        cloudlib.lane_reset()


@pytest.mark.slow
def test_injected_lane_delay_fires_straggler_on_exact_lane(_shard_env,
                                                          monkeypatch):
    """The acceptance pin: a WHOLE sharded GBM fit with an injected
    `mesh.lane_delay` fault on lane 5 fires the straggler detector on
    exactly lane 5, deterministically; the fit plan carries the skew
    summary naming the same lane."""
    from h2o3_tpu.models import dataset_cache
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.runtime import faults, metrics_registry as registry

    dataset_cache.clear()
    cloudlib.reset()
    cloudlib.init(jax.devices())
    cloudlib.lane_reset()
    monkeypatch.setenv("H2O3_STRAGGLER_FENCES", "2")
    before = registry.get("h2o3_stragglers")
    before5 = before.value("5") if before else 0.0
    faults.arm("mesh.lane_delay", error="none", latency_ms=120, lane=5)
    try:
        est = H2OGradientBoostingEstimator(ntrees=8, max_depth=3, seed=3,
                                           score_tree_interval=1)
        est.train(y="label", training_frame=_frame())
        st = cloudlib.lane_stats()
        assert st["fences"] >= 3, st
        assert set(st["stragglers"]) == {"5"}, st
        c = registry.get("h2o3_stragglers")
        assert c.value("5") == before5 + 1
        plan = histogram.kernel_stats()["plans"][-1]
        skew = plan.get("collective_skew")
        assert skew and skew["worst_lane"] == 5, plan
        assert skew["skew_max_ms"] >= 100
        assert skew["fences"] == st["fences"]
    finally:
        faults.reset()
        cloudlib.lane_reset()
        dataset_cache.clear()


@pytest.mark.slow
def test_lane_timing_quiet_without_fault_and_off_hot_path(_shard_env):
    """Without injected latency an 8-device fit records fences whose skew
    is benign and fires NO straggler; fences count scoring events, not
    levels (the instrument must stay off the per-level hot path)."""
    from h2o3_tpu.models import dataset_cache
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    dataset_cache.clear()
    cloudlib.reset()
    cloudlib.init(jax.devices())
    cloudlib.lane_reset()
    try:
        ntrees, interval = 8, 2
        est = H2OGradientBoostingEstimator(ntrees=ntrees, max_depth=3,
                                           seed=3,
                                           score_tree_interval=interval)
        est.train(y="label", training_frame=_frame())
        st = cloudlib.lane_stats()
        assert st["fences"] >= 1
        # one instrumented fence per scoring event (+ warm-up), NEVER one
        # per level: depth-3 x 8 trees would be >= 24 level passes
        assert st["fences"] <= ntrees // interval + 2, st
        assert st["stragglers"] == {}, st
    finally:
        cloudlib.lane_reset()
        dataset_cache.clear()
