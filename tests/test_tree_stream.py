"""Out-of-core streamed GBM/DRF (ISSUE 14) — block streaming under the
memory ledger's budget, bit-exactness vs the in-core fit, and GOSS.

Pins: (1) a streamed fit (sampling OFF) is BIT-IDENTICAL to the in-core
fit sharing its block count S — forest, varimp, scoring history,
early-stop tree count, CV metrics, predictions — across GBM/DRF ×
early-stop × CV fold reuse × host-kernel lane; (2) the `H2O3_TREE_OOC=0`
escape hatch is pinned bit-equal to a plain fit; (3) BlockStore device
eviction ORDER lands in the timeline (cap = LRU, pressure = shed keeps
only the double buffer), mirroring test_memory_ledger's LRU pin; (4) the
stream is observable — per-fit `_stream_stats`, the plan's `stream` fold,
the `h2d_stream` phase bucket and the Prometheus counters; (5) GOSS is
deterministic per seed, streams FEWER bytes than the unsampled fit, and
rejects invalid configs; (6) the disk tier (round 19) — spill LRU ORDER
via timeline events, evict-then-restore keeps the host watermark under
budget, restores are bit-identical (also mid-read under an armed
`persist.read` fault), spilled copies are kept, and a spilled fit is
bit-identical to in-core across GBM early-stop × DRF × CV fold reuse,
with `H2O3_TREE_OOC_DISK=0` pinning the host-only escape hatch. The
oversubscribed whole-fit (matrix ≥10× the budget, resident watermark
under budget) and the mesh-oversubscription pin run as ``slow`` (tier-1
budget is tight)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.models import block_store as bslib
from h2o3_tpu.models import tree as treelib
from h2o3_tpu.ops import histogram, packing
from h2o3_tpu.runtime import memory_ledger as ml
from h2o3_tpu.runtime.timeline import Timeline

from conftest import make_classification

_ENV_KEYS = ("H2O3_TREE_OOC", "H2O3_STREAM_BLOCKS", "H2O3_STREAM_BUDGET_MB",
             "H2O3_TREE_SHARD", "H2O3_TREE_SHARD_BLOCKS",
             "H2O3_HIST_METHOD",
             "H2O3_MEM_BUDGET_MB", "H2O3_MEM_EVICT_PRESSURE",
             "H2O3_STREAM_HOST_BUDGET_MB", "H2O3_TREE_OOC_DISK",
             "H2O3_SPILL_DIR")

# the streamed fit and its in-core comparator share S=4 — the reduction
# tree is a function of S alone (PR 9), which is what makes the pair
# bit-comparable
_STREAM_ENV = {"H2O3_TREE_OOC": "1", "H2O3_STREAM_BLOCKS": "4",
               "H2O3_STREAM_BUDGET_MB": "0.02"}
_INCORE_ENV = {"H2O3_TREE_OOC": "0", "H2O3_TREE_SHARD": "1",
               "H2O3_TREE_SHARD_BLOCKS": "4"}
# the spilled fit adds a host-tier budget under the packed matrix size,
# so blocks overflow through the disk tier too — same S=4 grid, so the
# whole bit-exactness matrix above applies unchanged
_SPILL_ENV = dict(_STREAM_ENV, H2O3_STREAM_HOST_BUDGET_MB="0.005")

_X, _Y = make_classification(n=1500, f=8, seed=3)
_NAMES = [f"f{i}" for i in range(8)] + ["label"]


@pytest.fixture()
def _ooc_env():
    prior = {k: os.environ.pop(k, None) for k in _ENV_KEYS}
    yield
    for k, v in prior.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    ml.refresh(force=True)


def _frame(X=_X, y=_Y, names=_NAMES, factor=True):
    from h2o3_tpu.frame.frame import Frame

    fr = Frame.from_numpy(np.column_stack([X, y]), names=names)
    return fr.asfactor("label") if factor else fr


def _fit(env, mode="gbm", X=_X, y=_Y, names=_NAMES, frame=None,
         factor=True, **params):
    from h2o3_tpu.models import dataset_cache
    from h2o3_tpu.models.drf import H2ORandomForestEstimator
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    dataset_cache.clear()
    for k in _ENV_KEYS:
        os.environ.pop(k, None)
    os.environ.update(env)
    try:
        cls = (H2OGradientBoostingEstimator if mode == "gbm"
               else H2ORandomForestEstimator)
        est = cls(seed=42, **params)
        est.train(y="label",
                  training_frame=frame if frame is not None
                  else _frame(X, y, names, factor))
    finally:
        for k in _ENV_KEYS:
            os.environ.pop(k, None)
    return est


def _assert_bitexact(a, b):
    assert a.model.ntrees_built == b.model.ntrees_built
    for k in range(len(a.model.forest)):
        for f in treelib.Tree._fields:
            assert np.array_equal(
                np.asarray(getattr(a.model.forest[k], f)),
                np.asarray(getattr(b.model.forest[k], f))), (k, f)
    va = getattr(a.model, "varimp_table", None)
    vb = getattr(b.model, "varimp_table", None)
    if va is not None or vb is not None:
        assert [r[0] for r in va] == [r[0] for r in vb]
        np.testing.assert_array_equal([r[1] for r in va],
                                      [r[1] for r in vb])


# -- ops: the block-wise pack API -------------------------------------------

def test_pack_host_range_matches_whole_matrix_pack():
    """A block packed via pack_host_range is byte-identical to the same
    rows of a whole-matrix pack — O(block) ingest, same bitstream."""
    rng = np.random.default_rng(5)
    for bits, B in ((4, 16), (5, 21), (6, 33)):
        codes = rng.integers(0, B, (256, 6)).astype(np.uint8)
        whole = packing.pack_host(codes, bits)
        group, gbytes = packing.GROUP_ROWS[bits], packing.GROUP_BYTES[bits]
        r0, r1 = 4 * group, 12 * group
        blk = packing.pack_host_range(codes, bits, r0, r1)
        np.testing.assert_array_equal(
            blk, whole[r0 // group * gbytes:r1 // group * gbytes])
    with pytest.raises(ValueError):
        packing.pack_host_range(codes, 5, 3, 19)   # off the pack group


# -- BlockStore: LRU residency + eviction order ------------------------------

def _mk_store(n_blocks=4, rows=64, F=4):
    rng = np.random.default_rng(0)
    blocks = [rng.integers(0, 16, (rows, F)).astype(np.uint8)
              for _ in range(n_blocks)]
    nb = blocks[0].nbytes
    return bslib.BlockStore(blocks, rows, 0, budget_bytes=2 * nb,
                            register=False), nb


def test_block_store_cap_eviction_is_lru_ordered(_ooc_env):
    """Walking blocks under a 2-block budget evicts LRU-first; every
    eviction is a timeline `memory` event naming the block."""
    st, nb = _mk_store()
    cur = Timeline.cursor()
    for b in range(4):
        st.get(b)
    assert st.resident_bytes() == 2 * nb
    evs = [e for e in Timeline.snapshot(since=cur, n=1000)
           if e["kind"] == "memory" and e["owner"].startswith(st.owner)]
    assert [e["owner"] for e in evs] == [f"{st.owner}:block0",
                                         f"{st.owner}:block1"]
    assert all(e["trigger"] == "cap" and e["bytes"] == nb for e in evs)
    assert st.counters["uploaded"] == 4 and st.counters["evicted"] == 2
    st.get(2)                       # LRU hit — no upload
    assert st.counters["reused"] == 1


def test_block_store_pressure_shed_order_and_double_buffer(_ooc_env):
    """Past the ledger's eviction threshold a get() sheds everything but
    the double buffer (b, b+1) BEFORE growing the resident set — LRU
    order, trigger='pressure', pinned via timeline events."""
    st, nb = _mk_store()
    st.get(2)
    st.get(3)
    os.environ["H2O3_MEM_BUDGET_MB"] = "1"
    os.environ["H2O3_MEM_EVICT_PRESSURE"] = "0.5"
    ml.refresh(force=True)
    cur = Timeline.cursor()
    try:
        st.get(0)
    finally:
        os.environ.pop("H2O3_MEM_BUDGET_MB", None)
        os.environ.pop("H2O3_MEM_EVICT_PRESSURE", None)
        ml.refresh(force=True)
    evs = [e for e in Timeline.snapshot(since=cur, n=1000)
           if e["kind"] == "memory" and e.get("trigger") == "pressure"
           and e["owner"].startswith(st.owner)]
    assert [e["owner"] for e in evs] == [f"{st.owner}:block2",
                                         f"{st.owner}:block3"]
    assert st.resident_bytes() == nb    # only block0 resident


def test_dataset_cache_sheds_device_blocks_first(cloud1, _ooc_env):
    """The dataset cache's pressure response drops device blocks before
    entries — a shed block keeps its host copy (cost: one re-upload)."""
    from h2o3_tpu.models import dataset_cache as dsc

    fr = _frame()   # kept alive: the cache entry is weakref'd to it
    est = _fit(dict(_STREAM_ENV), frame=fr, ntrees=2, max_depth=3)
    assert est.model._stream_stats["blocks_uploaded"] > 0
    entries = [e for e in dsc._ENTRIES.values() if e.blocks]
    assert entries, "streamed fit did not land a blocked cache layer"
    st = next(iter(entries[0].blocks.values()))
    assert st.resident_bytes() > 0
    os.environ["H2O3_MEM_BUDGET_MB"] = "1"
    os.environ["H2O3_MEM_EVICT_PRESSURE"] = "0.5"
    ml.refresh(force=True)
    cur = Timeline.cursor()
    try:
        with dsc._LOCK:
            dsc._evict_locked()
    finally:
        os.environ.pop("H2O3_MEM_BUDGET_MB", None)
        os.environ.pop("H2O3_MEM_EVICT_PRESSURE", None)
        ml.refresh(force=True)
    assert st.resident_bytes() == 0
    evs = [e for e in Timeline.snapshot(since=cur, n=1000)
           if e["kind"] == "memory" and e.get("trigger") == "pressure"
           and e["owner"].startswith(st.owner)]
    assert evs, "block shedding did not land in the timeline"
    dsc.clear()


# -- BlockStore: disk tier (round 19) ----------------------------------------

def _mk_spill_store(tmp_path, n_blocks=4, rows=64, F=4):
    """Store whose 4-block host set overflows a 2-block host budget, with
    spill files rooted in the test's tmp dir; returns pristine copies of
    the blocks for restore bit-compares."""
    os.environ["H2O3_SPILL_DIR"] = str(tmp_path)
    rng = np.random.default_rng(0)
    blocks = [rng.integers(0, 16, (rows, F)).astype(np.uint8)
              for _ in range(n_blocks)]
    ref = [b.copy() for b in blocks]
    nb = blocks[0].nbytes
    st = bslib.BlockStore(blocks, rows, 0, budget_bytes=2 * nb,
                          host_budget_bytes=2 * nb, register=False)
    return st, ref, nb


def test_block_store_disk_spill_lru_order_and_restore_bitexact(
        _ooc_env, tmp_path):
    """Overflowing the host budget spills LRU-first (timeline-pinned
    order), a restore is bit-identical, its spill file is KEPT, and the
    restore makes room FIRST so the host watermark never exceeds the
    budget — the evict-then-restore ordering lands in the timeline too."""
    cur = Timeline.cursor()
    st, ref, nb = _mk_spill_store(tmp_path)
    try:
        evs = [e for e in Timeline.snapshot(since=cur, n=1000)
               if e["kind"] == "memory" and e.get("space") == "disk"
               and e["owner"].startswith(st.owner)]
        assert [e["owner"] for e in evs] == [f"{st.owner}:block0",
                                             f"{st.owner}:block1"]
        assert all(e["detail"].startswith("spill ") and e["bytes"] == nb
                   and e["trigger"] == "host_cap" for e in evs)
        assert st.counters["spilled"] == 2
        assert st.host_bytes() == 2 * nb and st.disk_bytes() == 2 * nb
        assert sorted(os.listdir(st._spill_dir)) == ["block0.bin",
                                                     "block1.bin"]
        # construction necessarily sees all blocks resident (they are
        # passed in); the watermark contract starts at the fit's window
        st.peak_window_start()
        cur2 = Timeline.cursor()
        got = st.fetch_host(0)
        np.testing.assert_array_equal(got, ref[0])
        assert st.counters["restored"] == 1
        # spilled copies kept: the restored block's file is still there
        assert os.path.exists(st._spill_path(0))
        # evict-then-restore: the colder victim's spill event precedes
        # the restore event, so residency never exceeded the budget
        evs2 = [e for e in Timeline.snapshot(since=cur2, n=1000)
                if e["kind"] == "memory" and e.get("space") == "disk"
                and e["owner"].startswith(st.owner)]
        assert [e["detail"].split()[0] for e in evs2] == ["spill",
                                                          "restore"]
        assert evs2[0]["owner"] == f"{st.owner}:block2"
        assert evs2[1]["owner"] == f"{st.owner}:block0"
        assert st.host_peak_window_bytes() <= st.host_budget_bytes()
        # every spilled block restores bit-identically
        for b in range(4):
            np.testing.assert_array_equal(st.fetch_host(b), ref[b])
        assert st.host_peak_window_bytes() <= st.host_budget_bytes()
    finally:
        st.close()
    # close() removes the spill files and the per-store directory
    assert not os.path.exists(st._spill_dir)


def test_block_store_spill_read_fault_resumes_bitexact(_ooc_env, tmp_path):
    """An armed `persist.read` fault mid-restore resumes under the shared
    retry policy and the restored block is still bit-identical — the
    Range-resume machinery is the same one the ingest path uses."""
    from h2o3_tpu.runtime import faults

    st, ref, nb = _mk_spill_store(tmp_path)
    try:
        faults.arm("persist.read", error="io", count=1)
        try:
            got = st.fetch_host(1)
            fired = faults.snapshot()["points"][0]["fires"]
        finally:
            faults.reset()
        assert fired == 1, "the armed fault never fired"
        np.testing.assert_array_equal(got, ref[1])
        assert st.counters["restored"] == 1
    finally:
        st.close()


def test_spill_ledger_disk_space_and_leak_detection(_ooc_env, tmp_path):
    """Spill bytes surface as `h2o3_memory_bytes{space="disk"}` under the
    block_store kind; a store dropped WITHOUT close() leaves its dead
    `:spill` owner still reporting filesystem bytes — a leak — which
    clears when the files go away."""
    import gc

    from h2o3_tpu.runtime import metrics_registry as reg

    st, ref, nb = _mk_spill_store(tmp_path)
    owner = st.owner
    sd = st._spill_dir
    snap = ml.refresh(force=True)
    bk = snap["by_kind"].get("block_store")
    assert bk is not None and bk["disk_bytes"] >= 2 * nb
    assert snap["totals"]["disk_bytes"] >= 2 * nb
    text = reg.prometheus_text()
    assert 'h2o3_memory_bytes{owner_kind="block_store",space="disk"}' \
        in text
    del st
    gc.collect()
    snap = ml.refresh(force=True)
    leaks = [l for l in snap["leaks"] if l["owner"] == f"{owner}:spill"]
    assert leaks and leaks[0]["reason"] == "referent_dead"
    assert leaks[0]["bytes"] >= 2 * nb
    for f in os.listdir(sd):
        os.remove(os.path.join(sd, f))
    os.rmdir(sd)
    snap = ml.refresh(force=True)
    assert not any(l["owner"] == f"{owner}:spill" for l in snap["leaks"])


# -- the bit-exactness matrix ------------------------------------------------

def test_streamed_gbm_early_stop_bitexact_vs_incore(cloud1, _ooc_env):
    """GBM + firing early stop: streamed forest, varimp, scoring history,
    tree count and predictions == the in-core fit sharing S."""
    params = dict(ntrees=10, max_depth=3, learn_rate=0.3,
                  score_tree_interval=2, stopping_rounds=2,
                  stopping_tolerance=0.5)
    a = _fit(dict(_STREAM_ENV), **params)
    assert a.model._stream_stats["streamed_bytes"] > 0
    assert a.model.ntrees_built < 10, "early stop never fired"
    b = _fit(dict(_INCORE_ENV), **params)
    assert not hasattr(b.model, "_stream_stats")
    _assert_bitexact(a, b)
    ha = [e.get("logloss") for e in a.model.scoring_history]
    hb = [e.get("logloss") for e in b.model.scoring_history]
    assert ha == hb
    fr = _frame()
    np.testing.assert_array_equal(
        np.asarray(a.model.predict(fr).vec("1").data),
        np.asarray(b.model.predict(fr).vec("1").data))


def test_streamed_drf_bitexact_vs_incore(cloud1, _ooc_env):
    """DRF (row sampling + mtries + OOB) streams bit-identically."""
    params = dict(ntrees=5, max_depth=3, sample_rate=0.7, mtries=3)
    a = _fit(dict(_STREAM_ENV), mode="drf", **params)
    assert a.model._stream_stats["blocks"] == 4
    b = _fit(dict(_INCORE_ENV), mode="drf", **params)
    _assert_bitexact(a, b)


def test_streamed_onehot_kernel_lane_bitexact(cloud1, _ooc_env):
    """The streamed blocks take whatever kernel the fit names: on the
    one-hot matmul kernel too, each block partial is the in-core blocked
    reduction's, bit for bit."""
    env_a = dict(_STREAM_ENV, H2O3_HIST_METHOD="onehot")
    env_b = dict(_INCORE_ENV, H2O3_HIST_METHOD="onehot")
    params = dict(ntrees=4, max_depth=3, learn_rate=0.2)
    _assert_bitexact(_fit(env_a, **params), _fit(env_b, **params))


def test_streamed_cv_fold_reuse_bitexact(cloud1, _ooc_env):
    """CV fold reuse composes with streaming: fold models slice the same
    quantization grid and the cross-validated parent is bit-identical."""
    params = dict(ntrees=4, max_depth=3, nfolds=2)
    a = _fit(dict(_STREAM_ENV), **params)
    b = _fit(dict(_INCORE_ENV), **params)
    _assert_bitexact(a, b)
    ma, mb = a.model.cross_validation_metrics, b.model.cross_validation_metrics
    assert ma is not None and mb is not None
    np.testing.assert_array_equal(ma.logloss(), mb.logloss())
    np.testing.assert_array_equal(ma.auc(), mb.auc())


def test_ooc_escape_hatch_is_plain_fit(cloud1, _ooc_env):
    """H2O3_TREE_OOC=0 under a tiny budget == a plain fit, bit-identical
    (the acceptance-criteria escape hatch)."""
    params = dict(ntrees=4, max_depth=3)
    a = _fit({"H2O3_TREE_OOC": "0", "H2O3_STREAM_BUDGET_MB": "0.001"},
             **params)
    b = _fit({}, **params)
    assert not hasattr(a.model, "_stream_stats")
    _assert_bitexact(a, b)


def test_ooc_auto_streams_only_when_oversubscribed(cloud1, _ooc_env):
    """auto (the default) consults the stream budget: a matrix over
    budget streams, one under it does not."""
    small = _fit({"H2O3_STREAM_BUDGET_MB": "0.002"}, ntrees=2, max_depth=3)
    assert small.model._stream_stats["blocks_uploaded"] > 0
    big = _fit({"H2O3_STREAM_BUDGET_MB": "100"}, ntrees=2, max_depth=3)
    assert not hasattr(big.model, "_stream_stats")


# -- disk tier: spilled fits (round 19) --------------------------------------

def _assert_spilled_under_budget(st):
    """The fit genuinely crossed the disk tier AND its host-resident
    watermark stayed under the effective host budget (configured value,
    floored at the 2-block disk double buffer)."""
    assert st["spilled_blocks"] > 0 and st["restored_blocks"] > 0
    per_block = st["spilled_bytes"] // max(st["spilled_blocks"], 1)
    budget = max(int(0.005 * 1e6), 2 * per_block)
    assert st["resident_host_peak"] <= budget, \
        f"host watermark {st['resident_host_peak']} over budget {budget}"


def test_spilled_gbm_early_stop_bitexact_vs_incore(cloud1, _ooc_env):
    """A fit overflowing BOTH the device and host budgets (blocks live on
    disk mid-fit) is bit-identical to the in-core fit sharing S — forest,
    varimp, scoring history, early-stop tree count."""
    params = dict(ntrees=10, max_depth=3, learn_rate=0.3,
                  score_tree_interval=2, stopping_rounds=2,
                  stopping_tolerance=0.5)
    a = _fit(dict(_SPILL_ENV), **params)
    st = a.model._stream_stats
    _assert_spilled_under_budget(st)
    assert st["disk_bytes"] > 0
    assert a.model.ntrees_built < 10, "early stop never fired"
    b = _fit(dict(_INCORE_ENV), **params)
    _assert_bitexact(a, b)
    ha = [e.get("logloss") for e in a.model.scoring_history]
    hb = [e.get("logloss") for e in b.model.scoring_history]
    assert ha == hb


def test_spilled_drf_bitexact_vs_incore(cloud1, _ooc_env):
    """DRF (row sampling + mtries + OOB) through the disk tier streams
    bit-identically."""
    params = dict(ntrees=5, max_depth=3, sample_rate=0.7, mtries=3)
    a = _fit(dict(_SPILL_ENV), mode="drf", **params)
    _assert_spilled_under_budget(a.model._stream_stats)
    _assert_bitexact(a, _fit(dict(_INCORE_ENV), mode="drf", **params))


def test_spilled_cv_fold_reuse_bitexact(cloud1, _ooc_env):
    """CV fold reuse composes with the disk tier: fold fits share the
    spilled block grid and the cross-validated parent stays
    bit-identical."""
    params = dict(ntrees=4, max_depth=3, nfolds=2)
    a = _fit(dict(_SPILL_ENV), **params)
    st = a.model._stream_stats
    assert st["restored_blocks"] > 0 and st["disk_bytes"] > 0
    b = _fit(dict(_INCORE_ENV), **params)
    _assert_bitexact(a, b)
    ma, mb = a.model.cross_validation_metrics, b.model.cross_validation_metrics
    assert ma is not None and mb is not None
    np.testing.assert_array_equal(ma.logloss(), mb.logloss())


def test_disk_tier_escape_hatch_streams_without_spilling(cloud1, _ooc_env):
    """H2O3_TREE_OOC_DISK=0 under a tiny host budget keeps the two-tier
    behaviour: the fit still streams, writes NOTHING to disk, and is
    bit-identical to the spilled fit (same S)."""
    params = dict(ntrees=4, max_depth=3)
    a = _fit(dict(_SPILL_ENV, H2O3_TREE_OOC_DISK="0"), **params)
    st = a.model._stream_stats
    assert st["blocks_uploaded"] > 0
    assert st["spilled_blocks"] == 0 and st["disk_bytes"] == 0
    b = _fit(dict(_SPILL_ENV), **params)
    assert b.model._stream_stats["spilled_blocks"] > 0
    _assert_bitexact(a, b)


def test_spilled_fit_survives_midstream_read_fault(cloud1, _ooc_env):
    """An armed `persist.read` fault mid-fit (a torn spill read) resumes
    under the retry policy and the fit is STILL bit-identical — fault
    recovery never changes bits."""
    from h2o3_tpu.runtime import faults

    params = dict(ntrees=3, max_depth=3)
    b = _fit(dict(_SPILL_ENV), **params)
    faults.arm("persist.read", error="io", count=1)
    try:
        a = _fit(dict(_SPILL_ENV), **params)
        fired = faults.snapshot()["points"][0]["fires"]
    finally:
        faults.reset()
    assert fired == 1, "the armed fault never fired"
    assert a.model._stream_stats["restored_blocks"] > 0
    _assert_bitexact(a, b)


# -- observability -----------------------------------------------------------

def test_stream_stats_plan_phase_and_prometheus_surface(cloud1, _ooc_env):
    """The fit's stream trajectory is a read, not a rerun: model stats,
    the kernel plan's `stream` fold, the h2d_stream phase bucket and the
    Prometheus counters all carry it."""
    from h2o3_tpu.runtime import metrics_registry as reg
    from h2o3_tpu.runtime import phases

    est = _fit(dict(_STREAM_ENV), ntrees=3, max_depth=3)
    st = est.model._stream_stats
    assert st["blocks"] == 4 and st["blocks_uploaded"] >= 4
    assert st["streamed_bytes"] > 0 and st["resident_block_peak"] > 0
    assert st["bytes_per_tree"] > 0 and st["goss"] is False
    plans = [p for p in histogram.kernel_stats()["plans"] if "stream" in p]
    assert plans and plans[-1]["stream"]["streamed_bytes"] == \
        st["streamed_bytes"]
    snap = phases.snapshot()
    assert snap.get("bytes_h2d_stream", 0) > 0
    text = reg.prometheus_text()
    assert "h2o3_tree_stream_bytes" in text
    assert 'h2o3_tree_stream_blocks_total{event="uploaded"}' in text
    totals = bslib.process_totals()
    assert totals["streamed_bytes"] >= st["streamed_bytes"]
    assert totals["resident_block_peak"] >= st["resident_block_peak"]


# -- GOSS ---------------------------------------------------------------------

def test_goss_streams_fewer_bytes_and_is_deterministic(cloud1, _ooc_env):
    """Past goss_start_tree later trees stream a fraction of the blocks
    (the perf headline when oversubscribed); the same seed reproduces the
    identical forest."""
    # a budget of ~2 blocks forces genuine oversubscription (every level
    # pass re-streams evicted blocks) — the regime where sampling pays;
    # with the whole matrix resident GOSS's compact-sample uploads would
    # only ADD bytes
    env = dict(_STREAM_ENV, H2O3_STREAM_BUDGET_MB="0.004")
    params = dict(ntrees=6, max_depth=3, learn_rate=0.2)
    plain = _fit(env, **params)
    assert plain.model._stream_stats["blocks_evicted"] > 0
    g1 = _fit(env, goss=True, goss_start_tree=2, **params)
    g2 = _fit(env, goss=True, goss_start_tree=2, **params)
    assert g1.model._stream_stats["goss"] is True
    assert (g1.model._stream_stats["streamed_bytes"]
            < plain.model._stream_stats["streamed_bytes"])
    _assert_bitexact(g1, g2)


def test_goss_validation_and_ineligible_fallback(cloud1, _ooc_env):
    """Invalid GOSS configs fail fast; an ineligible fit (DRF / custom
    sample_rate / bad rates) never silently samples."""
    with pytest.raises(ValueError, match="goss rates"):
        _fit(dict(_STREAM_ENV), ntrees=2, max_depth=2, goss=True,
             goss_top_rate=0.9, goss_other_rate=0.3)
    with pytest.raises(ValueError, match="goss_start_tree"):
        _fit(dict(_STREAM_ENV), ntrees=2, max_depth=2, goss=True,
             goss_start_tree=0)
    with pytest.raises(ValueError, match="sample_rate"):
        _fit(dict(_STREAM_ENV), ntrees=2, max_depth=2, goss=True,
             sample_rate=0.5)
    # an explicit 0.0 rate reaches the validator (not swapped for the
    # default by an `or` coercion)
    with pytest.raises(ValueError, match="goss rates"):
        _fit(dict(_STREAM_ENV), ntrees=2, max_depth=2, goss=True,
             goss_top_rate=0.0)


def test_goss_validation_fires_on_mesh_fits_too(cloud8, _ooc_env):
    """A bad goss config fails identically on a mesh-sharded fit — the
    shard gate must not silently drop the request."""
    with pytest.raises(ValueError, match="sample_rate"):
        _fit({"H2O3_TREE_OOC": "1"}, ntrees=2, max_depth=2, goss=True,
             sample_rate=0.5)


def test_goss_tied_gradients_sample_exactly(cloud1, _ooc_env):
    """Sign-shaped gradients (quantile loss: every row ties on |g|) still
    select EXACTLY the configured fraction — a >=threshold mask would
    mark every row `top` and the cap trim would keep an index-biased
    subset."""
    rng = np.random.default_rng(13)
    X = rng.normal(size=(1200, 6))
    y = X[:, 0] * 2 + rng.normal(scale=0.2, size=1200)
    names = [f"f{i}" for i in range(6)] + ["label"]
    est = _fit(dict(_STREAM_ENV), X=X, y=y, names=names, factor=False,
               distribution="quantile", ntrees=4, max_depth=3,
               goss=True, goss_start_tree=1)
    st = est.model._stream_stats
    assert st["goss"] is True and st["streamed_bytes"] > 0


def _random_tree(rng, F, D, B):
    T = treelib.heap_size(D)
    return treelib.Tree(
        feat=jnp.asarray(rng.integers(0, F, T).astype(np.int32)),
        bin=jnp.asarray(rng.integers(0, B - 2, T).astype(np.int32)),
        thr=jnp.zeros(T, jnp.float32),
        is_split=jnp.asarray(rng.random(T) < 0.8),
        value=jnp.asarray(rng.normal(size=T).astype(np.float32)))


@pytest.mark.parametrize("bits,B", [(4, 16), (5, 21), (6, 33)])
def test_predict_codes_packed_matches_dense(cloud1, bits, B):
    """The packed-word forest traversal (GOSS margin update) matches the
    dense predict_codes on every pack width."""
    rng = np.random.default_rng(9 + bits)
    N, F, D = 512, 5, 3
    tree = _random_tree(rng, F, D, B)
    codes = rng.integers(0, B, (N, F)).astype(np.uint8)
    dense = treelib.predict_codes(tree, jnp.asarray(codes), D)
    packed = treelib.predict_codes_packed(
        tree, jnp.asarray(packing.pack_host(codes, bits)), bits, D)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(packed))


@pytest.mark.parametrize("bits,B", [(0, 21), (4, 16), (5, 21), (6, 33)])
def test_streamed_partition_matches_dense_walk(cloud1, bits, B):
    """One streamed block's partition under a level decision — packed at
    every width, and full-width — routes each row as the dense walk over
    the full-width codes does."""
    from h2o3_tpu.models import tree_stream

    rng = np.random.default_rng(21 + bits)
    N, F, L = 1024, 6, 8
    codes = rng.integers(0, B, (N, F)).astype(np.uint8)
    idx = rng.integers(0, L, N).astype(np.int32)
    bf = rng.integers(0, F, L).astype(np.int32)
    bb = rng.integers(0, B - 2, L).astype(np.int32)
    do_split = rng.random(L) < 0.7
    want = 2 * idx + ((codes[np.arange(N), bf[idx]] > bb[idx])
                      & do_split[idx]).astype(np.int32)
    block = packing.pack_host(codes, bits) if bits else codes
    got = jax.jit(tree_stream._partition, static_argnums=(5, 6))(
        jnp.asarray(block), jnp.asarray(idx), jnp.asarray(bf),
        jnp.asarray(bb), jnp.asarray(do_split), L, bits)
    np.testing.assert_array_equal(np.asarray(got), want)


# -- slow lane ---------------------------------------------------------------

@pytest.mark.slow
def test_oversubscribed_whole_fit_stays_under_budget(cloud1, _ooc_env):
    """The acceptance pin: a packed matrix ≥10× the stream budget trains
    end-to-end with the device-resident block watermark under budget, and
    the ledger never sees the whole matrix resident."""
    X, y = make_classification(n=20_000, f=12, seed=11)
    names = [f"f{i}" for i in range(12)] + ["label"]
    est = _fit({"H2O3_TREE_OOC": "1", "H2O3_STREAM_BUDGET_MB": "0.015"},
               X=X, y=y, names=names, ntrees=8, max_depth=5,
               learn_rate=0.2, score_tree_interval=4)
    st = est.model._stream_stats
    budget = int(0.015 * 1e6)
    host_total = st["streamed_bytes"] / max(st["blocks_uploaded"], 1) \
        * st["blocks"]
    assert host_total >= 10 * budget, \
        f"matrix {host_total}B is not >=10x the {budget}B budget"
    assert st["resident_block_peak"] <= budget
    assert st["blocks_evicted"] > 0
    assert float(est.auc()) > 0.75
    # streamed vs in-core bit-exactness at this scale
    params = dict(ntrees=3, max_depth=4)
    env_a = {"H2O3_TREE_OOC": "1", "H2O3_STREAM_BUDGET_MB": "0.015"}
    env_b = dict(_INCORE_ENV, H2O3_TREE_SHARD_BLOCKS=str(st["blocks"]))
    a = _fit(env_a, X=X, y=y, names=names, **params)
    b = _fit(env_b, X=X, y=y, names=names, **params)
    _assert_bitexact(a, b)


@pytest.mark.slow
def test_mesh_sharded_fit_streams_when_oversubscribed(cloud8, _ooc_env):
    """Round 19 closes PR 11's gap: a mesh-sharded fit under a tiny
    budget is OOC-ELIGIBLE now — it converts to single-device streaming
    over a block grid matching the mesh shard count (S=8), so the
    streamed forest is bit-identical to the plain mesh fit."""
    params = dict(ntrees=3, max_depth=3)
    a = _fit({"H2O3_TREE_OOC": "1", "H2O3_STREAM_BUDGET_MB": "0.001",
              "H2O3_STREAM_BLOCKS": "8"}, **params)
    st = getattr(a.model, "_stream_stats", None)
    assert st is not None, "oversubscribed mesh fit did not stream"
    assert st["blocks"] == 8 and st["blocks_uploaded"] > 0
    b = _fit({}, **params)
    _assert_bitexact(a, b)
