"""Mid-fit supervisor (ISSUE 20): checkpoint store round-trip +
rejection discipline, `after=` fault placement, the deadline'd collective
fence abort, the supervisor state machine + fleet mark-down, the GBM
kill-at-tree-k → resume-bit-identical pin, the estimator segment-carry
snapshots, the SweepCheckpoint in-flight rider, and the tier-1 budget
tool. The multi-interpreter pod_chaos pin (2-process rank kill) lives in
the slow lane — each spawned interpreter cold-compiles for minutes,
which the tier-1 budget cannot absorb."""

import json
import os
import time

import numpy as np
import pytest

from h2o3_tpu.runtime import faults, supervisor, trainpool

from conftest import make_classification
from tests.multiproc_util import spawn_workers


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    faults.reset()
    supervisor.reset()
    trainpool.reset()
    monkeypatch.delenv("H2O3_CKPT", raising=False)
    monkeypatch.delenv("H2O3_CKPT_DIR", raising=False)
    monkeypatch.delenv("H2O3_CKPT_TREES", raising=False)
    monkeypatch.delenv("H2O3_FENCE_DEADLINE_S", raising=False)
    yield
    faults.reset()
    supervisor.reset()


def _totals():
    return supervisor.snapshot()["totals"]


# -- checkpoint store ---------------------------------------------------------

def test_ckpt_roundtrip_single_rank(tmp_path):
    d = str(tmp_path)
    fp = supervisor.run_fingerprint(algo="t", rows=100, seed=7)
    arrays = dict(a=np.arange(12, dtype=np.float32).reshape(3, 4),
                  b=np.array([1.5, -2.25], np.float64))
    supervisor.save_fit_checkpoint(d, "tree", fp, 5, arrays,
                                   meta=dict(history=[{"m": 5}]))
    rec = supervisor.latest_fit_checkpoint(d, "tree", fp)
    assert rec["step"] == 5 and rec["nproc"] == 1
    sh = rec["shards"][0]
    assert np.array_equal(sh["a"], arrays["a"]) and sh["a"].dtype == np.float32
    assert np.array_equal(sh["b"], arrays["b"])
    assert rec["meta"]["history"] == [{"m": 5}]
    # newest step wins; keep=2 GC drops the oldest of three
    supervisor.save_fit_checkpoint(d, "tree", fp, 10, arrays)
    supervisor.save_fit_checkpoint(d, "tree", fp, 15, arrays)
    assert supervisor.latest_fit_checkpoint(d, "tree", fp)["step"] == 15
    steps = sorted(int(f.split("_s")[1][:8]) for f in os.listdir(d))
    assert steps == [10, 15]
    assert not any(f.endswith(".part") for f in os.listdir(d))


def test_ckpt_multirank_requires_complete_rank_set(tmp_path):
    d = str(tmp_path)
    fp = supervisor.run_fingerprint(algo="t", rows=100)
    a = dict(x=np.ones(3, np.float32))
    # step 8: both ranks present; step 12: rank 1 missing (died mid-save)
    supervisor.save_fit_checkpoint(d, "tree", fp, 8, a, rank=0, nproc=2)
    supervisor.save_fit_checkpoint(d, "tree", fp, 8, a, rank=1, nproc=2)
    supervisor.save_fit_checkpoint(d, "tree", fp, 12, a, rank=0, nproc=2)
    r0 = _totals()["ckpt_rejects"]
    rec = supervisor.latest_fit_checkpoint(d, "tree", fp)
    assert rec["step"] == 8 and rec["nproc"] == 2
    assert len(rec["shards"]) == 2
    assert _totals()["ckpt_rejects"] == r0 + 1   # the torn step-12 set


def test_ckpt_fingerprint_mismatch_never_restores(tmp_path):
    d = str(tmp_path)
    fp_a = supervisor.run_fingerprint(seed=1, rows=100)
    fp_b = supervisor.run_fingerprint(seed=2, rows=100)
    assert fp_a != fp_b
    supervisor.save_fit_checkpoint(d, "tree", fp_a, 5,
                                   dict(x=np.zeros(2, np.float32)))
    assert supervisor.latest_fit_checkpoint(d, "tree", fp_b) is None


def test_run_fingerprint_sanitizes_and_orders():
    a = supervisor.run_fingerprint(rows=np.int64(100), lr=np.float32(0.1),
                                   cols=("a", "b"))
    b = supervisor.run_fingerprint(cols=["a", "b"], lr=0.10000000149011612,
                                   rows=100)
    assert a == b and len(a) == 16


def test_ckpt_truncated_rejected_falls_back_to_older(tmp_path):
    d = str(tmp_path)
    fp = supervisor.run_fingerprint(seed=3)
    a = dict(x=np.arange(64, dtype=np.float32))
    supervisor.save_fit_checkpoint(d, "tree", fp, 5, a)
    p10 = supervisor.save_fit_checkpoint(d, "tree", fp, 10, a)
    with open(p10, "rb") as f:
        blob = f.read()
    with open(p10, "wb") as f:
        f.write(blob[: len(blob) // 2])   # torn exactly like a crash
    r0 = _totals()["ckpt_rejects"]
    rec = supervisor.latest_fit_checkpoint(d, "tree", fp)
    assert rec["step"] == 5                      # fell back, didn't die
    assert _totals()["ckpt_rejects"] == r0 + 1


def test_ckpt_corrupt_fault_produces_rejected_snapshot(tmp_path):
    d = str(tmp_path)
    fp = supervisor.run_fingerprint(seed=4)
    a = dict(x=np.arange(32, dtype=np.float32))
    faults.arm("supervisor.ckpt_corrupt", error="io", count=1)
    supervisor.save_fit_checkpoint(d, "tree", fp, 5, a)   # torn on disk
    assert faults.snapshot()["points"][0]["fires"] == 1
    assert supervisor.latest_fit_checkpoint(d, "tree", fp) is None
    # the next save (fault exhausted) is valid and restores normally
    supervisor.save_fit_checkpoint(d, "tree", fp, 10, a)
    assert supervisor.latest_fit_checkpoint(d, "tree", fp)["step"] == 10


# -- `after=` fault placement -------------------------------------------------

def test_fault_after_skips_first_k_checks():
    faults.arm("p.x", error="io", count=1, after=2)
    faults.check("p.x")
    faults.check("p.x")
    with pytest.raises(faults.InjectedIOError):
        faults.check("p.x")
    faults.check("p.x")   # count=1 exhausted
    desc = faults.snapshot()["points"][0]
    assert desc["after"] == 2 and desc["fires"] == 1 and desc["checks"] == 4


def test_fault_after_parses_from_env(monkeypatch):
    monkeypatch.setenv("H2O3_FAULT_MESH_RANK_KILL",
                       "error=crash,count=1,after=12")
    faults._env_parse()
    desc = [p for p in faults.snapshot()["points"]
            if p["point"] == "mesh.rank_kill"][0]
    assert desc["after"] == 12 and desc["count"] == 1
    assert desc["error"] == "crash"


# -- deadline'd fence ---------------------------------------------------------

def test_deadline_block_aborts_hung_collective():
    t0 = _totals()
    with pytest.raises(supervisor.CollectiveTimeout) as ei:
        supervisor.deadline_block(None, timeout_s=0.2, tag="fence7",
                                  _blocker=lambda: time.sleep(30))
    assert "fence7" in str(ei.value)
    assert isinstance(ei.value, TimeoutError)   # retry-classifier: transient
    t1 = _totals()
    assert t1["aborts"] == t0["aborts"] + 1
    snap = supervisor.snapshot()
    assert snap["state"] == "aborted"
    assert snap["last_abort"]["tag"] == "fence7"
    assert snap["last_abort"]["latency_s"] >= 0.19
    assert snap["detect_ms"]["count"] >= 1


def test_deadline_block_passes_results_and_errors_through():
    hits = []
    supervisor.deadline_block(None, timeout_s=5.0,
                              _blocker=lambda: hits.append(1))
    assert hits == [1]
    # no deadline configured → direct call, no worker thread
    supervisor.deadline_block(None, timeout_s=0,
                              _blocker=lambda: hits.append(2))
    assert hits == [1, 2]

    def boom():
        raise ValueError("bad dispatch")

    with pytest.raises(ValueError, match="bad dispatch"):
        supervisor.deadline_block(None, timeout_s=5.0, _blocker=boom)


def test_state_machine_and_snapshot():
    supervisor.fit_started("tree", "fp123", total=40)
    supervisor.pulse("tree", 10)
    s = supervisor.snapshot()
    assert s["state"] == "watching"
    assert s["fit"]["tag"] == "tree" and s["fit"]["total"] == 40
    assert s["heartbeat"]["step"] == 10
    supervisor.fit_finished("other")      # stale tag: no-op
    assert supervisor.snapshot()["state"] == "watching"
    supervisor.fit_finished("tree")
    s = supervisor.snapshot()
    assert s["state"] == "idle" and s["fit"] is None
    assert set(s["totals"]) == {"aborts", "resumes", "ckpt_saves",
                                "ckpt_rejects", "marked_down"}
    assert s["config"]["ckpt_trees"] == 25


def test_mark_ranks_down_flips_fleet_peer_up_gauge():
    from h2o3_tpu.runtime import fleet

    supervisor.mark_ranks_down([3], reason="test")
    assert fleet._registry()["peer_up"].value("rank3") == 0.0


# -- GBM kill-at-tree-k → resume (the tier-1 pin) -----------------------------

def _gbm_frame():
    from h2o3_tpu.frame.frame import Frame

    X, y = make_classification(200, 3, seed=11)
    return Frame.from_numpy(
        np.column_stack([X, y]), names=["x0", "x1", "x2", "y"]
    ).asfactor("y")


def _fit_gbm(fr):
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    est = H2OGradientBoostingEstimator(ntrees=9, max_depth=2, seed=13,
                                       score_tree_interval=3)
    est.train(y="y", training_frame=fr)
    return est.model


def _assert_models_bitidentical(a, b):
    assert len(a.forest) == len(b.forest)
    for ta, tb in zip(a.forest, b.forest):
        for f in ("feat", "bin", "thr", "value"):
            assert np.array_equal(np.asarray(getattr(ta, f)),
                                  np.asarray(getattr(tb, f))), f
    assert [r[0] for r in a.varimp_table] == [r[0] for r in b.varimp_table]
    assert np.array_equal(
        np.asarray([r[1] for r in a.varimp_table], np.float64),
        np.asarray([r[1] for r in b.varimp_table], np.float64))
    for ra, rb in zip(a.scoring_history, b.scoring_history):
        for k, va in ra.items():
            if k == "timestamp":
                continue
            vb = rb[k]
            if (isinstance(va, float) and isinstance(vb, float)
                    and np.isnan(va) and np.isnan(vb)):
                continue
            assert va == vb, k


def test_gbm_midfit_kill_and_resume_bitidentical(cloud1, monkeypatch,
                                                 tmp_path):
    """The ISSUE 20 tier-1 acceptance pin: a fit killed at tree k with
    H2O3_CKPT_TREES=c resumes from its snapshot, retrains <= c trees, and
    the final model is BIT-identical (forest, varimp, scoring history) to
    an undisturbed fit — and H2O3_CKPT=0 disables the whole machinery."""
    fr = _gbm_frame()
    ref = _fit_gbm(fr)                      # baseline: checkpointing off

    d = str(tmp_path / "ck")
    monkeypatch.setenv("H2O3_CKPT_DIR", d)
    monkeypatch.setenv("H2O3_CKPT_TREES", "3")

    # escape hatch first: H2O3_CKPT=0 with a dir set writes nothing and
    # matches the pre-supervisor fit bit-for-bit
    monkeypatch.setenv("H2O3_CKPT", "0")
    off = _fit_gbm(fr)
    _assert_models_bitidentical(off, ref)
    assert not os.path.exists(d) or not os.listdir(d)
    monkeypatch.setenv("H2O3_CKPT", "1")

    # kill at the second chunk (after=1 skips the m=0 boundary): the
    # m=0..2 chunk completed and checkpointed at step 3 before the crash
    faults.arm("supervisor.fit_abort", error="crash", count=1, after=1)
    with pytest.raises(faults.InjectedCrash):
        _fit_gbm(fr)
    assert any(f.startswith("fitckpt_tree_") for f in os.listdir(d))
    assert supervisor.snapshot()["state"] == "watching"  # died mid-fit

    resumed = _fit_gbm(fr)                  # same params → restores
    s = supervisor.snapshot()
    assert s["last_resume"] is not None
    assert s["last_resume"]["step"] == 3    # retrained 9-3=6 <= ntrees
    assert s["totals"]["resumes"] >= 1
    assert trainpool.snapshot()["totals"]["resumed_mid_fit"] >= 1
    assert s["state"] == "idle"             # fit_finished after resume
    assert resumed.ntrees_built == ref.ntrees_built
    _assert_models_bitidentical(resumed, ref)


# -- estimator segment carry --------------------------------------------------

def test_estimator_segment_carry_roundtrip(monkeypatch, tmp_path):
    import jax.numpy as jnp

    from h2o3_tpu.models import estimator_engine as _est

    # gate: no ckpt dir → fingerprint None → save/restore are no-ops
    assert _est.segment_fingerprint("kmeans", rows=10) is None
    monkeypatch.setenv("H2O3_CKPT_DIR", str(tmp_path))
    fp = _est.segment_fingerprint("kmeans", rows=10, k=3, seed=1)
    assert fp is not None
    carry = (jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
             jnp.asarray(4, jnp.int32), jnp.asarray(0.25, jnp.float32))
    _est.segment_carry_save("kmeans", fp, 4, carry)
    step, back = _est.segment_carry_restore("kmeans", fp)
    assert step == 4 and len(back) == 3
    for orig, rb in zip(carry, back):
        assert np.array_equal(np.asarray(orig), np.asarray(rb))
        assert np.asarray(orig).dtype == np.asarray(rb).dtype
    assert trainpool.snapshot()["totals"]["resumed_mid_fit"] >= 1
    # a different fit identity must not see these snapshots
    assert _est.segment_carry_restore(
        "kmeans", _est.segment_fingerprint("kmeans", rows=11)) is None


def test_kmeans_segmented_fit_checkpoints_and_resumes(cloud1, monkeypatch,
                                                      tmp_path):
    """A segmented (QoS-capped) K-Means fit snapshots its carry at segment
    boundaries; a re-run fit restores and lands on bitwise-identical
    centroids."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.kmeans import H2OKMeansEstimator

    # unstructured data: Lloyd must NOT converge inside 6 iterations, or
    # the done-gate skips every segment save and there is nothing to
    # restore (well-separated blobs converge in ~2)
    rng = np.random.default_rng(5)
    X = rng.normal(size=(240, 3))
    fr = Frame.from_numpy(X, names=["a", "b", "c"])
    monkeypatch.setenv("H2O3_QOS_EST_ITERS_PER_DISPATCH", "2")

    def _fit():
        km = H2OKMeansEstimator(k=3, max_iterations=6, seed=1)
        km.train(training_frame=fr)
        return np.asarray(km.model.centers_std, np.float64)

    ref = _fit()                            # no ckpt dir: plain segmented
    monkeypatch.setenv("H2O3_CKPT_DIR", str(tmp_path))
    c1 = _fit()
    assert any(f.startswith("fitckpt_estkmeans_")
               for f in os.listdir(tmp_path))
    assert np.array_equal(c1, ref)
    c2 = _fit()                             # restores a saved carry
    assert supervisor.snapshot()["totals"]["resumes"] >= 1
    assert np.array_equal(c2, ref)


# -- SweepCheckpoint in-flight rider ------------------------------------------

def test_sweep_checkpoint_inflight_roundtrip(tmp_path):
    d = str(tmp_path)
    ck = trainpool.SweepCheckpoint(d, "sw1", fingerprint=dict(seed=1))
    ck.mark_inflight("GBM_1", dict(ckpt_dir="/ck", fingerprint="abc"))
    # a killed sweep leaves the pointer on disk for the re-run
    ck2 = trainpool.SweepCheckpoint(d, "sw1", fingerprint=dict(seed=1))
    info = ck2.inflight("GBM_1")
    assert info["ckpt_dir"] == "/ck" and info["fingerprint"] == "abc"
    # completion clears it — a finished candidate needs no pointer
    ck2.mark("GBM_1", dict(auc=0.9))
    ck3 = trainpool.SweepCheckpoint(d, "sw1", fingerprint=dict(seed=1))
    assert ck3.inflight("GBM_1") is None and ck3.inflight() == {}
    assert ck3.completed("GBM_1") == {"auc": 0.9}
    # a mismatched fingerprint drops in-flight pointers with the records
    ck4 = trainpool.SweepCheckpoint(d, "sw1", fingerprint=dict(seed=2))
    assert ck4.completed("GBM_1") is None and ck4.inflight() == {}


# -- tools/t1_budget ----------------------------------------------------------

def _t1_budget():
    import importlib.util

    p = os.path.join(os.path.dirname(__file__), "..", "tools",
                     "t1_budget.py")
    spec = importlib.util.spec_from_file_location("t1_budget", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_t1_budget_parses_and_thresholds(tmp_path, monkeypatch, capsys):
    tb = _t1_budget()
    log = tmp_path / "t1.log"
    log.write_text(
        "....\n"
        "2.50s call     tests/test_a.py::test_slow\n"
        "0.30s setup    tests/test_a.py::test_slow\n"
        "1.10s call     tests/test_b.py::test_other\n"
        "709 passed, 1 skipped in 633.50s\n")
    durations, wall = tb.parse(str(log))
    assert wall == 633.50 and len(durations) == 3
    monkeypatch.setenv("T1_BUDGET_SOFT_S", "700")
    assert tb.main([str(log)]) == 0
    out = capsys.readouterr().out
    assert "634s" in out and "test_a.py::test_slow" in out
    monkeypatch.setenv("T1_BUDGET_SOFT_S", "600")
    assert tb.main([str(log)]) == 1          # past the soft threshold
    assert tb.main([str(tmp_path / "missing.log")]) == 2
    empty = tmp_path / "empty.log"
    empty.write_text("hello\n")
    assert tb.main([str(empty)]) == 2


# -- slow lane: the multi-interpreter pod_chaos pin ---------------------------

# one pod rank: a 20-tree GBM fit of the drill's CSV; rank 0 writes the
# supervisor's record and, when the fit completed, the forest
_POD_CHAOS_BODY = """
import json, time
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
    """An n-rank loopback pod running `_POD_CHAOS_BODY`; per-rank
    (returncode, output). Rank death (rc 43) is the scenario, so a
    nonzero exit is the caller's to judge."""
    return spawn_workers(
        nproc, _POD_CHAOS_BODY.format(csv=csv, out=out, info=info),
        extra_env=extra_env, rank_env=rank_env, timeout=timeout)


@pytest.mark.slow
def test_pod_chaos_rank_kill_resume_bitidentical(tmp_path):
    """The full ISSUE 20 acceptance drill — 2-process pod GBM fit, rank 1
    hard-killed mid-fit (mesh.rank_kill), survivor aborts within the
    fence deadline, degraded single-host resume bit-identical to an
    undisturbed run. Slow lane (tracked reason): every spawned
    interpreter cold-compiles its own jit cache — minutes per run, far
    past the tier-1 budget; the in-process pin above covers the
    state-machine/checkpoint logic in tier-1."""
    import csv as _csv
    import shutil

    import h2o3_tpu

    deadline_s = 15.0
    # the rank_kill point is checked at the ONE instrumented fence per
    # scoring interval (ops/histogram ordered_axis_fold's event-loss tag),
    # so a 20-tree fit at score_tree_interval=5 sees only ~4 arrivals per
    # rank: after=2 lands the kill at the 3rd arrival (~tree 15), with the
    # tree-5/10 checkpoints already committed
    kill_after = 2
    tmp = str(tmp_path)
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
    assert res[0][0] == 0 and os.path.exists(ref_out), \
        f"comparator fit failed: {res[0][1][-2000:]}"
    # B: 2-rank pod; rank 1 dies at its (kill_after+1)-th collective
    # arrival; rank 0's fences run under the supervisor deadline. The
    # doomed pod gets its OWN compilation cache — a fixed sub-directory of
    # the resolved one, emptied first: os._exit mid-write would tear the
    # shared persistent cache and the resume leg then segfaults
    # deserializing the torn entry (observed once) — cache poisoning is a
    # different failure than the one this drill pins
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
    chaos = json.loads(open(info_p).read()) if os.path.exists(info_p) \
        else {}
    assert chaos.get("error"), (
        "rank 0 completed despite a dead peer — the kill never landed:"
        f"\n{res[0][1][-2000:]}")
    assert os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir), \
        "no fit checkpoints were committed before the kill"
    # C: degraded single-host resume on the SAME shard plan S — restores
    # the rank-sharded snapshots (rank-ordered concat) and completes
    res_out = os.path.join(tmp, "resumed.npz")
    res_info = os.path.join(tmp, "resumed.json")
    res = _pod_chaos_spawn(1, csv_p, res_out, res_info,
                           extra_env=dict(shared, H2O3_TREE_SHARD="1"))
    assert res[0][0] == 0 and os.path.exists(res_out), \
        f"degraded resume failed: {res[0][1][-2000:]}"
    rinfo = json.loads(open(res_info).read())
    restored = int((rinfo.get("last_resume") or {}).get("restored") or 0)
    assert restored > 0, f"resume did not restore a checkpoint: {rinfo}"
    ref, got = np.load(ref_out), np.load(res_out)
    assert int(got["ntrees"]) == int(ref["ntrees"])
    for k in ("feat", "bins", "thr", "val", "vi_gain", "sh_ll"):
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
    assert int(got["ntrees"]) - restored <= 20
    detect_s = (chaos.get("last_abort") or {}).get("latency_s")
    detect_s = float(detect_s if detect_s is not None else detect_wall)
    assert np.isfinite(detect_s) and detect_s > 0
