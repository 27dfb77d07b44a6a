"""Multi-tenant QoS gate (ISSUE 19) — tier-1 tests.

Covers the cooperative two-class dispatch gate (runtime/qos): priority
ordering (serving never waits, training yields), the
``H2O3_QOS_TRAIN_MIN_SHARE`` anti-starvation floor under the armed
``qos.starve`` fault, yield/wait bookkeeping (totals + registry families +
the ``qos_wait`` phase bucket), admission-throttle hysteresis, the single
``pressure_view()`` snapshot shared by serving admission and the dataset
cache, and the bit-exactness pins: a fit under QoS (tree chunk yields,
estimator ``while_loop`` segmentation) is bit-identical to QoS-off.

The full concurrent soak (live REST server + open-loop load + in-process
grid sweep) lives in the slow lane (`test_qos_concurrent_soak_slow`):
tier-1 already runs ~700 s of its 870 s budget, and the soak needs
multi-second serving windows to produce meaningful percentiles — it is
exercised by nightly ``-m slow`` runs instead.
"""

import math
import threading
import time

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.runtime import faults, phases, qos
from h2o3_tpu.runtime import metrics_registry as reg


@pytest.fixture(autouse=True)
def _qos_clean(monkeypatch):
    """Every test starts and ends with a cold gate and no armed faults."""
    qos.reset()
    faults.reset()
    yield
    qos.reset()
    faults.reset()


def _rng_frame(rows=200, seed=7, binomial=True):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, 4)).astype(np.float64)
    if binomial:
        y = (X[:, 0] + 0.5 * X[:, 1] + rng.normal(scale=0.3, size=rows)
             > 0).astype(np.float64)
    else:
        y = X[:, 0] - 2.0 * X[:, 2] + rng.normal(scale=0.1, size=rows)
    names = ["a", "b", "c", "d", "y"]
    fr = Frame.from_numpy(np.column_stack([X, y]), names=names)
    return fr.asfactor("y") if binomial else fr


# ---------------------------------------------------------------- gate basics

def test_qos_off_is_free(monkeypatch):
    monkeypatch.delenv("H2O3_QOS", raising=False)
    assert not qos.enabled()
    assert qos.yield_point("tree_chunk") == 0.0
    with qos.serving_dispatch("m"):
        pass
    t = qos.totals()
    assert t["yields"] == 0 and t["serving_dispatches"] == 0


def test_serving_priority_over_training(monkeypatch):
    """A training yield waits while a serving dispatch is in flight and
    resumes promptly on release; serving entry itself never blocks."""
    monkeypatch.setenv("H2O3_QOS", "1")
    monkeypatch.setenv("H2O3_QOS_TRAIN_MIN_SHARE", "0.1")
    monkeypatch.setenv("H2O3_QOS_LINGER_MS", "0")
    monkeypatch.setenv("H2O3_QOS_MAX_WAIT_MS", "2000")

    release = threading.Event()
    entered = threading.Event()

    def serve():
        t0 = time.monotonic()
        with qos.serving_dispatch("gbm_1"):
            entry_cost = time.monotonic() - t0
            assert entry_cost < 0.05  # serving entry is non-blocking
            entered.set()
            release.wait(2.0)

    srv = threading.Thread(target=serve, daemon=True)
    # seed the training thread's share ledger with some "ran" time so the
    # min-share wait budget is positive
    assert qos.yield_point("tree_chunk") == 0.0
    time.sleep(0.05)
    srv.start()
    assert entered.wait(2.0)
    timer = threading.Timer(0.15, release.set)
    timer.start()
    waited = qos.yield_point("tree_chunk")
    timer.cancel()
    srv.join(2.0)
    assert waited >= 0.10  # blocked until the serving release
    assert waited < 1.0
    t = qos.totals()
    assert t["yields"] == 2 and t["serving_dispatches"] == 1
    assert t["waits_ms"] >= 100


def test_min_share_floor_under_starve_fault(monkeypatch):
    """With qos.starve armed every yield sees a closed gate; the
    min-share floor bounds cumulative wait so ran/(ran+waited) converges
    to the configured share instead of starving."""
    monkeypatch.setenv("H2O3_QOS", "1")
    monkeypatch.setenv("H2O3_QOS_TRAIN_MIN_SHARE", "0.5")
    monkeypatch.setenv("H2O3_QOS_MAX_WAIT_MS", "5000")
    faults.arm("qos.starve", error="none")

    qos.yield_point("tree_chunk")          # first visit: ran=0, no wait
    ran = 0.0
    for _ in range(3):
        time.sleep(0.03)
        ran += 0.03
        qos.yield_point("tree_chunk")
    waited = qos.totals()["waits_ms"] / 1e3
    # share=0.5 → cumulative wait tracks cumulative run time
    assert waited == pytest.approx(ran, rel=0.6)
    assert waited > 0.04
    # and with the fault disarmed the gate opens instantly again
    faults.reset()
    assert qos.yield_point("tree_chunk") < 0.02


def test_starve_fault_match_scoping(monkeypatch):
    """`match=` scopes qos.starve to one yield site — the other sites
    pass through an open gate."""
    monkeypatch.setenv("H2O3_QOS", "1")
    monkeypatch.setenv("H2O3_QOS_TRAIN_MIN_SHARE", "0.5")
    faults.arm("qos.starve", error="none", match="tree_block")
    assert faults.is_armed("qos.starve", "tree_block")
    assert not faults.is_armed("qos.starve", "est_segment")


def test_preempt_delay_fault_and_bookkeeping(monkeypatch):
    """qos.preempt_delay injects latency at the yield itself; yields are
    counted per site in the registry and in the process totals."""
    monkeypatch.setenv("H2O3_QOS", "1")
    faults.arm("qos.preempt_delay", error="none", latency_ms=30)
    t0 = time.monotonic()
    qos.yield_point("score_event")
    assert time.monotonic() - t0 >= 0.025
    assert qos.totals()["yields"] == 1
    fam = reg.get("h2o3_qos_yields")
    assert fam is not None


def test_qos_wait_booked_into_phases(monkeypatch):
    """Waits land in the ``qos_wait`` phase bucket and are subtracted
    from the compensated bucket (no double-booking)."""
    monkeypatch.setenv("H2O3_QOS", "1")
    monkeypatch.setenv("H2O3_QOS_TRAIN_MIN_SHARE", "0.5")
    faults.arm("qos.starve", error="none")
    phases.reset()
    qos.yield_point("tree_chunk")
    time.sleep(0.04)
    w = qos.yield_point("tree_chunk", compensate="compute")
    assert w > 0.01
    snap = phases.snapshot()
    assert snap.get("qos_wait_s", 0.0) >= 0.01
    # compensated bucket went negative by the same amount (subtraction
    # happened; the real sites only pass compensate while accounting)
    assert snap.get("compute_s", 0.0) <= -0.01


# ------------------------------------------------------------------ throttle

def test_throttle_hysteresis(monkeypatch):
    """Enter at pressure >= HI, stay throttled between LO and HI, exit
    only at <= LO — exactly two transitions, both counted."""
    monkeypatch.setenv("H2O3_QOS", "1")
    monkeypatch.setenv("H2O3_QOS_PRESSURE_HI", "0.9")
    monkeypatch.setenv("H2O3_QOS_PRESSURE_LO", "0.75")
    monkeypatch.setenv("H2O3_QOS_SLO_MS", "0")  # pressure-only

    cur = {"p": 0.5}

    def fake_view(max_age_s=None):
        return qos.PressureView(cur["p"], cur["p"] >= 0.97,
                                cur["p"] >= 0.9, time.monotonic())

    monkeypatch.setattr(qos, "pressure_view", fake_view)
    assert not qos.throttled()
    cur["p"] = 0.95
    assert qos.throttled()          # transition 1: on
    cur["p"] = 0.8
    assert qos.throttled()          # hysteresis: still on above LO
    cur["p"] = 0.7
    assert not qos.throttled()      # transition 2: off
    assert qos.totals()["throttle_transitions"] == 2


def test_throttle_latency_term(monkeypatch):
    """p99 >= SLO*RATIO_HI alone throttles; exit needs p99 <= SLO*LO."""
    monkeypatch.setenv("H2O3_QOS", "1")
    monkeypatch.setenv("H2O3_QOS_SLO_MS", "10")
    monkeypatch.setenv("H2O3_QOS_P99_RATIO_HI", "2.0")
    monkeypatch.setenv("H2O3_QOS_P99_RATIO_LO", "1.5")
    monkeypatch.setattr(qos, "pressure_view", lambda max_age_s=None:
                        qos.PressureView(0.1, False, False,
                                         time.monotonic()))
    p99 = {"v": 5.0}
    monkeypatch.setattr(qos, "serving_p99_ms", lambda: p99["v"])
    assert not qos.throttled()
    p99["v"] = 25.0                  # 2.5x SLO → throttle
    assert qos.throttled()
    p99["v"] = 17.0                  # 1.7x: above exit ratio → hold
    assert qos.throttled()
    p99["v"] = 12.0                  # 1.2x: below exit ratio → open
    assert not qos.throttled()


def test_admission_gate_bounded_wait(monkeypatch):
    """admission_gate can never deadlock a sweep: the wait is bounded by
    H2O3_QOS_THROTTLE_MAX_WAIT_S even with the throttle stuck closed."""
    monkeypatch.setenv("H2O3_QOS", "1")
    monkeypatch.setenv("H2O3_QOS_THROTTLE_MAX_WAIT_S", "0.15")
    monkeypatch.setenv("H2O3_QOS_THROTTLE_POLL_MS", "20")
    monkeypatch.setattr(qos, "pressure_view", lambda max_age_s=None:
                        qos.PressureView(0.99, True, True,
                                         time.monotonic()))
    t0 = time.monotonic()
    waited = qos.admission_gate("cand_0")
    assert 0.1 <= waited <= 1.0
    assert time.monotonic() - t0 < 2.0
    assert qos.totals()["throttle_waits_ms"] >= 100


# -------------------------------------------------------------- pressure view

def test_pressure_view_invariant(monkeypatch):
    """Within one snapshot shed_serving implies evict_cache (0.97 vs 0.9):
    training artifacts always shed before serving requests do."""
    from h2o3_tpu.runtime import memory_ledger as ml

    for p in (0.5, 0.91, 0.98):
        monkeypatch.setattr(ml, "pressure", lambda p=p: p)
        v = qos.pressure_view()
        assert not (v.shed_serving and not v.evict_cache)
        assert v.value == p
    # threshold ordering that guarantees it
    assert 0.97 >= ml.evict_threshold()


def test_admission_sheds_through_view(monkeypatch):
    """Serving admission's 429 path reads the same snapshot: pressure
    0.98 rejects, pressure 0.5 admits."""
    from h2o3_tpu.runtime import memory_ledger as ml
    from h2o3_tpu.serving.admission import AdmissionController, RejectedError
    from h2o3_tpu.serving.config import ServingConfig
    from h2o3_tpu.serving.metrics import ServingMetrics

    ctl = AdmissionController(ServingConfig(), ServingMetrics())
    monkeypatch.setattr(ml, "pressure", lambda: 0.98)
    with pytest.raises(RejectedError):
        ctl.admit("m")
    monkeypatch.setattr(ml, "pressure", lambda: 0.5)
    ctl.admit("m")
    ctl.release("m")


# ------------------------------------------------------------- observability

def test_gate_state_and_profiler_fold(monkeypatch):
    monkeypatch.setenv("H2O3_QOS", "1")
    from h2o3_tpu.runtime import profiler

    assert qos.gate_state()["holder"] == "idle"
    with qos.serving_dispatch("gbm_7"):
        gs = qos.gate_state()
        assert gs["holder"] == "serving"
        assert gs["serving_detail"] == "gbm_7"
    qos.yield_point("tree_block")
    gs = qos.gate_state()
    assert gs["holder"] == "training"
    assert gs["last_training_site"] == "tree_block"
    fold = profiler.qos_stats()
    assert fold["active"] and fold["totals"]["yields"] == 1


# ----------------------------------------------------------- bit-exactness

def _canon_history(model):
    """Scoring-history rows with NaN canonicalized (NaN != NaN) and the
    wall-clock timestamp dropped."""
    rows = []
    for r in model.scoring_history:
        rows.append({k: ("nan" if isinstance(v, float) and math.isnan(v)
                         else v)
                     for k, v in r.items() if k != "timestamp"})
    return rows


def test_gbm_bit_exact_under_qos(monkeypatch):
    """QoS changes WHEN tree programs dispatch, never what they compute:
    forest, varimp, scoring history and early-stop tree count are
    bit-identical with the gate armed."""
    import jax

    from h2o3_tpu.models.gbm import GBM

    fr = _rng_frame(rows=200, seed=3)
    kw = dict(ntrees=4, max_depth=3, seed=42, score_tree_interval=2)

    monkeypatch.delenv("H2O3_QOS", raising=False)
    m_off = GBM(**kw).train(x=["a", "b", "c", "d"], y="y",
                            training_frame=fr).model
    monkeypatch.setenv("H2O3_QOS", "1")
    qos.reset()
    m_on = GBM(**kw).train(x=["a", "b", "c", "d"], y="y",
                           training_frame=fr).model

    assert m_on.ntrees_built == m_off.ntrees_built
    for a, b in zip(jax.tree_util.tree_leaves(m_on.forest),
                    jax.tree_util.tree_leaves(m_off.forest)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert m_on.varimp(use_pandas=False) == m_off.varimp(use_pandas=False)
    assert _canon_history(m_on) == _canon_history(m_off)
    assert qos.totals()["yields"] > 0  # the gate actually ran


def test_kmeans_bit_exact_under_segmentation(monkeypatch):
    """The estimator engine's while_loop segmentation (bounded device
    programs with yields between them) is the identity on results."""
    from h2o3_tpu.models.kmeans import KMeans

    rng = np.random.default_rng(5)
    X = np.concatenate([rng.normal(i * 4.0, 1.0, size=(60, 3))
                        for i in range(3)]).astype(np.float64)
    fr = Frame.from_numpy(X, names=["x0", "x1", "x2"])

    monkeypatch.delenv("H2O3_QOS", raising=False)
    monkeypatch.delenv("H2O3_QOS_EST_ITERS_PER_DISPATCH", raising=False)
    m_off = KMeans(k=3, max_iterations=12, seed=9).train(
        x=["x0", "x1", "x2"], training_frame=fr).model
    monkeypatch.setenv("H2O3_QOS", "1")
    monkeypatch.setenv("H2O3_QOS_EST_ITERS_PER_DISPATCH", "3")
    qos.reset()
    m_on = KMeans(k=3, max_iterations=12, seed=9).train(
        x=["x0", "x1", "x2"], training_frame=fr).model
    assert np.array_equal(np.asarray(m_off.centers()),
                          np.asarray(m_on.centers()))


def test_glm_bit_exact_under_segmentation(monkeypatch):
    from h2o3_tpu.models.glm import GLM

    fr = _rng_frame(rows=200, seed=11, binomial=False)
    kw = dict(family="gaussian", lambda_=0.01, max_iterations=10, seed=1)

    monkeypatch.delenv("H2O3_QOS", raising=False)
    monkeypatch.delenv("H2O3_QOS_EST_ITERS_PER_DISPATCH", raising=False)
    g_off = GLM(**kw).train(x=["a", "b", "c", "d"], y="y",
                            training_frame=fr)
    monkeypatch.setenv("H2O3_QOS", "1")
    monkeypatch.setenv("H2O3_QOS_EST_ITERS_PER_DISPATCH", "3")
    qos.reset()
    g_on = GLM(**kw).train(x=["a", "b", "c", "d"], y="y",
                           training_frame=fr)
    assert g_off.coef() == g_on.coef()


def test_segment_stops(monkeypatch):
    from h2o3_tpu.models import estimator_engine as est

    monkeypatch.delenv("H2O3_QOS", raising=False)
    monkeypatch.delenv("H2O3_QOS_EST_ITERS_PER_DISPATCH", raising=False)
    assert est.max_iters_per_dispatch() == 0      # QoS off: unbounded
    assert est.segment_stops(100) == [100]
    monkeypatch.setenv("H2O3_QOS", "1")
    assert est.max_iters_per_dispatch() == 32     # QoS on: default cap
    monkeypatch.setenv("H2O3_QOS_EST_ITERS_PER_DISPATCH", "3")
    assert est.segment_stops(10) == [3, 6, 9, 10]
    assert est.segment_stops(3) == [3]
    assert est.segment_stops(2) == [2]


# ------------------------------------------------------------------ slow soak

@pytest.mark.slow
def test_qos_concurrent_soak_slow(tmp_path, monkeypatch):
    """Full concurrent soak: live REST server + open-loop serving load
    while an in-process grid sweep trains on the same backend, QoS armed.

    Slow-lane on purpose: tier-1 already consumes ~700 s of its 870 s
    budget and this needs multi-second load windows for stable
    percentiles. It asserts completion, gate activity and at most 1 %
    hard errors, never a p99 ratio."""
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "deploy"))
    try:
        import loadgen
    finally:
        sys.path.pop(0)

    from h2o3_tpu.models.gbm import GBM
    from h2o3_tpu.rest.server import start_server
    from h2o3_tpu.runtime.dkv import DKV

    fr = _rng_frame(rows=600, seed=2)
    est = GBM(ntrees=5, max_depth=3, seed=42).train(
        x=["a", "b", "c", "d"], y="y", training_frame=fr)
    DKV.put("soak_gbm", est.model)
    DKV.put(fr.key, fr)
    monkeypatch.setenv("H2O3_QOS", "1")
    qos.reset()
    srv = start_server(port=0)
    try:
        stats = loadgen.run_concurrent_sweep(
            "127.0.0.1", srv.port, "soak_gbm", fr.key,
            rate=8.0, window_s=3.0, candidates=2, sweep_rows=4000,
            sweep_ntrees=4, timeout_s=30.0, idle=False)
    finally:
        srv.stop()
    assert stats["sweep"].get("done") == 2
    assert stats["completed"] > 0
    assert stats["contended"]["p99_ms"] > 0
    assert stats["contended"]["errors"] <= 0.01 * max(
        stats["contended"]["offered"], 1)
    assert qos.totals()["yields"] > 0
