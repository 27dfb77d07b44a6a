"""Serving subsystem (h2o3_tpu/serving/) — compiled-scorer cache,
micro-batching, admission control, metrics, and the REST predict rewiring.

CPU-only, tier-1 friendly. The acceptance pins from the PR issue live
here: a warm second `/3/Predictions` call moves only the cache-hit counter
(no new compile), and 16 concurrent requests for one model are served in
≤ 4 device batches.
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.runtime.dkv import DKV
from h2o3_tpu.serving import (RejectedError, ScoringEngine, get_engine,
                              reset_engine)
from h2o3_tpu.serving.admission import AdmissionController
from h2o3_tpu.serving.batcher import MicroBatcher
from h2o3_tpu.serving.config import ServingConfig
from h2o3_tpu.serving.metrics import LatencyHistogram, ServingMetrics
from h2o3_tpu.serving.model_cache import (CompiledScorer, ScorerCache,
                                          bucket_rows)


class StubModel:
    """Deterministic numpy 'model': predict = row sum. `fail_above`
    poisons rows whose first column exceeds it (error-isolation tests);
    `delay_s` simulates device time (batching-window tests)."""

    def __init__(self, n_features=3, fail_above=None, delay_s=0.0,
                 gate=None):
        self.x = [f"f{i}" for i in range(n_features)]
        self.fail_above = fail_above
        self.delay_s = delay_s
        self.gate = gate            # threading.Event: block until set
        self.calls = 0

    def predict(self, fr):
        self.calls += 1
        if self.gate is not None:
            assert self.gate.wait(timeout=30)
        if self.delay_s:
            time.sleep(self.delay_s)
        X = np.column_stack([fr.vec(n).numeric_np() for n in self.x])
        if self.fail_above is not None and np.any(X[:, 0] > self.fail_above):
            raise ValueError("poisoned rows in batch")
        return Frame.from_dict({"predict": X.sum(axis=1)})


def _frame(n_rows, n_features=3, base=0.0):
    rng = np.random.default_rng(int(base * 1000) % 2**31)
    return Frame.from_dict(
        {f"f{i}": base + rng.random(n_rows) for i in range(n_features)})


def _cfg(**kw):
    return ServingConfig(**{**dict(
        max_batch_rows=4096, max_wait_ms=5.0, request_timeout_s=30.0,
        idle_worker_s=2.0, max_queue=64, model_inflight=64,
        retry_after_s=1.0, cache_capacity=8), **kw})


# -- model_cache ------------------------------------------------------------
def test_bucket_rows_padding_ladder():
    assert bucket_rows(1) == 64
    assert bucket_rows(64) == 64
    assert bucket_rows(65) == 128
    assert bucket_rows(200) == 256
    assert bucket_rows(300) == 512
    assert bucket_rows(513) == 1024
    assert bucket_rows(1025) == 1536


def test_cache_hit_miss_eviction():
    cache = ScorerCache(capacity=2)
    m1, m2, m3 = StubModel(), StubModel(), StubModel()
    e1, hit = cache.get_or_build("m1", m1)
    assert not hit and cache.misses == 1
    e1b, hit = cache.get_or_build("m1", m1)
    assert hit and e1b is e1 and cache.hits == 1
    cache.get_or_build("m2", m2)
    cache.get_or_build("m3", m3)          # capacity 2 → m1 evicted
    assert cache.evictions == 1
    _, hit = cache.get_or_build("m1", m1)
    assert not hit                         # rebuilt after eviction
    assert len(cache) == 2


def test_cache_stale_model_identity_rebuilds():
    """Re-training under the same DKV key must not serve the old model's
    executable."""
    cache = ScorerCache(capacity=4)
    old, new = StubModel(), StubModel()
    e_old, _ = cache.get_or_build("m", old)
    e_new, hit = cache.get_or_build("m", new)
    assert not hit and e_new is not e_old and e_new.model is new


def test_compiled_scorer_pads_and_slices():
    entry = CompiledScorer("m", StubModel(), "predict")
    fr = _frame(10)
    out, compiled, _ = entry.score(fr)
    assert compiled                        # cold bucket 64
    assert out.nrow == 10                  # pad rows sliced off
    expect = sum(fr.vec(n).numeric_np() for n in fr.names)
    np.testing.assert_allclose(out.vec("predict").numeric_np(), expect,
                               rtol=1e-6)
    _, compiled, _ = entry.score(_frame(37))
    assert not compiled                    # 37 → same 64 bucket: warm
    _, compiled, _ = entry.score(_frame(100))
    assert compiled                        # 100 → new 128 bucket
    assert entry.warm_buckets == {64, 128}


def test_unsupported_output_kind_raises_value_error():
    with pytest.raises(ValueError, match="does not support contributions"):
        CompiledScorer("m", StubModel(), "contributions")


# -- metrics ----------------------------------------------------------------
def test_latency_histogram_buckets_and_stats():
    h = LatencyHistogram((1, 10, 100))
    for v in (0.5, 5, 50, 500):
        h.record(v)
    assert h.counts == [1, 1, 1, 1]        # one per bucket incl. overflow
    s = h.snapshot()
    assert s["count"] == 4 and s["min"] == 0.5 and s["max"] == 500


def test_metrics_snapshot_totals():
    m = ServingMetrics()
    m.record_request("a")
    m.record_request("b")
    m.record_rejection("b")
    m.record_batch("a", n_requests=3, n_rows=24, device_s=0.01,
                   compiled=True)
    m.record_batch("a", n_requests=1, n_rows=8, device_s=0.001,
                   compiled=False)
    snap = m.snapshot()
    assert snap["totals"]["requests"] == 2
    assert snap["totals"]["rejections"] == 1
    a = snap["models"]["a"]["counters"]
    assert a["batches"] == 2 and a["batched_requests"] == 4
    assert a["compiles"] == 1 and a["cache_hits"] == 1


# -- admission control ------------------------------------------------------
def test_admission_global_and_per_model_bounds():
    metrics = ServingMetrics()
    adm = AdmissionController(_cfg(max_queue=3, model_inflight=2), metrics)
    adm.admit("a")
    adm.admit("a")
    with pytest.raises(RejectedError):     # per-model bound
        adm.admit("a")
    adm.admit("b")
    with pytest.raises(RejectedError) as ei:   # global bound
        adm.admit("c")
    assert ei.value.retry_after_s == 1.0
    adm.release("a")
    adm.admit("c")                         # slot freed
    assert metrics.counter("a", "rejections") == 1
    assert metrics.counter("c", "rejections") == 1
    assert adm.stats()["in_flight"] == 3


def test_engine_backpressure_sheds_excess_concurrency():
    gate = threading.Event()
    model = StubModel(gate=gate)
    eng = ScoringEngine(_cfg(max_queue=2, max_wait_ms=1.0))
    results, rejects = [], []

    def call(i):
        try:
            results.append(eng.score("m", model, _frame(4)))
        except RejectedError:
            rejects.append(i)

    ts = [threading.Thread(target=call, args=(i,)) for i in range(6)]
    for t in ts:
        t.start()
    time.sleep(0.3)          # let all six hit admission while gate is shut
    gate.set()
    for t in ts:
        t.join(timeout=30)
    assert len(rejects) == 4 and len(results) == 2
    assert eng.metrics.counter("m", "rejections") == 4
    eng.shutdown()


# -- micro-batcher ----------------------------------------------------------
def test_batcher_coalesces_16_concurrent_into_few_batches():
    """Acceptance: 16 concurrent requests for one model → ≤ 4 device
    batches (and every caller gets exactly its own rows back)."""
    model = StubModel(delay_s=0.02)
    eng = ScoringEngine(_cfg(max_wait_ms=60.0, max_batch_rows=4096))
    # warm the scorer so the first batch's window isn't spent compiling
    eng.score("m", model, _frame(8, base=0.5))
    before = eng.metrics.counter("m", "batches")

    def call(i):
        fr = _frame(8, base=float(i + 1))
        out = eng.score("m", model, fr)
        expect = sum(fr.vec(n).numeric_np() for n in fr.names)
        np.testing.assert_allclose(out.vec("predict").numeric_np(),
                                   expect, rtol=1e-6)
        return out.nrow

    with ThreadPoolExecutor(max_workers=16) as ex:
        rows = list(ex.map(call, range(16)))
    assert rows == [8] * 16
    snap = eng.metrics.snapshot()["models"]["m"]["counters"]
    n_batches = snap["batches"] - before
    assert n_batches <= 4, f"16 concurrent requests took {n_batches} batches"
    assert snap["batched_rows"] == 8 + 16 * 8
    eng.shutdown()


def test_batch_error_isolation():
    """A poisoned request fails alone; coalesced batch-mates still get
    their predictions (per-request rescore fallback)."""
    model = StubModel(fail_above=100.0, delay_s=0.02)
    eng = ScoringEngine(_cfg(max_wait_ms=80.0))
    eng.score("m", model, _frame(4, base=0.5))     # warm → fast batches

    oks, errs = [], []

    def good(i):
        out = eng.score("m", model, _frame(4, base=float(i + 1)))
        oks.append(out.nrow)

    def bad():
        try:
            eng.score("m", model, Frame.from_dict(
                {"f0": [1e6, 2.0], "f1": [0.1, 0.2], "f2": [0.1, 0.2]}))
        except ValueError as e:
            errs.append(str(e))

    threads = ([threading.Thread(target=good, args=(i,)) for i in range(6)]
               + [threading.Thread(target=bad)])
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert oks == [4] * 6                  # every good request answered
    assert errs and "poisoned" in errs[0]  # the bad one got ITS error
    assert eng.metrics.counter("m", "errors") == 1
    eng.shutdown()


def test_batcher_retires_expired_pendings_unscored():
    """Requests whose caller already timed out (admission slot released)
    are retired at pop time instead of scored: abandoned work must not
    consume device time, and the deque cannot grow past the live set
    under sustained overload (ROADMAP item)."""
    from h2o3_tpu.serving.batcher import _Pending
    from h2o3_tpu.serving.metrics import ServingMetrics
    from h2o3_tpu.serving.model_cache import ScorerCache

    model = StubModel()
    gate = threading.Event()
    blocker = StubModel(gate=gate)
    metrics = ServingMetrics()
    # the timeout is also what the FRESH request below may wait in the queue:
    # wide enough that a worker thread starved by the other test processes
    # does not expire it (0.15 s did, now and then, under six xdist workers)
    cfg = _cfg(request_timeout_s=0.6, max_wait_ms=1.0)
    batcher = MicroBatcher(ScorerCache(4), metrics, cfg)

    # a caller that will give up (its model blocks past the timeout)
    def abandoned():
        with pytest.raises(TimeoutError):
            batcher.submit("m", blocker, _frame(4, base=1.0))

    t = threading.Thread(target=abandoned)
    t.start()
    time.sleep(0.05)
    # pile queued requests behind the blocked batch; their callers all
    # time out before the worker ever gets to them
    stale = [_Pending(_frame(2, base=float(i + 2)), blocker)
             for i in range(5)]
    with batcher._lock:
        w = batcher._workers[("m", "predict")]
        with w.cond:
            w.q.extend(stale)
            w.cond.notify_all()
    t.join(timeout=10)
    time.sleep(0.2)            # let every stale entry pass its timeout
    gate.set()                 # unblock the in-flight batch
    # a FRESH live request is still served promptly...
    out = batcher.submit("m", model, _frame(3, base=9.0))
    assert out.nrow == 3
    # ...and the stale ones were retired unscored (blocker scored only its
    # first batch — the expired queue never reached the device)
    deadline = time.time() + 5
    while time.time() < deadline \
            and metrics.counter("m", "expired") < len(stale):
        time.sleep(0.02)
    assert metrics.counter("m", "expired") == len(stale)
    assert blocker.calls == 1
    for p in stale:
        assert p.result is None and isinstance(p.error, TimeoutError)
    batcher.shutdown()


def test_batcher_schema_mismatch_never_coalesced():
    """Frames with different schemas must not rbind into one batch."""
    class TwoColModel(StubModel):
        def predict(self, fr):
            self.calls += 1
            cols = [fr.vec(n).numeric_np() for n in fr.names]
            return Frame.from_dict({"predict": np.sum(cols, axis=0)})

    model = TwoColModel()
    cfg = _cfg(max_wait_ms=50.0)
    metrics = ServingMetrics()
    batcher = MicroBatcher(ScorerCache(4), metrics, cfg)
    outs = {}

    def call(name, frame):
        outs[name] = batcher.submit("m", model, frame)

    t1 = threading.Thread(target=call, args=("a", _frame(4, n_features=3)))
    t2 = threading.Thread(target=call, args=("b", _frame(4, n_features=2)))
    t1.start()
    t2.start()
    t1.join(timeout=30)
    t2.join(timeout=30)
    assert outs["a"].nrow == 4 and outs["b"].nrow == 4
    assert metrics.counter("m", "batches") == 2   # one per schema
    batcher.shutdown()


def test_idle_worker_expires_and_resurrects():
    model = StubModel()
    eng = ScoringEngine(_cfg(idle_worker_s=0.2, max_wait_ms=1.0))
    assert eng.score("m", model, _frame(4)).nrow == 4
    assert len(eng.batcher._workers) == 1
    deadline = time.time() + 10
    while eng.batcher._workers and time.time() < deadline:
        time.sleep(0.05)
    assert not eng.batcher._workers        # expired after quiet period
    assert eng.score("m", model, _frame(4)).nrow == 4   # fresh worker
    eng.shutdown()


# -- REST rewiring (acceptance: warm second call skips retracing) -----------
@pytest.fixture()
def rest_server():
    from h2o3_tpu.rest import start_server

    srv = start_server(port=0)
    engine = reset_engine(_cfg(max_wait_ms=2.0))
    yield srv, engine
    srv.stop()
    reset_engine()


def _http(method, port, path, headers=None):
    import json as _json
    import urllib.request

    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=b"" if method == "POST" else None,
                                 method=method, headers=headers or {})
    with urllib.request.urlopen(req) as r:
        return _json.loads(r.read())


def _train_tiny_gbm(tag):
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    rng = np.random.default_rng(7)
    n = 200
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.int64)
    fr = Frame.from_dict(
        {"a": X[:, 0], "b": X[:, 1], "c": X[:, 2],
         "y": np.asarray(["n", "p"], dtype=object)[y]},
        column_types={"y": "enum"})
    fr.key = f"serving_fr_{tag}"
    DKV.put(fr.key, fr)
    est = H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=1,
                                       model_id=f"serving_gbm_{tag}")
    est.train(x=["a", "b", "c"], y="y", training_frame=fr)
    m = est.model
    DKV.put(m.model_id, m)
    return m.model_id, fr.key


def test_rest_warm_predict_hits_cache_no_new_compile(rest_server, cloud1):
    """Acceptance: the second `/3/Predictions` call for the same model is
    a pure cache hit — cache_hits increments, compiles does not move."""
    srv, engine = rest_server
    mid, fkey = _train_tiny_gbm("warm")
    r1 = _http("POST", srv.port, f"/3/Predictions/models/{mid}/frames/{fkey}")
    pred_key = r1["predictions_frame"]["name"]
    assert pred_key == f"prediction_{mid}_{fkey}"
    snap1 = _http("GET", srv.port, "/3/Serving/metrics")
    c1 = snap1["models"][mid]["counters"]
    assert c1["compiles"] >= 1

    from h2o3_tpu.runtime import phases

    xla1 = phases.xla_counts()
    r2 = _http("POST", srv.port, f"/3/Predictions/models/{mid}/frames/{fkey}")
    assert r2["predictions_frame"]["name"] == pred_key   # overwrote, same key
    snap2 = _http("GET", srv.port, "/3/Serving/metrics")
    c2 = snap2["models"][mid]["counters"]
    assert c2["compiles"] == c1["compiles"], "warm call re-traced!"
    # the counter pin (ISSUE 6): the warm call records ZERO new XLA traces
    # in the runtime/phases tracker — pinned at the jax-monitoring layer,
    # not just the serving cache's own bookkeeping
    xla2 = phases.xla_counts()
    assert xla2["traces"] == xla1["traces"], "warm predict traced!"
    assert xla2["retraces"] == xla1["retraces"]
    assert c2["cache_hits"] == c1["cache_hits"] + 1
    assert c2["requests"] == c1["requests"] + 1
    # histograms recorded
    h = snap2["models"][mid]["histograms"]
    assert h["queue_wait_ms"]["count"] >= 2
    assert h["batch_size"]["count"] >= 2
    # cache stats ride the same document
    assert snap2["cache"]["size"] >= 1


def test_rest_429_backpressure_with_retry_after(rest_server, cloud1):
    import urllib.error
    import urllib.request

    srv, _ = rest_server
    mid, fkey = _train_tiny_gbm("shed")
    reset_engine(_cfg(max_queue=0))        # reject everything
    with pytest.raises(urllib.error.HTTPError) as ei:
        _http("POST", srv.port, f"/3/Predictions/models/{mid}/frames/{fkey}")
    assert ei.value.code == 429
    assert ei.value.headers["Retry-After"] == "1"
    body = ei.value.read()
    assert b"429" in body or b"retry" in body.lower()
    snap = _http("GET", srv.port, "/3/Serving/metrics")
    assert snap["models"][mid]["counters"]["rejections"] == 1


def test_rest_serving_cache_clear_and_schema(rest_server, cloud1):
    srv, engine = rest_server
    mid, fkey = _train_tiny_gbm("clear")
    _http("POST", srv.port, f"/3/Predictions/models/{mid}/frames/{fkey}")
    assert len(engine.cache) >= 1
    out = _http("DELETE", srv.port, f"/3/Serving/cache?model={mid}")
    assert out["invalidated"] == 1
    sch = _http("GET", srv.port, "/3/Serving/metrics?schema=1")
    assert sch["name"] == "ServingMetricsV3"
    assert any(f["name"] == "cache" for f in sch["fields"])


def test_rest_contributions_via_serving_path(rest_server, cloud1):
    """The contributions output kind rides the serving path too (distinct
    cache entry per output_kind)."""
    srv, engine = rest_server
    mid, fkey = _train_tiny_gbm("contrib")
    r = _http("POST", srv.port,
              f"/3/Predictions/models/{mid}/frames/{fkey}"
              "?predict_contributions=true")
    assert r["predictions_frame"]["name"] == \
        f"prediction_contributions_{mid}_{fkey}"
    kinds = {e["output_kind"] for e in engine.cache.stats()["entries"]}
    assert "contributions" in kinds


def test_profiler_reports_serving_section():
    from h2o3_tpu.runtime import profiler

    reset_engine(_cfg())
    model = StubModel()
    get_engine().score("m", model, _frame(4))
    stats = profiler.serving_stats()
    assert stats["active"] and "m" in stats["models"]
    reset_engine()


# -- loadgen smoke (slow: excluded from tier-1) -----------------------------
@pytest.mark.slow
def test_loadgen_smoke_2s(cloud1):
    import importlib.util
    import os

    from h2o3_tpu.rest import start_server

    spec = importlib.util.spec_from_file_location(
        "loadgen", os.path.join(os.path.dirname(__file__), "..",
                                "deploy", "loadgen.py"))
    loadgen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loadgen)

    srv = start_server(port=0)
    reset_engine(_cfg())
    try:
        mid, fkey = _train_tiny_gbm("loadgen")
        stats = loadgen.run_load("127.0.0.1", srv.port, mid, fkey,
                                 threads=4, requests=10_000,
                                 duration_s=2.0)
        assert stats["completed"] > 0 and stats["errors"] == 0
        assert stats["throughput_rps"] > 0
        assert stats["p50_ms"] is not None and stats["p99_ms"] is not None
        snap = get_engine().snapshot()
        assert snap["models"][mid]["counters"]["batches"] >= 1
    finally:
        srv.stop()
        reset_engine()
