"""Memory ledger (ISSUE 8) — unified host+device byte accounting, pressure
signals and leak detection.

The acceptance pins live here: ledger-vs-census attribution reconciliation
on a real GBM fit + predict (the unattributed remainder is explicit, never
silently absorbed), kill-the-frame leak detection fires AND clears,
pressure-driven dataset-cache eviction in LRU order, the `GET /3/Memory` /
Prometheus / MemoryV3 schema surfaces, DKV.stats() delegation (the two
surfaces can never disagree), and the loadgen sustained-mode leak canary.
"""

import gc
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.runtime import memory_ledger as ml
from h2o3_tpu.runtime import metrics_registry as registry
from h2o3_tpu.runtime.dkv import DKV
from h2o3_tpu.runtime.timeline import Timeline


def _cls_frame(key, n=400, f=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.int64)
    d = {f"x{i}": X[:, i] for i in range(f)}
    d["y"] = np.asarray(["n", "p"], dtype=object)[y]
    fr = Frame.from_dict(d, column_types={"y": "enum"})
    fr.key = key
    DKV.put(key, fr)
    return fr


def _gbm(fr, **kw):
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    est = H2OGradientBoostingEstimator(
        ntrees=kw.pop("ntrees", 3), max_depth=kw.pop("max_depth", 3),
        seed=kw.pop("seed", 1), **kw)
    est.train(x=[c for c in fr.names if c != "y"], y="y",
              training_frame=fr)
    return est


def _census_device_bytes():
    import jax

    return sum(int(a.nbytes) for a in jax.live_arrays())


# -- measure(): the one deep sizer --------------------------------------------

def test_measure_counts_jax_and_nested_buffers(cloud1):
    import jax.numpy as jnp

    arr = np.zeros((1000, 4), np.float32)
    h, d = ml.measure(arr)
    assert (h, d) == (16000, 0)
    dev = jnp.zeros((256, 4), jnp.float32)
    h, d = ml.measure(dev)
    assert h == 0 and d == 256 * 4 * 4
    # nested: a dict holding both plus a Frame
    fr = Frame.from_dict({"a": np.arange(100.0)})
    h, d = ml.measure({"host": arr, "dev": dev, "frame": fr})
    assert h >= 16000 + 100 * 4 and d == 256 * 4 * 4
    # shared-buffer dedup inside one graph
    h2, _ = ml.measure({"x": arr, "y": arr})
    assert h2 == 16000


def test_dkv_nbytes_counts_device_values_and_stats_delegates(cloud1):
    """Satellite: DKV._nbytes no longer reports ~0 for device-resident
    values, and DKV.stats() is the ledger's view — one accounting."""
    import jax.numpy as jnp

    class Holder:
        pass

    h = Holder()
    h.pack = jnp.zeros((512, 6), jnp.float32)     # a device-resident value
    assert DKV._nbytes(h) >= 512 * 6 * 4
    DKV.put("ml_dev_holder", h)
    try:
        st = DKV.stats()
        assert st["by_kind"]["Holder"]["bytes"] >= 512 * 6 * 4
        # the two surfaces are the same store: every DKV entry is a ledger
        # dkv: owner and the by-kind sums agree by construction
        assert st == ml.dkv_stats()
        dkv_owners = ml.owners("dkv:ml_dev_holder")
        assert len(dkv_owners) == 1
        assert dkv_owners[0]["device_bytes"] >= 512 * 6 * 4
    finally:
        DKV.remove("ml_dev_holder")
    assert ml.owners("dkv:ml_dev_holder") == []


# -- attribution reconciliation (THE acceptance pin) ---------------------------

def test_attribution_reconciliation_gbm_fit_predict(cloud1):
    """≥95% of the device bytes a GBM train + predict leaves resident must
    be attributed to named owners; the remainder is explicitly
    `unaccounted` in /3/Memory, never silently absorbed."""
    from h2o3_tpu.models import dataset_cache

    dataset_cache.clear()
    gc.collect()
    ml.refresh(force=True)
    census0 = _census_device_bytes()
    dev0 = ml.totals()["device_bytes"]

    fr = _cls_frame("ml_attr_fr", n=20_000, f=8, seed=3)
    est = _gbm(fr, ntrees=5, max_depth=4)
    DKV.put("ml_attr_gbm", est.model)
    pred = est.model.predict(fr)
    assert pred.nrow == fr.nrow

    gc.collect()
    snap = ml.snapshot()
    census1 = _census_device_bytes()
    dev1 = snap["totals"]["device_bytes"]
    delta_census = census1 - census0
    delta_ledger = dev1 - dev0
    assert delta_census > 10_000, \
        f"workload left no device bytes to attribute ({delta_census})"
    assert delta_ledger >= 0.95 * delta_census - 65_536, \
        (f"ledger attributed {delta_ledger} of {delta_census} "
         f"census-new device bytes; owners={snap['owners'][:6]}")
    # the reconciliation contract: probe - attributed == unaccounted ≥ 0
    probe = snap["device"]
    assert probe["probe"] in ("census", "memory_stats")
    assert snap["totals"]["unaccounted_device_bytes"] == max(
        int(probe["in_use_bytes"]) - dev1, 0)
    # named owners of the taxonomy actually carry the bytes
    kinds = snap["by_kind"]
    assert "dataset_cache" in kinds and "model" in kinds
    DKV.remove("ml_attr_gbm")
    DKV.remove("ml_attr_fr")


# -- leak detection ------------------------------------------------------------

def test_kill_the_frame_leak_fires_and_clears(cloud1):
    """A dead owner whose buffers persist (something else pins them) is a
    leak: h2o3_memory_leaked_bytes rises and a timeline event fires; when
    the buffers are finally released the leak CLEARS and the owner
    retires."""
    fr = _cls_frame("ml_leak_fr", n=500)
    hold = {"buf": fr.vec("x0").data}      # the rogue cache pinning a buffer
    ml.register("frame:ml_leak_probe", kind="frame", referent=fr,
                bytes_fn=lambda: (hold["buf"].nbytes if "buf" in hold
                                  else 0, 0))
    ml.refresh(force=True)
    assert not any(l["owner"] == "frame:ml_leak_probe"
                   for l in ml.snapshot()["leaks"])
    DKV.remove("ml_leak_fr")
    del fr
    gc.collect()
    cur = Timeline.cursor()
    snap = ml.snapshot()
    leaks = [l for l in snap["leaks"] if l["owner"] == "frame:ml_leak_probe"]
    assert leaks and leaks[0]["reason"] == "referent_dead"
    assert snap["totals"]["leaked_bytes"] >= 500 * 4
    assert registry.get("h2o3_memory_leaked_bytes").value() >= 500 * 4
    evs = [e for e in Timeline.snapshot(n=10_000)
           if e["kind"] == "memory" and "leak frame:ml_leak_probe"
           in e["detail"]]
    assert evs, "leak did not land in the timeline"
    # release the pinned buffer → the leak clears and the gauge drops
    hold.clear()
    snap2 = ml.snapshot()
    assert not any(l["owner"] == "frame:ml_leak_probe"
                   for l in snap2["leaks"])
    assert not any(o["owner"] == "frame:ml_leak_probe"
                   for o in ml.owners("frame:ml_leak_probe"))
    cleared = [e for e in Timeline.snapshot(since=cur, n=10_000)
               if e["kind"] == "memory"
               and "leak_cleared frame:ml_leak_probe" in e["detail"]]
    assert cleared


def test_frame_death_cleans_cache_owners_without_leak(cloud1):
    """The healthy path: killing a frame drops its dataset-cache entry via
    weakref, unregisters the ledger owners and leaks NOTHING."""
    from h2o3_tpu.models import dataset_cache

    dataset_cache.clear()
    fr = _cls_frame("ml_clean_fr", n=300)
    _gbm(fr, ntrees=2, max_depth=2)
    ml.refresh(force=True)
    assert ml.owners("dataset_cache:"), "fit registered no cache owners"
    base0 = ml.snapshot()["totals"]["leaked_bytes"]
    DKV.remove("ml_clean_fr")
    del fr
    # the fit's helper threads (program warm-up, overlapped scoring) may
    # hold the frame a moment past train() on a loaded host: the contract
    # is that its death cleans the owners, not that one gc pass is enough
    for _ in range(50):
        gc.collect()
        if not ml.owners("dataset_cache:"):
            break
        time.sleep(0.1)
    snap = ml.snapshot()
    assert ml.owners("dataset_cache:") == []
    assert snap["totals"]["leaked_bytes"] <= base0


def test_job_end_leak_fires_and_clears(cloud1):
    """DKV keys not freed after a failed job surface in the leak report
    (and in h2o3_memory_leaked_bytes) until the key is removed."""
    fr = _cls_frame("ml_job_fr", n=300)
    est = _gbm(fr, ntrees=2, max_depth=2)
    DKV.put("ml_job_partial", est.model)
    ml.job_end("ml_job_partial", "FAILED")
    snap = ml.snapshot()
    leaks = [l for l in snap["leaks"] if l["owner"] == "dkv:ml_job_partial"]
    assert leaks and leaks[0]["reason"] == "job_failed"
    assert leaks[0]["bytes"] > 0
    DKV.remove("ml_job_partial")
    snap2 = ml.snapshot()
    assert not any(l["owner"] == "dkv:ml_job_partial"
                   for l in snap2["leaks"])
    DKV.remove("ml_job_fr")
    # a DONE job never flags anything
    DKV.put("ml_job_done", est.model)
    ml.job_end("ml_job_done", "DONE")
    assert not any(l["owner"] == "dkv:ml_job_done"
                   for l in ml.snapshot()["leaks"])
    DKV.remove("ml_job_done")


# -- pressure ------------------------------------------------------------------

def test_pressure_threshold_crossing_events(cloud1):
    events = registry.get("h2o3_memory_events") or ml._registry()["events"]
    before_hi = events.value("pressure_high", "ledger")
    before_lo = events.value("pressure_normal", "ledger")
    os.environ["H2O3_MEM_BUDGET_MB"] = "1"     # rss >> 1MB → pressure 1.0
    try:
        st = ml.refresh(force=True)
        assert st["pressure"]["value"] == 1.0
        assert ml.pressure() == 1.0
        assert events.value("pressure_high", "ledger") == before_hi + 1
    finally:
        os.environ.pop("H2O3_MEM_BUDGET_MB", None)
    st = ml.refresh(force=True)
    assert st["pressure"]["value"] < 1.0
    assert events.value("pressure_normal", "ledger") == before_lo + 1


def test_pressure_driven_cache_eviction_lru_order(cloud1, monkeypatch):
    """Past H2O3_MEM_EVICT_PRESSURE the dataset cache sheds LRU entries —
    oldest first, each eviction a traced `pressure` event."""
    from h2o3_tpu.models import dataset_cache

    dataset_cache.clear()
    frames = [_cls_frame(f"ml_press_{i}", n=300, seed=10 + i)
              for i in range(3)]
    _gbm(frames[0], ntrees=2, max_depth=2)
    owners0 = {o["owner"].rsplit(":", 1)[0]
               for o in ml.owners("dataset_cache:")}
    assert len(owners0) == 1
    base0 = owners0.pop()
    _gbm(frames[1], ntrees=2, max_depth=2)
    bases = {o["owner"].rsplit(":", 1)[0]
             for o in ml.owners("dataset_cache:")}
    base1 = (bases - {base0}).pop()
    cur = Timeline.cursor()
    monkeypatch.setenv("H2O3_MEM_BUDGET_MB", "1")
    monkeypatch.setenv("H2O3_MEM_EVICT_PRESSURE", "0.5")
    try:
        ml.refresh(force=True)
        _gbm(frames[2], ntrees=2, max_depth=2)
        evs = [e for e in Timeline.snapshot(since=cur, n=10_000)
               if e["kind"] == "memory" and e.get("trigger") == "pressure"]
        owners_evicted = [e["owner"] for e in evs]
        assert base0 in owners_evicted and base1 in owners_evicted, evs
        assert owners_evicted.index(base0) < owners_evicted.index(base1), \
            "pressure eviction was not LRU-ordered"
        s = dataset_cache.snapshot()
        assert s["entries"] == 1 and s["evictions"] >= 2
    finally:
        monkeypatch.delenv("H2O3_MEM_BUDGET_MB", raising=False)
        ml.refresh(force=True)     # drop the cached pressure=1.0 state
    for fr in frames:
        DKV.remove(fr.key)


# -- scorer cache + eviction events -------------------------------------------

def test_scorer_owner_attributes_deleted_model_and_evict_events(cloud1):
    """While the DKV holds a model its scorer owner reports 0 (no double
    count); after DELETE the compiled-scorer cache is what pins it and the
    bytes move to `scorer:<key>:<kind>`; invalidation emits an evict
    event."""
    from h2o3_tpu.serving.model_cache import ScorerCache

    fr = _cls_frame("ml_sc_fr", n=300)
    est = _gbm(fr, ntrees=2, max_depth=2)
    DKV.put("ml_sc_gbm", est.model)
    cache = ScorerCache(capacity=4)
    entry, hit = cache.get_or_build("ml_sc_gbm", est.model, "predict")
    assert not hit
    ml.refresh(force=True)
    (own,) = ml.owners("scorer:ml_sc_gbm:predict")
    assert own["host_bytes"] + own["device_bytes"] == 0   # DKV accounts it
    DKV.remove("ml_sc_gbm")
    DKV.remove(est.model.model_id)   # train auto-registered this key too
    ml.refresh(force=True)
    (own,) = ml.owners("scorer:ml_sc_gbm:predict")
    assert own["host_bytes"] + own["device_bytes"] > 0    # scorer pins it
    cur = Timeline.cursor()
    cache.invalidate("ml_sc_gbm")
    assert ml.owners("scorer:ml_sc_gbm:predict") == []
    evs = [e for e in Timeline.snapshot(since=cur, n=1000)
           if e["kind"] == "memory"
           and e["owner"] == "scorer:ml_sc_gbm:predict"]
    assert evs and evs[0]["trigger"] == "invalidate"
    assert evs[0]["bytes"] > 0
    DKV.remove("ml_sc_fr")


def test_dataset_cache_cap_eviction_emits_event(cloud1, monkeypatch):
    """Satellite: cap evictions are no longer silent — owner, bytes freed
    and the trigger land in the timeline (and the events counter)."""
    from h2o3_tpu.models import dataset_cache

    dataset_cache.clear()
    monkeypatch.setenv("H2O3_DATASET_CACHE_ENTRIES", "1")
    events = ml._registry()["events"]
    before = events.value("evict", "dataset_cache")
    fr1 = _cls_frame("ml_cap_1", n=300, seed=20)
    fr2 = _cls_frame("ml_cap_2", n=300, seed=21)
    _gbm(fr1, ntrees=2, max_depth=2)
    cur = Timeline.cursor()
    _gbm(fr2, ntrees=2, max_depth=2)
    evs = [e for e in Timeline.snapshot(since=cur, n=10_000)
           if e["kind"] == "memory" and e.get("trigger") == "cap"
           and e["owner"].startswith("dataset_cache:")]
    assert evs and evs[0]["bytes"] > 0
    assert events.value("evict", "dataset_cache") > before
    DKV.remove("ml_cap_1")
    DKV.remove("ml_cap_2")


def test_tree_targets_layer_registered_and_released(cloud1, monkeypatch):
    """A tree fit's targets (padded device response and weights) are a
    `dataset_cache:<fp>:targets` owner holding their device bytes; a cap
    eviction and a clear each unregister it."""
    from h2o3_tpu.models import dataset_cache

    dataset_cache.clear()
    fr1 = _cls_frame("ml_tgt_1", n=300, seed=22)
    _gbm(fr1, ntrees=2, max_depth=2)
    ml.refresh(force=True)
    tgt = [o for o in ml.owners("dataset_cache:")
           if o["owner"].endswith(":targets")]
    assert len(tgt) == 1
    assert tgt[0]["device_bytes"] >= 2 * 4 * 300   # y_d and w_d, padded
    monkeypatch.setenv("H2O3_DATASET_CACHE_ENTRIES", "1")
    fr2 = _cls_frame("ml_tgt_2", n=300, seed=23)
    _gbm(fr2, ntrees=2, max_depth=2)
    assert ml.owners(tgt[0]["owner"]) == []          # evicted with fr1's entry
    assert [o for o in ml.owners("dataset_cache:")
            if o["owner"].endswith(":targets")]
    dataset_cache.clear()
    assert not [o for o in ml.owners("dataset_cache:")
                if o["owner"].endswith(":targets")]
    DKV.remove("ml_tgt_1")
    DKV.remove("ml_tgt_2")


def test_ingest_buffer_accounted(cloud1):
    from h2o3_tpu.frame import chunked

    events = ml._registry()["events"]
    before = events.value("alloc", "ingest")
    cols, info = chunked.tokenize_data(b"a,b\n1,2\n3,4\n", ",", True, 2)
    assert len(cols) == 2
    assert events.value("alloc", "ingest") == before + 1
    ml.refresh(force=True)
    (own,) = ml.owners("ingest:tokenize")
    assert own["host_bytes"] == 0      # transient: released after the parse


# -- REST + loadgen surfaces ---------------------------------------------------

@pytest.fixture(scope="module")
def mem_server():
    from h2o3_tpu.rest import start_server

    srv = start_server(port=0)
    yield srv
    srv.stop()


def _http(port, path, post=False):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=(b"" if post else None))
    with urllib.request.urlopen(req) as r:
        raw = r.read()
        return (json.loads(raw) if "json" in r.headers.get("Content-Type",
                                                           "") else raw)


def test_rest_memory_json_schema_and_prometheus(mem_server, cloud1):
    fr = _cls_frame("ml_rest_fr", n=500)
    doc = _http(mem_server.port, "/3/Memory")
    assert doc["__meta"]["schema_type"] == "MemoryV3"
    assert doc["totals"]["owner_count"] >= 1
    assert any(o["owner"] == "dkv:ml_rest_fr" for o in doc["owners"])
    assert 0.0 <= doc["pressure"]["value"] <= 1.0
    assert doc["device"]["probe"] in ("census", "memory_stats",
                                      "unavailable")
    assert doc["watermarks"]["total_bytes"] >= doc["totals"]["host_bytes"]
    sch = _http(mem_server.port, "/3/Memory?schema=1")
    assert sch["name"] == "MemoryV3" and sch["fields"]
    meta = _http(mem_server.port, "/3/Metadata/schemas")
    assert any(s.get("name") == "MemoryV3" for s in meta["schemas"])
    text = _http(mem_server.port, "/3/Metrics").decode()
    for needle in ("h2o3_memory_bytes", "h2o3_memory_pressure",
                   "h2o3_memory_leaked_bytes", "h2o3_memory_owners",
                   "h2o3_memory_high_watermark_bytes",
                   'owner_kind="unaccounted"'):
        assert needle in text, f"{needle} missing from /3/Metrics"
    prof = _http(mem_server.port, "/3/Profiler")
    assert prof["memory"]["totals"]["owner_count"] >= 1
    # metrics-consistency contract: every numeric totals field of
    # /3/Memory is declared registry-backed (bind_rest_field)
    declared = registry.rest_bindings().get("memory", {})
    for k, v in doc["totals"].items():
        if isinstance(v, (int, float)):
            assert f"totals.{k}" in declared, f"totals.{k} not bound"
    DKV.remove("ml_rest_fr")


def test_loadgen_leak_canary_fields(mem_server, cloud1):
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "deploy"))
    from loadgen import run_load_open

    fr = _cls_frame("ml_lg_fr", n=64, seed=5)
    est = _gbm(fr, ntrees=2, max_depth=2)
    DKV.put("ml_lg_gbm", est.model)
    stats = run_load_open("127.0.0.1", mem_server.port, "ml_lg_gbm",
                          "ml_lg_fr", rate=10.0, duration_s=1.2,
                          timeout_s=30.0)
    assert stats["completed"] >= 1
    # per-decile samples + the post-drain closer, each with RSS and (in-
    # process) ledger bytes
    assert len(stats["mem_samples"]) >= 3
    assert all(s["rss_bytes"] and s["rss_bytes"] > 0
               for s in stats["mem_samples"])
    assert all(s["ledger_bytes"] is not None
               for s in stats["mem_samples"])
    assert stats["mem_growth_bytes_per_min"] is not None
    assert stats["ledger_growth_bytes_per_min"] is not None
    DKV.remove("ml_lg_gbm")
    DKV.remove("ml_lg_fr")
