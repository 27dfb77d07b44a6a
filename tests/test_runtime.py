"""Runtime aux subsystems: Log, Timeline, DKV, Persist, profiler
(reference: water/util/Log, water/TimeLine, water/DKV, water/persist,
water/api/ProfilerHandler)."""

import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.runtime import profiler
from h2o3_tpu.runtime.dkv import DKV
from h2o3_tpu.runtime.log import Log
from h2o3_tpu.runtime.persist import for_uri
from h2o3_tpu.runtime.timeline import Timeline


def test_log_ring_and_levels(tmp_path):
    Log.clear()
    Log.set_log_dir(str(tmp_path))
    Log.info("hello world")
    Log.warn("watch out")
    Log.debug("dropped at INFO level")
    lines = Log.get_logs()
    assert any("hello world" in l and "INFO" in l for l in lines)
    assert any("watch out" in l and "WARN" in l for l in lines)
    assert not any("dropped at INFO" in l for l in lines)
    # file sink received the same lines
    files = list(tmp_path.glob("h2o3tpu_*.log"))
    assert files and "hello world" in files[0].read_text()
    Log.set_log_dir(None)
    with pytest.raises(ValueError):
        Log.set_level("NOPE")


def test_timeline_ring():
    Timeline.clear()
    for i in range(5):
        Timeline.record("compile", f"program_{i}", dur=i)
    evs = Timeline.snapshot()
    assert len(evs) == 5
    assert evs[-1]["detail"] == "program_4"
    assert evs[0]["ts"] <= evs[-1]["ts"]


def test_dkv_lifecycle():
    DKV.put("k1", Frame.from_dict({"a": np.arange(3.0)}))
    assert isinstance(DKV.get("k1"), Frame)
    assert "k1" in DKV.keys(Frame)
    DKV.remove("k1")
    assert DKV.get("k1") is None


def test_persist_spi(tmp_path):
    f = tmp_path / "x.csv"
    f.write_text("a,b\n1,2\n")
    p = for_uri(str(f))
    assert p.exists(str(f))
    assert p.size(str(f)) > 0
    with p.open(f"file://{f}") as fh:
        assert fh.read().startswith(b"a,b")
    # glob listing
    assert p.list(str(tmp_path / "*.csv")) == [str(f)]
    # cloud schemes are real pyarrow.fs backends now; in this egress-less
    # environment first use surfaces a connectivity/credential error
    # (NOT NotImplementedError — the backend exists)
    s3 = for_uri("s3://bucket/key")
    with pytest.raises((OSError, RuntimeError)):
        s3.open("s3://bucket/key")
    with pytest.raises(ValueError):
        for_uri("weird://x")


def test_profiler_samples():
    samples = profiler.stack_samples()
    assert any("MainThread" in s["thread"] for s in samples)
    prof = profiler.profile(nsamples=2, interval=0.0)
    assert prof and all(p["count"] >= 1 for p in prof)


def test_http_persist_import(tmp_path, cloud1):
    """h2o-persist-http: import_file over a loopback HTTP server."""
    import http.server
    import threading

    d = tmp_path / "serve"
    d.mkdir()
    (d / "data.csv").write_text("a,b\n1,2\n3,4\n")

    handler = lambda *a, **k: http.server.SimpleHTTPRequestHandler(
        *a, directory=str(d), **k)
    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), handler)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{httpd.server_address[1]}/data.csv"
        from h2o3_tpu.runtime import persist as P

        assert P.for_uri(url).exists(url)
        assert P.for_uri(url).size(url) > 0
        fr = h2o.import_file(url)
        assert fr.key == "data.csv"
        assert fr.vec("a").numeric_np().tolist() == [1.0, 3.0]
    finally:
        httpd.shutdown()


def test_cloud_scheme_backends_registered(cloud1):
    from h2o3_tpu.runtime import persist as P

    for scheme in ("s3", "gs", "hdfs"):
        b = P.for_uri(f"{scheme}://bucket/key")
        assert b.scheme == scheme
    with pytest.raises(ValueError):
        P.for_uri("ftp://x/y")


def test_dkv_stats_and_timeline_phases(cloud1):
    """DKV size accounting + timeline depth."""
    import numpy as np

    import h2o3_tpu as h2o
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.runtime.dkv import DKV
    from h2o3_tpu.runtime.timeline import Timeline

    rng = np.random.default_rng(0)
    X = rng.normal(size=(500, 4))
    y = (X[:, 0] > 0).astype(int)
    fr = h2o.H2OFrame_from_python(
        {**{f"c{i}": X[:, i] for i in range(4)}, "y": y.astype(str)},
        column_types={"y": "enum"})
    st = DKV.stats()
    assert st["entries"] >= 1
    assert st["by_kind"]["Frame"]["bytes"] >= 500 * 4 * 4  # 4 f32 cols min
    Timeline.clear()
    m = H2OGradientBoostingEstimator(ntrees=3, max_depth=3)
    m.train(x=[f"c{i}" for i in range(4)], y="y", training_frame=fr)
    phases = [e["detail"] for e in Timeline.snapshot() if e["kind"] == "train_phase"]
    # the training driver's cost structure is visible after the fact
    for expected in ("build_bins", "device_put", "training_metrics"):
        assert expected in phases, phases


def test_phases_accounting_and_mark_mapping():
    """runtime.phases: byte/second accumulation, mark→bucket mapping, and
    compile-time subtraction in accounted_h2d."""
    import jax.numpy as jnp

    from h2o3_tpu.runtime import phases

    phases.reset()
    phases.add("h2d", 0.5, 1000)
    phases.add("h2d", 0.25, 24)
    phases.add_mark("device_put", 0.1)          # → h2d bucket
    phases.add_mark("chunk_3_2trees", 0.2)      # → compute
    phases.add_mark("frame_to_matrix", 0.05)    # → host_prep
    phases.add_mark("margins_D2H", 0.01)        # → d2h
    snap = phases.snapshot()
    assert snap["bytes_h2d"] == 1024
    assert snap["h2d_s"] == pytest.approx(0.85, abs=1e-6)
    assert snap["compute_s"] == pytest.approx(0.2)
    assert snap["host_prep_s"] == pytest.approx(0.05)
    assert snap["d2h_s"] == pytest.approx(0.01)
    assert phases.totals(("h2d", "compute")) == pytest.approx(1.05)
    phases.reset()
    assert phases.snapshot() == {}

    # accounted_h2d: runs the thunk, books bytes; result passes through
    out = phases.accounted_h2d(lambda: jnp.arange(8), 32)
    assert int(out[3]) == 3
    assert phases.snapshot()["bytes_h2d"] == 32
    phases.reset()
