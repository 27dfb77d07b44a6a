"""Observability spine (ISSUE 6) — central metrics registry, request/job
tracing, /3/Metrics Prometheus exposition, /3/Trace Chrome-trace export,
XLA retrace counters, bounded /3/Timeline tailing, open-loop loadgen.

The acceptance pins live here: end-to-end trace-id propagation (client →
REST → Job → trainpool candidate → serving batch under ONE trace id),
Prometheus text validity (unique families, HELP/TYPE lines, monotone
counters), histogram percentiles vs a numpy reference, warm-path
zero-new-traces counter pins, and the metrics-consistency check that
makes it impossible to ship a REST counter outside the scrape surface.
"""

import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.runtime import metrics_registry as registry
from h2o3_tpu.runtime import phases, tracing
from h2o3_tpu.runtime.dkv import DKV
from h2o3_tpu.runtime.metrics_registry import (LATENCY_MS_BOUNDS, Counter,
                                               Gauge, Histogram)
from h2o3_tpu.runtime.timeline import Timeline


# -- registry primitives ------------------------------------------------------

def test_counter_monotone_and_labels():
    c = registry.counter("h2o3_test_obs_events", "test events",
                         labelnames=("kind",))
    v0 = c.value("a")
    c.inc(1, "a")
    c.inc(2.5, "a")
    c.inc(1, "b")
    assert c.value("a") == pytest.approx(v0 + 3.5)
    assert c.total() >= c.value("a") + c.value("b") - 1e-9
    with pytest.raises(ValueError):
        c.inc(-1, "a")                      # counters only go up
    # idempotent by name, kind conflicts rejected
    assert registry.counter("h2o3_test_obs_events") is c
    with pytest.raises(ValueError):
        registry.gauge("h2o3_test_obs_events")


def test_gauge_set_and_callback():
    g = registry.gauge("h2o3_test_obs_level", "a level")
    g.set(7.5)
    assert g.value() == 7.5
    g.set(3.0)                              # gauges go both ways
    assert g.value() == 3.0
    cb = registry.gauge("h2o3_test_obs_cb", "sampled", fn=lambda: 42.0)
    assert cb.value() == 42.0
    assert "h2o3_test_obs_cb 42" in registry.prometheus_text()


def test_histogram_percentiles_vs_numpy():
    """Bucket-interpolated percentile estimates must land inside the
    bucket that holds the exact numpy percentile — the histogram state is
    O(bounds), so bucket resolution is the contract, not exactness."""
    rng = np.random.default_rng(42)
    vals = rng.lognormal(mean=3.0, sigma=1.2, size=5000)   # ~1..1000 ms
    h = Histogram("local_pctl_test", "unregistered", bounds=LATENCY_MS_BOUNDS)
    for v in vals:
        h.observe(float(v))
    bounds = (0.0,) + tuple(LATENCY_MS_BOUNDS) + (float("inf"),)
    for q in (0.50, 0.95, 0.99):
        ref = float(np.percentile(vals, q * 100))
        est = h.percentile(q)
        i = next(k for k in range(len(bounds) - 1)
                 if bounds[k] < ref <= bounds[k + 1] or bounds[k + 1] == ref)
        lo, hi = bounds[i], min(bounds[i + 1], float(np.max(vals)))
        assert lo <= est <= hi + 1e-9, (q, ref, est, (lo, hi))
    s = h.summary()
    assert s["count"] == 5000
    assert s["p50"] <= s["p95"] <= s["p99"]
    assert s["min"] == pytest.approx(float(np.min(vals)))
    assert s["max"] == pytest.approx(float(np.max(vals)))


def test_histogram_percentile_edge_cases():
    h = Histogram("local_pctl_edge", "x", bounds=(1.0, 10.0))
    assert h.percentile(0.5) is None        # empty
    h.observe(5.0)
    assert 1.0 <= h.percentile(0.5) <= 5.0  # single value clamps to max
    h2 = Histogram("local_pctl_over", "x", bounds=(1.0,))
    for v in (50.0, 60.0, 70.0):
        h2.observe(v)                       # all overflow bucket
    assert 50.0 <= h2.percentile(0.99) <= 70.0


def test_label_cardinality_caps_at_overflow_series():
    """Past H2O3_METRICS_MAX_SERIES distinct label tuples, new labels
    collapse into one `_overflow` series — model churn on a long-lived
    fleet cannot grow the registry or the scrape body without bound."""
    c = registry.counter("h2o3_test_obs_churn", "churny",
                         labelnames=("model",))
    cap = registry._MAX_SERIES
    for i in range(cap + 50):
        c.inc(1, f"model_{i:04d}")
    kids = c.children()
    assert len(kids) <= cap + 1              # the cap + one overflow child
    assert (registry._OVERFLOW,) in kids
    assert c.value(registry._OVERFLOW) >= 50.0
    assert c.total() == pytest.approx(cap + 50)   # totals stay correct
    # an existing series keeps its own child past the cap
    c.inc(1, "model_0000")
    assert c.value("model_0000") == 2.0


def test_counter_rate_window():
    c = registry.counter("h2o3_test_obs_rate", "rated")
    assert c.rate(60.0) is None             # no samples yet
    c.inc(5)                                # first ring sample
    # the ring samples at most once per interval; a second inc inside the
    # interval must not crash the rate read
    c.inc(5)
    assert c.rate(60.0) is None or c.rate(60.0) >= 0.0


# -- Prometheus exposition ----------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? (NaN|[+-]Inf|-?[0-9.eE+-]+)$")


def _parse_expo(text):
    """Tiny exposition parser: {family: {"type":..., "samples": {line: v}}}."""
    fams, cur = {}, None
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("# HELP "):
            cur = line.split(" ", 3)[2]
            fams.setdefault(cur, {"help": 1, "type": None, "samples": {}})
        elif line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert name == cur, f"TYPE {name} not right after HELP {cur}"
            assert fams[cur]["type"] is None, f"duplicate TYPE for {name}"
            fams[cur]["type"] = kind
        else:
            assert _SAMPLE_RE.match(line), f"malformed sample line: {line!r}"
            mname = line.split("{")[0].split(" ")[0]
            base = re.sub(r"_(bucket|sum|count)$", "", mname)
            owner = (cur if cur and (mname == cur or base == cur
                                     or mname.startswith(cur)) else mname)
            fams.setdefault(owner, {"help": 0, "type": None, "samples": {}})
            key = line.rsplit(" ", 1)[0]
            v = line.rsplit(" ", 1)[1]
            fams[owner]["samples"][key] = float(
                v.replace("+Inf", "inf").replace("-Inf", "-inf"))
    return fams


def test_prometheus_exposition_validity():
    c = registry.counter("h2o3_test_expo_ops", "ops with labels",
                         labelnames=("op",))
    c.inc(3, 'we"ird\nlabel')               # escaping must round-trip
    h = registry.histogram("h2o3_test_expo_ms", "latencies",
                           bounds=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    text = registry.prometheus_text()
    assert text.endswith("\n")
    fams = _parse_expo(text)
    # every family exactly one HELP/TYPE pair (parser asserts duplicates)
    assert fams["h2o3_test_expo_ops_total"]["type"] == "counter"
    assert fams["h2o3_test_expo_ms"]["type"] == "histogram"
    sam = fams["h2o3_test_expo_ms"]["samples"]
    # cumulative buckets monotone, +Inf == _count
    cum = [sam[k] for k in sorted(sam) if "_bucket" in k and "+Inf" not in k]
    assert cum == sorted(cum)
    inf_key = next(k for k in sam if "+Inf" in k)
    count_key = next(k for k in sam if k.endswith("_count"))
    assert sam[inf_key] == sam[count_key] == 4
    # label escaping survived
    assert r'op="we\"ird\nlabel"' in text


def test_prometheus_counters_monotone_across_scrapes():
    c = registry.counter("h2o3_test_expo_mono", "monotone")
    c.inc(1)
    t1 = _parse_expo(registry.prometheus_text())
    c.inc(2)
    t2 = _parse_expo(registry.prometheus_text())
    for fam, d in t1.items():
        if d["type"] != "counter" or fam not in t2:
            continue
        for k, v in d["samples"].items():
            if k in t2[fam]["samples"]:
                assert t2[fam]["samples"][k] >= v, (fam, k)


# -- tracing engine -----------------------------------------------------------

def test_span_nesting_parents_and_chrome_export():
    tracing.clear()
    with tracing.span("outer", kind="request") as outer:
        tid = outer.trace_id
        with tracing.span("inner", kind="job") as inner:
            assert inner.trace_id == tid
            assert inner.parent_id == outer.span_id
            tracing.event("retry", policy="client")
        assert tracing.current() is outer
    assert tracing.current() is None
    out = tracing.export_chrome(tid)
    evs = [e for e in out["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in evs} == {"outer", "inner"}
    assert all(e["args"]["trace_id"] == tid for e in evs)
    assert any(e["ph"] == "i" and e["name"] == "retry"
               for e in out["traceEvents"])
    assert any(e["ph"] == "M" for e in out["traceEvents"])  # thread names


def test_attach_cross_thread_and_record_span():
    tracing.clear()
    with tracing.span("root", kind="request") as root:
        tid, pid = root.trace_id, root.span_id

        def worker():
            with tracing.attach(tid, pid, name="hop", kind="job"):
                tracing.record_span("retro", 0.25, kind="ingest", rows=10)

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    spans = tracing.spans(trace_id=tid)
    by_name = {s["name"]: s for s in spans}
    assert by_name["hop"]["parent_id"] == pid
    assert by_name["retro"]["parent_id"] == by_name["hop"]["span_id"]
    assert by_name["retro"]["duration_s"] == pytest.approx(0.25)
    # attach with no trace id is a recorded no-op
    with tracing.attach(None) as sp:
        assert sp is None


def test_span_error_annotation_and_ring_bound():
    tracing.clear()
    with pytest.raises(RuntimeError):
        with tracing.span("boom"):
            raise RuntimeError("kaput")
    (sp,) = tracing.spans(n=1)
    assert "RuntimeError: kaput" in sp["attrs"]["error"]
    for i in range(5000):
        tracing.record_span(f"s{i}", 0.0)
    assert tracing.span_count() <= 4096      # bounded ring, oldest evicted


def test_timeline_bounded_and_since_cursor():
    Timeline.clear()
    Timeline.record("test", "a")
    c1 = Timeline.cursor()
    Timeline.record("test", "b")
    Timeline.record("test", "c")
    tail = Timeline.snapshot(since=c1)
    assert [e["detail"] for e in tail] == ["b", "c"]
    assert all(e["seq"] > c1 for e in tail)
    assert Timeline.cursor() == c1 + 2
    for i in range(6000):
        Timeline.record("flood", str(i))
    assert len(Timeline.snapshot(n=100_000)) <= 4096   # ring stays bounded
    assert Timeline.cursor() == c1 + 2 + 6000          # cursor still exact
    Timeline.clear()


# -- XLA retrace tracker ------------------------------------------------------

def test_xla_tracker_counts_and_retrace_detection():
    phases.install_listener()
    before = phases.xla_counts()
    sig = "test:retrace_probe"
    phases._xla_count("traces", sig)
    phases._xla_count("traces", sig)         # same signature → retrace
    after = phases.xla_counts()
    assert after["traces"] == before["traces"] + 2
    assert after["retraces"] == before["retraces"] + 1
    snap = phases.xla_snapshot()
    assert snap["signatures"][sig]["traces"] == 2
    assert snap["signatures"][sig]["retraces"] == 1
    # the registry fold moved too
    assert registry.get("h2o3_xla_retraces").total() >= 1


def test_xla_signature_is_program_identity_not_span_name(cloud1):
    """Two different shape-bucket programs of one function, traced under
    ONE span, are distinct first traces (no fabricated retrace); the same
    program genuinely re-traced is counted no matter which span is open.
    Signatures are the compilation-cache keys jax reports for each compile
    request (phases._CompileTap), not whatever span happens to be open."""
    import jax
    import jax.numpy as jnp

    phases.install_listener()

    def obs_sig_probe(x):
        return x * 2.0 + 1.0

    f = jax.jit(obs_sig_probe)
    before = phases.xla_counts()
    with tracing.span("batch:one_model", kind="batch"):
        f(jnp.zeros((4,), jnp.float32)).block_until_ready()
        f(jnp.zeros((8,), jnp.float32)).block_until_ready()  # new bucket
    mid = phases.xla_counts()
    assert mid["traces"] >= before["traces"] + 2
    assert mid["retraces"] == before["retraces"], \
        "cold shape buckets under one span fabricated a retrace"
    sigs = [s for s in phases.xla_snapshot()["signatures"]
            if s.startswith("jit_obs_sig_probe-")]
    assert len(sigs) >= 2                   # per-avals identity
    # a genuine retrace (cache dropped, same program+shape) IS counted,
    # under a differently-named span
    jax.clear_caches()
    with tracing.span("candidate:other_name", kind="candidate") as sp:
        f(jnp.zeros((4,), jnp.float32)).block_until_ready()
    after = phases.xla_counts()
    assert after["retraces"] >= mid["retraces"] + 1, \
        "a real retrace under a new span name went uncounted"
    # the span got the event as an annotation (correlation without
    # leaking span names into program identity)
    assert any(ev["name"] == "xla_retrace" for ev in sp.events)


def test_cached_sweep_fit_records_zero_new_traces(cloud1):
    """Acceptance pin: a repeat sweep fit over cached programs must not
    trace a single new XLA program — the PR 4 'warm cache never
    re-traces' invariant as a counter, not a monkeypatch."""
    phases.install_listener()
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.models.grid import H2OGridSearch

    rng = np.random.default_rng(3)
    n = 200
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] - X[:, 1] > 0).astype(np.int64)
    fr = Frame.from_dict(
        {"a": X[:, 0], "b": X[:, 1], "c": X[:, 2],
         "y": np.asarray(["n", "p"], dtype=object)[y]},
        column_types={"y": "enum"})

    def fit():
        g = H2OGridSearch(
            H2OGradientBoostingEstimator(ntrees=2, seed=1),
            {"max_depth": [2, 3]})
        g.train(x=["a", "b", "c"], y="y", training_frame=fr)
        assert len(g.models) == 2

    fit()                                   # cold: traces/compiles happen
    warm0 = phases.xla_counts()
    fit()                                   # warm: every program cached
    warm1 = phases.xla_counts()
    assert warm1["traces"] == warm0["traces"], \
        f"cached sweep re-traced: {warm0} -> {warm1}"
    assert warm1["retraces"] == warm0["retraces"]


# -- the fit's span tree (ISSUE 24) -------------------------------------------

# children of `train.fit`, in order, for the two fit paths; `train` itself
# holds train.resolve, train.fit, train.publish (train.cv only when it runs)
FIT_CHILDREN = {
    "glm": ["fit.response", "fit.design", "fit.init", "fit.iterate",
            "fit.metrics"],
    "gbm": ["fit.design", "fit.iterate", "fit.metrics"],
}


def _fit_frame(n=20000, seed=11):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 3))
    k = rng.integers(0, 5, n)
    y = (X[:, 0] + X[:, 1] + 0.3 * k + rng.normal(size=n) > 0.6).astype(int)
    return Frame.from_dict(
        {"a": X[:, 0], "b": X[:, 1], "c": X[:, 2],
         "k": np.asarray(list("vwxyz"), dtype=object)[k],
         "y": np.asarray(["n", "p"], dtype=object)[y]},
        column_types={"k": "enum", "y": "enum"})


def _fit_estimator(algo):
    if algo == "glm":
        from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator

        return H2OGeneralizedLinearEstimator(family="binomial", lambda_=0)
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    return H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=1)


def _children(spans, parent):
    """The fit's own spans under `parent`, on its thread: a first call's
    `xla.compile` / `xla.cache_load` (kind "xla") hang under whichever of
    them was open, and the tree fit's warm-up thread (`design.warm`) runs
    beside the stages, not among them."""
    return sorted((s for s in spans if s["parent_id"] == parent["span_id"]
                   and s["kind"] == parent["kind"]
                   and s.get("thread") == parent.get("thread")),
                  key=lambda s: s["ts"])


def _check_fit_tree(spans, algo):
    """One `train` root with the table's children in order, one trace id,
    `train.fit`'s children inside its interval; returns the root."""
    (root,) = [s for s in spans if s["name"] == "train"]
    tree = [s for s in spans if s["kind"] == "fit"]
    assert {s["trace_id"] for s in tree} == {root["trace_id"]}
    assert root["attrs"]["algo"] == algo and root["attrs"]["rows"] == 20000
    assert root["attrs"]["predictors"] == 4
    top = _children(spans, root)
    assert [s["name"] for s in top] == ["train.resolve", "train.fit",
                                        "train.publish"]
    fit = top[1]
    kids = _children(spans, fit)
    assert [s["name"] for s in kids] == FIT_CHILDREN[algo]
    eps = 5e-3      # `ts` is the wall clock, durations the monotonic one
    for k in kids:
        assert k["ts"] >= fit["ts"] - eps
        assert k["ts"] + k["duration_s"] <= fit["ts"] + fit["duration_s"] + eps
    # what only the two containers cover (their self time) is a sliver
    self_s = (root["duration_s"] - sum(s["duration_s"] for s in top)
              + fit["duration_s"] - sum(s["duration_s"] for s in kids))
    assert 0 <= self_s < 0.10 * root["duration_s"]
    return root


@pytest.mark.parametrize("algo", ["glm", "gbm"])
def test_fit_leaves_one_span_tree(cloud1, algo):
    tracing.clear()
    _fit_estimator(algo).train(y="y", training_frame=_fit_frame())
    spans = tracing.spans()
    _check_fit_tree(spans, algo)
    by_name = {s["name"]: s for s in spans}
    if algo == "glm":
        assert by_name["fit.design"]["attrs"]["cache"] in ("hit", "miss")
        assert by_name["fit.design"]["attrs"]["bytes_h2d"] > 0
        assert by_name["fit.iterate"]["attrs"]["iterations"] >= 1
        assert by_name["fit.iterate"]["attrs"]["segments"] == 1
        assert (by_name["metrics.d2h"]["parent_id"]
                == by_name["fit.metrics"]["span_id"])
        # the scores are ordered once, inside `fit.metrics` and before what
        # reads the ordering; a device scorer's float32 takes the packed key
        in_metrics = [s["name"] for s in
                      _children(spans, by_name["fit.metrics"])]
        assert in_metrics == ["metrics.d2h", "metrics.order", "metrics.auc",
                              "metrics.roc", "metrics.gains"]
        assert by_name["metrics.order"]["attrs"]["path"] == "packed32"
    else:
        # the tree fit's stages tile `fit.design` and `fit.iterate`
        stages = {"fit.design": ["design.matrix", "design.bins",
                                 "design.vectors", "design.codes",
                                 "design.state"],
                  "fit.iterate": ["iterate.setup", "iterate.dispatch",
                                  "iterate.forest", "iterate.model"],
                  "fit.metrics": ["metrics.binned", "metrics.margins",
                                  "metrics.make"]}
        root = by_name["train"]
        for parent, names in stages.items():
            kids = _children(spans, by_name[parent])
            assert [s["name"] for s in kids] == names
            bare = by_name[parent]["duration_s"] - sum(
                s["duration_s"] for s in kids)
            assert 0 <= bare < 0.05 * root["duration_s"]
        for name in ("design.matrix", "design.bins", "design.codes"):
            assert by_name[name]["attrs"]["cache"] in ("hit", "miss")
        if by_name["design.codes"]["attrs"]["cache"] == "miss":
            codes = _children(spans, by_name["design.codes"])
            assert [s["name"] for s in codes] == ["design.pack",
                                                  "design.upload"]
            assert codes[1]["attrs"]["bytes_h2d"] > 0
        assert by_name["fit.iterate"]["attrs"]["n_devices"] == 1
    # no span per iteration, level or tree: a fit is a handful of spans
    assert len([s for s in spans if s["kind"] == "fit"]) <= 24


def test_fit_spans_land_on_the_profilers_host_plane(cloud1, tmp_path):
    """The same spans, written by the same primitive, are events on
    /host:CPU of a `profiler.trace()` capture (python tracer off), nested
    in time — what the benchmark's reduction names idle gaps with."""
    import glob
    import os
    import sys

    from h2o3_tpu.runtime import profiler

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import reduce_trace

    est, fr = _fit_estimator("glm"), _fit_frame()
    with profiler.trace(str(tmp_path)):
        est.train(y="y", training_frame=fr)
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    assert os.path.getsize(path) < 8 << 20       # python tracer off
    trace = reduce_trace.load(path)
    ours = {n: (s, s + d) for _, n, s, d in trace.host
            if n.startswith(("train", "fit.", "metrics.", "design."))}
    assert set(ours) >= {"train", "train.resolve", "train.fit",
                         "train.publish", "metrics.d2h", "metrics.order",
                         *FIT_CHILDREN["glm"]}

    def inside(a, b):
        return ours[b][0] <= ours[a][0] and ours[a][1] <= ours[b][1]

    assert inside("train.fit", "train") and inside("train.resolve", "train")
    assert all(inside(n, "train.fit") for n in FIT_CHILDREN["glm"])
    assert inside("metrics.d2h", "fit.metrics")
    assert inside("metrics.order", "fit.metrics")
    assert inside("metrics.auc", "fit.metrics")
    assert inside("design.upload", "fit.design")
    # and a gap inside the design build is named by it, not by a container
    lo, hi = ours["fit.design"]
    assert trace.host_doing(lo + 0.1 * (hi - lo), hi - 0.1 * (hi - lo)) \
        not in ("train", "train.fit")


# -- REST surfaces ------------------------------------------------------------

@pytest.fixture(scope="module")
def obs_server():
    from h2o3_tpu.rest import start_server
    from h2o3_tpu.serving import reset_engine

    srv = start_server(port=0)
    engine = reset_engine()
    yield srv
    srv.stop()
    reset_engine()


def _http(method, port, path, headers=None, body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=(body if body is not None
              else (b"" if method == "POST" else None)),
        method=method, headers=headers or {})
    with urllib.request.urlopen(req) as r:
        raw = r.read()
        ctype = r.headers.get("Content-Type", "")
        out = raw if "json" not in ctype else json.loads(raw)
        return out, dict(r.headers)


def _tiny_frame(key, n=200, seed=7):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.int64)
    fr = Frame.from_dict(
        {"a": X[:, 0], "b": X[:, 1], "c": X[:, 2],
         "y": np.asarray(["n", "p"], dtype=object)[y]},
        column_types={"y": "enum"})
    fr.key = key
    DKV.put(key, fr)
    return fr


def _register_all_subsystems():
    """Force-register every subsystem's registry families (they register
    lazily on first record; the scrape/consistency checks need the
    declarations, not traffic)."""
    from h2o3_tpu.frame import ingest_stats, munge_stats
    from h2o3_tpu.parallel import mesh
    from h2o3_tpu.runtime import faults, fleet, memory_ledger, retry, \
        supervisor, trainpool
    from h2o3_tpu.serving import metrics as serving_metrics
    from h2o3_tpu.serving import router

    serving_metrics._registry()
    router._router_registry()  # router families + /3/Router bindings
    ingest_stats._registry()
    munge_stats._registry()
    trainpool._registry()
    retry._reg_counter()
    faults._fired_counter(registry)
    memory_ledger._registry()
    fleet._registry()          # fleet families + /3/Fleet bindings
    mesh._lane_registry()      # collective-skew/straggler families
    supervisor._registry()     # supervisor families + /3/Supervisor bindings


def test_rest_metrics_prometheus_endpoint(obs_server, cloud1):
    """Acceptance: GET /3/Metrics serves valid Prometheus text covering
    serving, ingest, munge, training, retry, and fault counters."""
    _register_all_subsystems()
    _http("GET", obs_server.port, "/3/Cloud")   # at least one request done
    body, headers = _http("GET", obs_server.port, "/3/Metrics")
    assert headers["Content-Type"].startswith("text/plain")
    assert "version=0.0.4" in headers["Content-Type"]
    text = body.decode()
    fams = _parse_expo(text)                 # parses clean
    for needle in ("h2o3_serving_requests_total", "h2o3_ingest_rows_total",
                   "h2o3_munge_ops", "h2o3_train_submitted_total",
                   "h2o3_retry_events", "h2o3_fault_fires",
                   "h2o3_rest_requests_total", "h2o3_xla_retraces",
                   "h2o3_rest_request_ms_bucket"):
        assert needle in text, f"{needle} missing from /3/Metrics"
    # the scrape itself is counted: a second scrape sees the first
    body2, _ = _http("GET", obs_server.port, "/3/Metrics")
    assert 'handler="metrics"' in body2.decode()
    assert fams  # non-empty
    # ?schema=1 returns the ObservabilityV3 field metadata as JSON (the
    # sibling /3/*/metrics convention), also folded into /3/Metadata
    doc, _ = _http("GET", obs_server.port, "/3/Metrics?schema=1")
    assert doc["name"] == "ObservabilityV3" and doc["fields"]
    meta, _ = _http("GET", obs_server.port, "/3/Metadata/schemas")
    assert any(s.get("name") == "ObservabilityV3"
               for s in meta["schemas"])


def test_rest_trace_header_echo_and_server_mint(obs_server, cloud1):
    tid = tracing.new_trace_id()
    _, headers = _http("GET", obs_server.port, "/3/Cloud",
                       headers={"X-H2O3-Trace-Id": tid})
    assert headers.get("X-H2O3-Trace-Id") == tid       # client id echoed
    _, headers2 = _http("GET", obs_server.port, "/3/Cloud")
    minted = headers2.get("X-H2O3-Trace-Id")
    assert minted and minted != tid                    # server minted one
    out, _ = _http("GET", obs_server.port, f"/3/Trace?trace_id={tid}")
    evs = [e for e in out["traceEvents"] if e.get("ph") == "X"]
    assert len(evs) == 1 and evs[0]["cat"] == "request"
    assert evs[0]["args"]["trace_id"] == tid


def test_rest_timeline_since_cursor_tailing(obs_server, cloud1):
    out1, _ = _http("GET", obs_server.port, "/3/Timeline")
    cur = out1["cursor"]
    assert "spans" in out1                   # recent span summaries fold in
    _http("GET", obs_server.port, "/3/Cloud")          # records an event
    out2, _ = _http("GET", obs_server.port, f"/3/Timeline?since={cur}")
    assert out2["cursor"] > cur
    assert out2["events"], "incremental tail missed the new event"
    assert all(e["seq"] > cur for e in out2["events"])
    # n= caps the page
    out3, _ = _http("GET", obs_server.port, "/3/Timeline?n=1")
    assert len(out3["events"]) <= 1
    # n=0 clamps to 1: it must not dump the whole ring, and with since=
    # it must not return an empty page whose cursor skips unread events
    out4, _ = _http("GET", obs_server.port, "/3/Timeline?n=0")
    assert len(out4["events"]) <= 1
    out5, _ = _http("GET", obs_server.port,
                    f"/3/Timeline?since={cur}&n=0")
    assert out5["events"] and out5["cursor"] == out5["events"][-1]["seq"]


def test_trace_id_propagation_client_job_candidate_batch(obs_server, cloud1):
    """THE tentpole acceptance pin: one client-minted trace id correlates
    the REST request spans, the training Job span, every trainpool
    candidate span, and the serving batch span of the follow-up predict."""
    from h2o3_tpu.client import H2OConnection

    fr = _tiny_frame("obs_e2e_fr")
    conn = H2OConnection(f"http://127.0.0.1:{obs_server.port}")
    with conn.trace() as tid:
        r = conn.post("/99/Grid/gbm", training_frame=fr.key,
                      response_column="y",
                      hyper_parameters=json.dumps({"max_depth": [2, 3]}),
                      ntrees=2, seed=1, parallelism=2)
        job_key = r["job"]["key"]["name"]
        conn.wait_for_job(job_key, timeout=300.0)
        grid = DKV.get(DKV.get(job_key).result)   # in-process server: DKV
        mid = grid.models[0].model.model_id
        conn.post(f"/3/Predictions/models/{mid}/frames/{fr.key}")
    # a request span records when the HANDLER finishes writing the
    # response, which legitimately races the client's next request — poll
    # briefly until the final request span (the batch span's parent) has
    # landed in the ring before pinning the tree shape
    import time as _time

    deadline = _time.time() + 5.0
    while True:
        out, _ = _http("GET", obs_server.port, f"/3/Trace?trace_id={tid}")
        evs = [e for e in out["traceEvents"] if e.get("ph") == "X"]
        _ids = {e["args"]["span_id"] for e in evs}
        if all(e["args"]["parent_id"] in _ids for e in evs
               if e["args"]["parent_id"] is not None) \
                or _time.time() > deadline:
            break
        _time.sleep(0.05)
    kinds = {e["cat"] for e in evs}
    assert {"request", "job", "candidate", "batch"} <= kinds, kinds
    assert all(e["args"]["trace_id"] == tid for e in evs)
    # both grid candidates landed in the one trace
    cands = [e for e in evs if e["cat"] == "candidate"]
    assert len(cands) == 2
    # spans parent into a single tree: every non-root span's parent exists
    ids = {e["args"]["span_id"] for e in evs}
    roots = [e for e in evs if e["args"]["parent_id"] is None]
    non_roots = [e for e in evs if e["args"]["parent_id"] is not None]
    assert roots and non_roots
    assert all(e["args"]["parent_id"] in ids for e in non_roots)


def test_rest_train_hangs_fit_tree_under_job_span(obs_server, cloud1):
    """ISSUE 24: a `POST /3/ModelBuilders/glm` leaves ONE tree at
    /3/Trace — request and job spans, and the fit's `train` under the
    `job:` span with its whole span tree, all on the client's trace id."""
    import time as _time

    from h2o3_tpu.client import H2OConnection

    fr = _fit_frame()
    fr.key = "obs_fit_tree_fr"
    DKV.put(fr.key, fr)
    conn = H2OConnection(f"http://127.0.0.1:{obs_server.port}")
    with conn.trace() as tid:
        r = conn.post("/3/ModelBuilders/glm", training_frame=fr.key,
                      response_column="y", family="binomial", lambda_=0)
        conn.wait_for_job(r["job"]["key"]["name"], timeout=300.0)
    # the job reads DONE inside train.publish, before `train` and `job:`
    # close: poll until the job span has landed in the ring
    deadline = _time.time() + 5.0
    while True:
        out, _ = _http("GET", obs_server.port, f"/3/Trace?trace_id={tid}")
        evs = [e for e in out["traceEvents"] if e.get("ph") == "X"]
        if any(e["cat"] == "job" for e in evs) or _time.time() > deadline:
            break
        _time.sleep(0.05)
    spans = [dict(name=e["name"], kind=e["cat"], ts=e["ts"] / 1e6,
                  duration_s=e["dur"] / 1e6, trace_id=e["args"]["trace_id"],
                  span_id=e["args"]["span_id"],
                  parent_id=e["args"]["parent_id"], attrs=e["args"])
             for e in evs]
    assert {s["trace_id"] for s in spans} == {tid}
    root = _check_fit_tree(spans, "glm")
    (job,) = [s for s in spans if s["kind"] == "job"]
    assert job["name"].startswith("job:glm_rest_")
    assert root["parent_id"] == job["span_id"]


def test_rest_warm_predict_zero_new_traces_pin(obs_server, cloud1):
    """Acceptance: warm-cache predict records ZERO new XLA traces — the
    counter pin that replaces monkeypatch-based no-retrace assertions."""
    fr = _tiny_frame("obs_warm_fr", seed=11)
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    est = H2OGradientBoostingEstimator(ntrees=2, max_depth=2, seed=1,
                                       model_id="obs_warm_gbm")
    est.train(x=["a", "b", "c"], y="y", training_frame=fr)
    DKV.put("obs_warm_gbm", est.model)
    _http("POST", obs_server.port,
          f"/3/Predictions/models/obs_warm_gbm/frames/{fr.key}")
    c1 = phases.xla_counts()
    _http("POST", obs_server.port,
          f"/3/Predictions/models/obs_warm_gbm/frames/{fr.key}")
    c2 = phases.xla_counts()
    assert c2["traces"] == c1["traces"], "warm predict traced a program!"
    assert c2["retraces"] == c1["retraces"]
    assert c2["compiles"] == c1["compiles"]


def test_metrics_consistency_registry_backs_every_rest_field(
        obs_server, cloud1):
    """CI check (ISSUE 6 satellite): every registered metric appears in
    GET /3/Metrics, every declared REST binding resolves to a live
    registry metric, and every counter-ish `totals`/`cv` field of every
    /3/*/metrics document is declared — a new counter cannot ship outside
    the scrape surface."""
    from h2o3_tpu.rest import schemas

    _register_all_subsystems()
    text = _http("GET", obs_server.port, "/3/Metrics")[0].decode()
    # 1) every registered family reaches the scrape surface
    for name in registry.names():
        m = registry.get(name)
        expo = (name if name.endswith("_total") or m.kind != "counter"
                else name + "_total")
        assert f"# TYPE {expo} {m.kind}" in text, \
            f"registered metric {name} missing from /3/Metrics"
    # 2) every declared binding points at a live metric
    bindings = registry.rest_bindings()
    for endpoint, fields in bindings.items():
        for path, metric in fields.items():
            assert registry.get(metric) is not None, \
                f"{endpoint}:{path} bound to unknown metric {metric}"
    # 3) every counter-ish field of every metrics document is declared
    derived = ("_per_s",)                    # ratios derived at read time
    for endpoint, route in schemas.METRICS_ENDPOINTS.items():
        doc, _ = _http("GET", obs_server.port, route)
        declared = bindings.get(endpoint, {})
        for section in ("totals", "cv"):
            for k, v in (doc.get(section) or {}).items():
                if not isinstance(v, (int, float)):
                    continue
                if any(k.endswith(sfx) for sfx in derived):
                    continue
                assert f"{section}.{k}" in declared, \
                    (f"/3/{endpoint} field {section}.{k} is not "
                     f"registry-backed (bind_rest_field missing)")


def test_profiler_folds_registry_xla_and_tracing(obs_server, cloud1):
    doc, _ = _http("GET", obs_server.port, "/3/Profiler")
    assert "totals" in doc["xla"]
    assert "retraces" in doc["xla"]["totals"]
    assert "recorded" in doc["tracing"]
    # the registry fold is served under /3/Profiler too (the documented
    # contract of metrics_registry.snapshot())
    assert any(k.startswith("h2o3_rest_requests") for k in doc["metrics"])
    fam = doc["metrics"]["h2o3_rest_requests"]
    assert fam["kind"] == "counter" and fam["series"]


def test_fault_fire_annotates_span_and_registry(cloud1):
    from h2o3_tpu.runtime import faults

    tracing.clear()
    faults.arm("client.request", error="conn", rate=1.0, seed=1)
    try:
        with tracing.span("req", kind="request") as sp:
            with pytest.raises(Exception):
                faults.check("client.request", "unit")
        assert any(ev["name"] == "fault_fired" for ev in sp.events)
        assert registry.get("h2o3_fault_fires").value("client.request") >= 1
    finally:
        faults.reset()


def test_retry_bump_feeds_registry_and_span_event(cloud1):
    from h2o3_tpu.runtime import retry

    before = registry.get("h2o3_retry_events")
    before_v = before.value("unit_test_policy", "retries") if before else 0
    with tracing.span("op") as sp:
        retry.record("unit_test_policy", "retries")
    c = registry.get("h2o3_retry_events")
    assert c.value("unit_test_policy", "retries") == before_v + 1
    assert any(ev["name"] == "retry" for ev in sp.events)


# -- open-loop loadgen --------------------------------------------------------

def test_loadgen_open_loop_percentiles(obs_server, cloud1):
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "deploy"))
    from loadgen import run_load_open

    fr = _tiny_frame("obs_lg_fr", n=64, seed=5)
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    est = H2OGradientBoostingEstimator(ntrees=2, max_depth=2, seed=1,
                                       model_id="obs_lg_gbm")
    est.train(x=["a", "b", "c"], y="y", training_frame=fr)
    DKV.put("obs_lg_gbm", est.model)
    stats = run_load_open("127.0.0.1", obs_server.port, "obs_lg_gbm",
                          "obs_lg_fr", rate=10.0, duration_s=1.5,
                          timeout_s=30.0)
    assert stats["completed"] >= 1
    assert stats["errors"] == 0
    assert stats["offered"] == 15
    for q in ("p50_ms", "p95_ms", "p99_ms"):
        assert stats[q] is not None and np.isfinite(stats[q])
    assert stats["p50_ms"] <= stats["p95_ms"] <= stats["p99_ms"]
    # the shared-bucket contract: bounds are the platform's latency bounds
    assert tuple(stats["hist_bounds_ms"]) == tuple(LATENCY_MS_BOUNDS)
    # every request folded into the scrapable registry family (the
    # platform is loaded in this process, so the fold is active)
    fam = registry.get("h2o3_loadgen_request_ms")
    assert fam is not None
    assert fam.summary("open")["count"] >= stats["completed"]


def test_loadgen_bounds_pinned_to_registry_bounds():
    """loadgen carries a literal copy of LATENCY_MS_BOUNDS (the standalone
    CLI must not import the platform); this pin keeps them in lockstep."""
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "deploy"))
    import loadgen

    assert tuple(loadgen.LATENCY_MS_BOUNDS) == tuple(LATENCY_MS_BOUNDS)


def test_loadgen_cli_is_stdlib_only():
    """The standalone loadgen CLI must not drag jax/h2o3_tpu into the
    loadgen process — importing the module and resolving the registry
    fold outside the platform loads nothing beyond the stdlib."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys, os\n"
        "sys.path.insert(0, os.path.join(%r, 'deploy'))\n"
        "import loadgen\n"
        "assert loadgen._registry_hist() is None\n"
        "assert 'jax' not in sys.modules, 'loadgen imported jax'\n"
        "assert 'h2o3_tpu' not in sys.modules, 'loadgen imported h2o3_tpu'\n"
        "assert 'numpy' not in sys.modules, 'loadgen imported numpy'\n"
        % repo)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
