"""Serving fleet router (ISSUE 16) — versioned model registry, warm-loaded
replicas, canary/shadow rollout, pressure-aware admission.

Tier-1 section: the registry's atomicity contract as pure filesystem
checks (a publish killed mid-write is never visible to `live()`,
double-publish is idempotent, rollback-with-no-canary is an audited
no-op), the routing decisions as pure units (deterministic canary split,
ring ordering, drain accounting), and the REST face driven in-process —
two ring members that are THREAD-backed servers in this process, so the
full forward/failover/warm/canary-rollback paths run without spawning
interpreters. Tier-1 is at ~647 s of its 870 s budget; the tests that
need real replica PROCESSES live in the slow lane below.

Slow section: the acceptance pin — loadgen drives the router open-loop
against three live replica processes, one is killed mid-load, and the
caller sees zero hard errors while `h2o3_fleet_peer_up` flips to 0 and
post-drain p99 stays within 2x of the pre-kill baseline — plus a
one-minute `loadgen --router` soak whose `mem_growth_bytes_per_min`
canary pins the router's RSS slope (round 19)."""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.runtime import faults, fleet
from h2o3_tpu.runtime import metrics_registry as registry
from h2o3_tpu.runtime.dkv import DKV
from h2o3_tpu.runtime.timeline import Timeline
from h2o3_tpu.serving import reset_engine
from h2o3_tpu.serving.config import ServingConfig
from h2o3_tpu.serving.registry import reset_registry, versioned_key
from h2o3_tpu.serving.router import (RouterConfig, _Replica, reset_router)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_router(tmp_path):
    fleet.reset()
    faults.reset()
    reset_registry(str(tmp_path / "registry"))
    reset_router(RouterConfig())
    yield
    faults.reset()
    fleet.reset()
    reset_registry()
    reset_router()


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return json.loads(r.read())


def _post(port, path, data=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=urllib.parse.urlencode(data or {}).encode())
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


# -- registry atomicity (pure; the publish pins) -----------------------------

def _src(tmp_path, name="src.zip", blob=b"mojo-bytes"):
    p = tmp_path / name
    p.write_bytes(blob)
    return str(p)


def test_publish_mid_write_failure_never_visible(tmp_path):
    """The atomicity pin: a publish whose artifact write dies (persist
    fault on the .part write) raises, and leaves `live()`/`versions()`/
    the registry directory exactly as they were — no half-artifact a
    replica could ever list or warm-load."""
    reg = reset_registry(str(tmp_path / "reg"))
    src = _src(tmp_path)
    faults.arm("persist.open", error="io", rate=1.0, match=".part")
    cur = Timeline.cursor()
    with pytest.raises(Exception):
        reg.publish("m", "v1", source_path=src)
    assert reg.live("m") is None
    assert reg.versions("m") == []
    # nothing on disk: no final .zip, and the .part was cleaned up
    assert not os.path.exists(reg.root) or os.listdir(reg.root) == []
    evs = [e for e in Timeline.snapshot(since=cur)
           if e["kind"] == "registry" and "publish_failed" in e["detail"]]
    assert evs, "failed publish must leave an audit event"
    # disarm → the same publish goes through and the record registers
    faults.reset()
    rec = reg.publish("m", "v1", source_path=src)
    assert rec["state"] == "published"
    assert os.path.exists(rec["artifact"])
    with open(rec["artifact"], "rb") as f:
        assert f.read() == b"mojo-bytes"
    assert reg.live("m") is None          # published, not yet promoted


def test_double_publish_is_idempotent(tmp_path):
    reg = reset_registry(str(tmp_path / "reg"))
    rec1 = reg.publish("m", "v1", source_path=_src(tmp_path, "a.zip",
                                                   b"first"))
    cur = Timeline.cursor()
    # second publish of the same (model, version) with DIFFERENT bytes:
    # the first artifact wins, the record comes back untouched
    rec2 = reg.publish("m", "v1", source_path=_src(tmp_path, "b.zip",
                                                   b"second"))
    assert rec2["artifact"] == rec1["artifact"]
    assert rec2["state"] == rec1["state"] == "published"
    assert len(reg.versions("m")) == 1
    with open(rec1["artifact"], "rb") as f:
        assert f.read() == b"first"
    evs = [e for e in Timeline.snapshot(since=cur)
           if e["kind"] == "registry"]
    assert any("publish_noop" in e["detail"] for e in evs)


def test_rollback_with_no_canary_is_audited_noop(tmp_path):
    reg = reset_registry(str(tmp_path / "reg"))
    cur = Timeline.cursor()
    out = reg.rollback("m", reason="operator said so")
    assert out["noop"] is True and out["rolled_back"] is None
    evs = [e for e in Timeline.snapshot(since=cur)
           if e["kind"] == "registry" and e["detail"].startswith("rollback")]
    assert len(evs) == 1 and evs[0]["noop"] is True
    assert evs[0]["reason"] == "operator said so"


def test_lifecycle_promote_canary_retire_rules(tmp_path):
    reg = reset_registry(str(tmp_path / "reg"))
    src = _src(tmp_path)
    reg.publish("m", "v1", source_path=src)
    reg.promote("m", "v1")
    assert reg.live("m") == "v1"
    reg.publish("m", "v2", source_path=src)
    # a live version cannot be its own canary
    with pytest.raises(ValueError):
        reg.set_canary("m", "v1", 10.0)
    # the live version cannot retire out from under traffic
    with pytest.raises(ValueError):
        reg.retire("m", "v1")
    reg.set_canary("m", "v2", 25.0)
    assert reg.canary("m") == ("v2", 25.0)
    # promote is the atomic flip: live moves, canary clears, v1 retires
    reg.promote("m", "v2")
    assert reg.live("m") == "v2"
    assert reg.canary("m") == (None, 0.0)
    states = {r["version"]: r["state"] for r in reg.versions("m")}
    assert states == {"v1": "retired", "v2": "live"}
    # rollback after the canary is gone: the audited no-op again
    assert reg.rollback("m")["noop"] is True
    # canary rolled back (not promoted) ends in `failed`
    reg.publish("m", "v3", source_path=src)
    reg.set_canary("m", "v3", 10.0)
    out = reg.rollback("m", reason="p99 breach")
    assert out["rolled_back"] == "v3" and out["noop"] is False
    rec = [r for r in reg.versions("m") if r["version"] == "v3"][0]
    assert rec["state"] == "failed" and "rollback" in rec["events"]


# -- routing decisions (pure units) ------------------------------------------

def test_canary_split_is_deterministic(tmp_path):
    """A 10% canary gets exactly 10 of every 100 requests — sequence mod
    100 against the split percent, not a coin flip."""
    reg = reset_registry(str(tmp_path / "reg"))
    src = _src(tmp_path)
    reg.publish("m", "v1", source_path=src)
    reg.promote("m", "v1")
    reg.publish("m", "v2", source_path=src)
    reg.set_canary("m", "v2", 10.0)
    router = reset_router(RouterConfig())
    lanes = [router._pick_version("m", s) for s in range(200)]
    assert lanes.count(("v2", "canary")) == 20
    assert lanes.count(("v1", "live")) == 180
    assert versioned_key("m", "v2") == "m@v2"
    # no registry state at all → the unversioned pass-through lane
    assert router._pick_version("other", 0) == (None, "unversioned")


def test_candidate_ranking_and_drain_accounting():
    router = reset_router(RouterConfig(drain_errors=2,
                                       drain_cooldown_s=30.0))
    a = _Replica("a", "http://x")
    a.pressure = 0.9
    b = _Replica("b", "http://x")
    b.inflight = 2
    b.pressure = 0.1
    c = _Replica("c", "http://x")
    c.up = False
    d = _Replica("d", "http://x")
    d.drained_until = time.monotonic() + 60
    router._replicas = {r.name: r for r in (a, b, c, d)}
    # in-flight dominates, drained replicas sort behind healthy ones,
    # down replicas last
    assert [r.name for r in router._candidates()] == ["a", "b", "d", "c"]
    # at equal in-flight, scraped pressure breaks the tie
    b.inflight = 0
    assert [r.name for r in router._candidates()][0] == "b"
    # drain only after `drain_errors` CONSECUTIVE failures
    router._mark_result(a, ok=False)
    assert a.drained_until <= time.monotonic()
    router._mark_result(a, ok=True)        # success resets the streak
    router._mark_result(a, ok=False)
    assert a.drained_until <= time.monotonic()
    router._mark_result(a, ok=False)
    assert a.drained_until > time.monotonic()
    assert router._counters["drains"] == 1


# -- REST face, in-process ----------------------------------------------------

@pytest.fixture(scope="module")
def router_server():
    from h2o3_tpu.rest.server import start_server

    srv = start_server(port=0)
    yield srv
    srv.stop()


def test_router_document_and_schema(router_server):
    doc = _get(router_server.port, "/3/Router?probe=0")
    assert doc["__meta"]["schema_type"] == "RouterV3"
    assert set(doc) >= {"__meta", "ring", "inflight", "totals", "models",
                        "canary_health", "config"}
    assert set(doc["totals"]) == {
        "requests", "errors", "shed", "retries", "failovers", "drains",
        "rollbacks", "warm_loads", "shadow_requests", "shadow_errors",
        "shadow_mismatches", "shadow_dropped"}
    schema = _get(router_server.port, "/3/Router?schema=1")
    assert schema["name"] == "RouterV3"
    fields = {f["name"] for f in schema["fields"]}
    assert {"ring", "totals", "models", "canary_health"} <= fields


def test_router_sheds_budget_with_retry_after(router_server):
    reset_router(RouterConfig(max_inflight=0, retry_after_s=2.0))
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(router_server.port, "/3/Router/models/m/frames/f")
    assert ei.value.code == 429
    assert ei.value.headers["Retry-After"] == "2"
    assert b"shed" in ei.value.read()
    doc = _get(router_server.port, "/3/Router?probe=0")
    assert doc["totals"]["shed"] == 1
    with urllib.request.urlopen(
            f"http://127.0.0.1:{router_server.port}/3/Metrics") as r:
        text = r.read().decode()
    assert 'h2o3_router_shed_total{reason="budget"}' in text


def test_router_sheds_when_ring_is_empty(router_server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(router_server.port, "/3/Router/models/m/frames/f")
    assert ei.value.code == 429
    assert b"no registered replicas" in ei.value.read()
    doc = _get(router_server.port, "/3/Router?probe=0")
    assert doc["totals"]["shed"] == 1 and doc["ring"] == []


def _train_gbm(tag):
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    rng = np.random.default_rng(7)
    n = 200
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.int64)
    fr = Frame.from_dict(
        {"a": X[:, 0], "b": X[:, 1], "c": X[:, 2],
         "y": np.asarray(["n", "p"], dtype=object)[y]},
        column_types={"y": "enum"})
    fr.key = f"router_fr_{tag}"
    DKV.put(fr.key, fr)
    est = H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=1,
                                       model_id=f"router_gbm_{tag}")
    est.train(x=["a", "b", "c"], y="y", training_frame=fr)
    DKV.put(est.model.model_id, est.model)
    return est.model.model_id, fr.key


@pytest.fixture()
def serving_engine():
    engine = reset_engine(ServingConfig(
        max_batch_rows=4096, max_wait_ms=2.0, request_timeout_s=30.0,
        idle_worker_s=2.0, max_queue=64, model_inflight=64,
        retry_after_s=1.0, cache_capacity=8))
    yield engine
    reset_engine()


def test_router_routes_and_fails_over_in_process(router_server, cloud1,
                                                 serving_engine):
    """Two ring members (both thread-backed by this process's server);
    the first one's forwards fail at the injection point — the request
    retries on the peer and the caller never sees an error."""
    mid, fkey = _train_gbm("failover")
    url = f"http://127.0.0.1:{router_server.port}"
    fleet.register_peer("r1", url)
    fleet.register_peer("r2", url)
    router = reset_router(RouterConfig(refresh_s=60.0, max_attempts=3,
                                       drain_errors=100))
    # fault whichever member the ring will try FIRST: both names point at
    # one server, but their scraped pressure/p99 come from two scrapes a
    # moment apart, so the dispatch order is not always (r1, r2). The
    # scrape is fresh for refresh_s, so the request sees this same order.
    router.refresh(force=True)
    sick = router._candidates()[0].name
    faults.arm("router.forward", error="conn", rate=1.0, match=f"{sick}:")
    doc = _post(router_server.port,
                f"/3/Router/models/{mid}/frames/{fkey}")
    assert doc["predictions_frame"]["name"]
    snap = _get(router_server.port, "/3/Router?probe=0")
    assert snap["totals"]["requests"] == 1
    assert snap["totals"]["errors"] == 0
    assert snap["totals"]["failovers"] >= 1
    assert snap["totals"]["retries"] >= 1
    r1 = [r for r in snap["ring"] if r["name"] == sick][0]
    assert r1["consecutive_errors"] >= 1
    with urllib.request.urlopen(f"{url}/3/Metrics") as r:
        text = r.read().decode()
    assert f'h2o3_router_failovers_total{{replica="{sick}"}}' in text
    # the faulted replica exhausted on every lane → caller-visible 500
    faults.arm("router.forward", error="conn", rate=1.0)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(router_server.port, f"/3/Router/models/{mid}/frames/{fkey}")
    assert ei.value.code == 500
    ei.value.read()
    snap2 = _get(router_server.port, "/3/Router?probe=0")
    assert snap2["totals"]["errors"] == 1


def test_warm_load_zero_trace_pin_and_shadow(router_server, cloud1,
                                             serving_engine):
    """The warm-load pin: publish → warm (replica loads the mojo and
    primes the compiled scorer) → promote → the FIRST routed predict on
    the live version records zero new XLA traces. Then a shadow version
    mirrors traffic without ever reaching the caller."""
    from h2o3_tpu.runtime import phases

    mid, fkey = _train_gbm("warm")
    url = f"http://127.0.0.1:{router_server.port}"
    fleet.register_peer("self", url)
    router = reset_router(RouterConfig(refresh_s=60.0,
                                       shadow_compare_rows=5))
    out = _post(router_server.port, "/3/Router",
                dict(action="publish", model=mid, version="v1"))
    assert out["state"] == "published" and os.path.exists(out["artifact"])
    warm = _post(router_server.port, "/3/Router",
                 dict(action="warm", model=mid, version="v1", frame=fkey))
    assert warm["warmed"] == 1
    rep = warm["replicas"]["self"]
    assert rep["ok"] and rep["primed"] and rep["model"] == f"{mid}@v1"
    _post(router_server.port, "/3/Router",
          dict(action="promote", model=mid, version="v1"))
    xla1 = phases.xla_counts()
    doc = _post(router_server.port,
                f"/3/Router/models/{mid}/frames/{fkey}")
    assert doc["predictions_frame"]["name"]
    # the hot-swap pin (ISSUE 6 counters): warm-loading primed the scorer
    # cache for the versioned key, so the first LIVE predict is traceless
    xla2 = phases.xla_counts()
    assert xla2["traces"] == xla1["traces"], "first live predict traced!"
    assert xla2["retraces"] == xla1["retraces"]
    snap = _get(router_server.port, "/3/Router?probe=0")
    m = snap["models"][mid]
    assert m["live"] == "v1"
    v1 = [r for r in m["versions"] if r["version"] == "v1"][0]
    assert v1["state"] == "live" and "self" in v1["warmed"]
    # shadow: publish+warm v2, mirror-only — the caller's traffic stays
    # on v1 while v2 sees a copy on a daemon thread
    _post(router_server.port, "/3/Router",
          dict(action="publish", model=mid, version="v2"))
    _post(router_server.port, "/3/Router",
          dict(action="warm", model=mid, version="v2", frame=fkey))
    _post(router_server.port, "/3/Router",
          dict(action="shadow", model=mid, version="v2"))
    doc = _post(router_server.port,
                f"/3/Router/models/{mid}/frames/{fkey}")
    assert doc["predictions_frame"]["name"]
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        totals = router.snapshot(probe=False)["totals"]
        if totals["shadow_requests"] >= 1 and router._shadow_inflight == 0:
            break
        time.sleep(0.05)
    assert totals["shadow_requests"] >= 1
    assert totals["shadow_errors"] == 0
    # same artifact → identical prediction heads → no mismatch verdict
    assert totals["shadow_mismatches"] == 0
    # empty version stops shadowing
    _post(router_server.port, "/3/Router", dict(action="shadow", model=mid))
    assert router.registry.shadow(mid) is None


def test_canary_auto_rollback_pin(router_server, cloud1, serving_engine):
    """The canary pin: a version-scoped `serving.scorer` crash fault on
    the candidate makes every canary-lane request fail; after
    `canary_min_samples` observations the router rolls the registry back
    automatically, live traffic never drops, and the story is visible in
    /3/Router, the rollback counter and the timeline."""
    mid, fkey = _train_gbm("canary")
    url = f"http://127.0.0.1:{router_server.port}"
    fleet.register_peer("self", url)
    router = reset_router(RouterConfig(refresh_s=60.0, drain_errors=1000,
                                       canary_min_samples=5))
    for action, ver in (("publish", "v1"), ("warm", "v1"),
                        ("promote", "v1"), ("publish", "v2"),
                        ("warm", "v2")):
        _post(router_server.port, "/3/Router",
              dict(action=action, model=mid, version=ver,
                   **(dict(frame=fkey) if action == "warm" else {})))
    _post(router_server.port, "/3/Router",
          dict(action="canary", model=mid, version="v2", pct=50))
    # fail EXACTLY the candidate's traffic: the fault matches the
    # versioned DKV key the router rewrites canary requests to
    faults.arm("serving.scorer", error="crash", rate=1.0,
               match=versioned_key(mid, "v2"))
    cur = Timeline.cursor()
    ok, failed = 0, 0
    for _ in range(60):
        try:
            _post(router_server.port,
                  f"/3/Router/models/{mid}/frames/{fkey}")
            ok += 1
        except urllib.error.HTTPError as e:
            assert e.code == 500
            e.read()
            failed += 1
    # the 50% split sends the first 50 of 100 sequence slots to the
    # canary; the 5th failure trips the verdict, everything after rides
    # the live lane untouched
    assert failed == 5 and ok == 55
    assert router.registry.canary(mid) == (None, 0.0)
    snap = _get(router_server.port, "/3/Router?probe=0")
    m = snap["models"][mid]
    assert m["live"] == "v1" and m["canary"] is None
    v2 = [r for r in m["versions"] if r["version"] == "v2"][0]
    assert v2["state"] == "failed" and "rollback" in v2["events"]
    assert snap["totals"]["rollbacks"] == 1
    assert snap["canary_health"] == {}     # window dropped with the canary
    with urllib.request.urlopen(f"{url}/3/Metrics") as r:
        text = r.read().decode()
    line = [l for l in text.splitlines() if l.startswith(
        f'h2o3_router_rollbacks_total{{model="{mid}"}}')]
    assert line and float(line[0].rsplit(" ", 1)[1]) == 1.0
    evs = [e for e in Timeline.snapshot(since=cur)
           if e["kind"] == "registry"
           and e["detail"] == f"rollback {mid}@v2"]
    assert evs and evs[0]["reason"].startswith("auto:")
    # live traffic still flows after the rollback
    doc = _post(router_server.port,
                f"/3/Router/models/{mid}/frames/{fkey}")
    assert doc["predictions_frame"]["name"]


def test_profiler_carries_router_fold(router_server):
    fleet.register_peer("rp", "http://127.0.0.1:1")
    reset_router(RouterConfig())
    doc = _get(router_server.port, "/3/Profiler")
    assert doc["router"]["active"] is True
    assert set(doc["router"]["totals"]) >= {"requests", "shed", "rollbacks"}


# -- the real thing: three live replica PROCESSES (slow lane) -----------------
# Multi-process router tests are slow-lane by charter: tier-1 sits at
# ~647 s of its 870 s budget, and this test pays three interpreter
# startups each importing jax and training a model before the first
# routed request.

REPLICA_BODY = """
import sys, time
sys.path.insert(0, {repo!r})
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["H2O3_REPLICA_NAME"] = {name!r}
import numpy as np
import urllib.request
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.runtime.dkv import DKV
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
from h2o3_tpu.rest.server import start_server
rng = np.random.default_rng(7)
n = 500
X = rng.normal(size=(n, 3))
y = (X[:, 0] + X[:, 1] > 0).astype(np.int64)
fr = Frame.from_dict(
    {{"a": X[:, 0], "b": X[:, 1], "c": X[:, 2],
      "y": np.asarray(["n", "p"], dtype=object)[y]}},
    column_types={{"y": "enum"}})
fr.key = "fleet_frame"
DKV.put(fr.key, fr)
est = H2OGradientBoostingEstimator(ntrees=5, max_depth=3, seed=42,
                                   model_id="fleet_gbm")
est.train(x=["a", "b", "c"], y="y", training_frame=fr)
DKV.put("fleet_gbm", est.model)
srv = start_server(port={port})
for _ in range(2):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/3/Predictions/models/fleet_gbm"
        "/frames/fleet_frame", data=b"")
    with urllib.request.urlopen(req, timeout=120) as r:
        r.read()
print("READY", flush=True)
time.sleep(600)
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _load_loadgen():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "loadgen", os.path.join(REPO, "deploy", "loadgen.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow
def test_router_survives_replica_kill_mid_load():
    """The failover acceptance pin: open-loop load through the router
    against 3 replica processes; one replica is killed between measured
    windows WITHOUT telling the router, so window 2's early requests
    discover the corpse live. Zero hard errors in both windows, the dead
    replica drains and flips `h2o3_fleet_peer_up` to 0, and the post-kill
    p99 stays within 2x of the baseline."""
    from h2o3_tpu.rest.server import start_server

    loadgen = _load_loadgen()
    ports = [_free_port() for _ in range(3)]
    procs = []
    srv = None
    try:
        for i, port in enumerate(ports):
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 REPLICA_BODY.format(repo=REPO, name=f"r{i + 1}",
                                     port=port)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
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
        names = {}
        for i, port in enumerate(ports):
            name = f"r{i + 1}"
            names[name] = procs[i]
            fleet.register_peer(name, f"http://127.0.0.1:{port}")
        # drain on the FIRST failure (a dead socket is unambiguous) and
        # keep the corpse drained for the whole run; refresh_s is huge so
        # only the explicit probe and the OSError-forced refresh scrape —
        # the router must discover the corpse through a FAILED FORWARD,
        # not through a lucky background scrape beating the traffic to it
        router = reset_router(RouterConfig(refresh_s=600.0, drain_errors=1,
                                           drain_cooldown_s=120.0,
                                           max_attempts=3))
        router.refresh(force=True)
        srv = start_server(port=0)
        s1 = loadgen.run_load_open("127.0.0.1", srv.port, "fleet_gbm",
                                   "fleet_frame", rate=10.0,
                                   duration_s=4.0, router=True)
        assert s1["completed"] > 0
        assert s1["errors"] == 0 and s1["shed_429"] == 0
        # kill whichever replica the router currently ranks FIRST: the
        # next dispatch is then guaranteed to walk into the dead socket
        # (killing an arbitrary replica makes discovery — and therefore
        # the failover/drain counters — timing-dependent)
        victim_name = router._candidates()[0].name
        victim = names[victim_name]
        victim.kill()
        victim.wait(timeout=30)
        # window 2 discovers the corpse: requests that pick the dead
        # replica pay the reroute blip as LATENCY — the pin is that none
        # of them become caller-visible errors
        s2 = loadgen.run_load_open("127.0.0.1", srv.port, "fleet_gbm",
                                   "fleet_frame", rate=10.0,
                                   duration_s=4.0, router=True)
        assert s2["completed"] > 0
        assert s2["errors"] == 0 and s2["shed_429"] == 0
        # window 3 is post-drain: the dead replica is marked down and
        # drained, so p99 must recover to within 2x of the baseline
        s3 = loadgen.run_load_open("127.0.0.1", srv.port, "fleet_gbm",
                                   "fleet_frame", rate=10.0,
                                   duration_s=4.0, router=True)
        assert s3["completed"] > 0
        assert s3["errors"] == 0 and s3["shed_429"] == 0
        totals = router.snapshot(probe=True)["totals"]
        assert totals["failovers"] >= 1
        assert totals["drains"] >= 1
        gauge = registry.get("h2o3_fleet_peer_up")
        assert gauge is not None and gauge.value(victim_name) == 0.0
        ring = {r["name"]: r for r in router.snapshot(probe=False)["ring"]}
        assert ring[victim_name]["up"] == 0 and ring[victim_name]["drained"]
        # post-drain p99 within 2x of the pre-kill baseline (floored at
        # 25 ms so a sub-ms baseline doesn't turn scheduler noise into a
        # verdict)
        assert s1["p99_ms"] is not None and s3["p99_ms"] is not None
        assert s3["p99_ms"] <= 2.0 * max(s1["p99_ms"], 25.0)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        if srv is not None:
            srv.stop()


@pytest.mark.slow
def test_router_soak_memory_growth_canary(cloud1, serving_engine):
    """Sustained `loadgen --router` soak against a self-registered
    replica: a minute of open-loop traffic completes with zero hard
    errors and the RSS slope (`mem_growth_bytes_per_min`, the canary
    loadgen already computes for the serving engine) stays under a
    64 MB/min ceiling — a leaky router (response buffers, drained-replica
    state, per-request inflight entries) shows up here as a positive
    slope long before an OOM would."""
    from h2o3_tpu.rest.server import start_server

    loadgen = _load_loadgen()
    mid, fkey = _train_gbm("soak")
    srv = start_server(port=0)
    try:
        fleet.register_peer("self", f"http://127.0.0.1:{srv.port}")
        router = reset_router(RouterConfig(refresh_s=60.0, max_attempts=3,
                                           drain_errors=100))
        router.refresh(force=True)
        s = loadgen.run_load_open("127.0.0.1", srv.port, mid, fkey,
                                  rate=12.0, duration_s=60.0,
                                  timeout_s=30.0, router=True)
        assert s["completed"] >= 300, s
        assert s["errors"] == 0 and s["shed_429"] == 0, s
        assert len(s["mem_samples"]) >= 5
        growth = s["mem_growth_bytes_per_min"]
        assert growth is not None
        assert growth < 64 * 1024 * 1024, \
            f"router soak leaked {growth / 1e6:.1f} MB/min of RSS"
        # the ledger's view must not diverge either: accounted bytes
        # growing while RSS is flat means an owner is accumulating state
        lg = s["ledger_growth_bytes_per_min"]
        assert lg is None or lg < 64 * 1024 * 1024
    finally:
        srv.stop()
