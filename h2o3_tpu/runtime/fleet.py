"""Fleet-scope observability — cross-process metric/trace aggregation.

Every observability surface built in PR 6/8 (`/3/Metrics`, `/3/Trace`,
`/3/Memory`, the phase buckets) is strictly single-process; scaling out
(N serving replicas today, `parallel/launcher.py` multi-host ranks
tomorrow) would otherwise mean N scrapes, N disconnected traces, and a
dashboard that has to know the fleet topology. This module makes ONE
process (the aggregator — any process, there is no special role) able to
answer for the whole fleet:

* **Peer registry** — `register_peer(name, url)` (or
  ``H2O3_FLEET_PEERS="r1=http://h:p,r2=..."``, or ``POST /3/Fleet``)
  names the replicas to aggregate. Peers are plain h2o3 REST servers;
  the aggregator itself always counts as the replica named by
  ``H2O3_REPLICA_NAME`` (default ``self``).

* **Metric aggregation** — `GET /3/Metrics?scope=fleet` scrapes every
  peer's lossless JSON export (``GET /3/Metrics?format=json``,
  `metrics_registry.export_state`) under the shared PR 5 `RetryPolicy`
  and merges by family semantics:

  - **counters sum** across replicas per label tuple (fleet totals);
  - **histograms bucket-merge** (per-bucket count sums over the shared
    fixed bounds) so p50/p95/p99 computed from the merged buckets are
    EXACT fleet percentiles, not averages of percentiles;
  - **gauges keep per-replica series** under an added ``replica`` label
    (a gauge is process state — summing RSS across replicas is
    meaningful only sometimes, attributing it always is);
  - an unreachable peer is an EXPLICIT ``h2o3_fleet_peer_up{replica} 0``
    series — the scrape never silently shrinks (absence-of-peer must
    alert, the same stance as the registry's 0-sample counters).

* **Trace aggregation** — ``X-H2O3-Trace-Id`` already propagates through
  the remote client, so one workflow's spans land in several processes;
  `GET /3/Trace?scope=fleet[&trace_id=]` pulls each peer's Chrome-trace
  export and merges them into ONE timeline with one ``process_name``
  track per replica (pid = replica index).

* **Fleet fold** — `snapshot()` backs ``GET /3/Fleet`` and the
  `/3/Profiler` ``fleet`` entry: per-replica liveness + serving request/
  error counts + predict p99, and the fleet-merged totals — the document
  `deploy/loadgen.py --fleet` reports from.

Merge conflicts (a family registered with a different kind or histogram
bounds on two replicas — a version-skewed fleet) keep the FIRST seen
shape and count the rest into ``dropped_series``; nothing is silently
averaged across mismatched semantics.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.parse
import urllib.request
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from . import env_float, metrics_registry as _reg
from .metrics_registry import _escape_label, _fmt_value

__all__ = ["register_peer", "remove_peer", "mark_down", "peers",
           "scrape_states", "merge_states", "render_prometheus",
           "fleet_metrics_text", "merge_traces", "fleet_trace", "snapshot",
           "register_with", "origin", "same_origin", "reset"]

_LOCK = threading.Lock()
_PEERS: "OrderedDict[str, Dict]" = OrderedDict()
_ENV_PARSED = False
_FLEET_REG: Dict = {}

# the family the aggregator rebuilds authoritatively per scrape — peers'
# own copies are skipped during the merge so the fleet scrape carries
# exactly one liveness series per replica
_PEER_UP = "h2o3_fleet_peer_up"


def _registry() -> Dict:
    """Memoized registry families + REST bindings for the /3/Fleet doc
    (the metrics-consistency test walks these)."""
    if not _FLEET_REG:
        _FLEET_REG["peer_up"] = _reg.gauge(
            _PEER_UP, "1 when the last scrape of this registered replica "
            "succeeded, 0 when it was unreachable", labelnames=("replica",))
        _FLEET_REG["scrapes"] = _reg.counter(
            "h2o3_fleet_scrapes", "peer scrape attempts, per replica",
            labelnames=("replica",))
        _FLEET_REG["scrape_errors"] = _reg.counter(
            "h2o3_fleet_scrape_errors",
            "failed peer scrapes (after retries), per replica",
            labelnames=("replica",))
        _FLEET_REG["peers"] = _reg.gauge(
            "h2o3_fleet_peers", "registered fleet peers",
            fn=lambda: float(len(_PEERS)))
        _reg.bind_rest_field("fleet", "totals.peers", "h2o3_fleet_peers")
        _reg.bind_rest_field("fleet", "totals.up", _PEER_UP)
        _reg.bind_rest_field("fleet", "totals.scrapes", "h2o3_fleet_scrapes")
        _reg.bind_rest_field("fleet", "totals.scrape_errors",
                             "h2o3_fleet_scrape_errors")
    return _FLEET_REG


def self_name() -> str:
    return os.environ.get("H2O3_REPLICA_NAME", "self")


def _parse_env_once() -> None:
    global _ENV_PARSED
    if _ENV_PARSED:
        return
    _ENV_PARSED = True
    spec = os.environ.get("H2O3_FLEET_PEERS", "")
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, url = part.partition("=")
        if name and url:
            register_peer(name.strip(), url.strip())


def origin(url: str) -> str:
    """Normalize a URL to its REST origin (``http://host:port``) —
    trailing slashes and path suffixes stripped."""
    u = urllib.parse.urlparse(url if "//" in url else "http://" + url)
    return f"{u.scheme or 'http'}://{u.netloc or u.path}"


def same_origin(a: str, b: str) -> bool:
    return origin(a) == origin(b)


def register_peer(name: str, url: str) -> Dict:
    """Register (or re-point) one replica. `url` is the peer's REST base
    (``http://host:port``); trailing slashes and path suffixes are
    stripped to the origin."""
    if not name or not url:
        raise ValueError("peer name and url are both required")
    base = origin(url)
    with _LOCK:
        _PEERS[name] = dict(name=name, url=base, registered=time.time(),
                            up=None, last_scrape_ms=None, last_error=None)
        return dict(_PEERS[name])


def remove_peer(name: str) -> bool:
    reg = _registry()
    with _LOCK:
        removed = _PEERS.pop(name, None) is not None
        if removed:
            # the liveness gauge is current state: a decommissioned peer
            # must LEAVE the scrape, not freeze at its last 0/1 (the
            # documented contract is "alert on peer_up == 0" — a stale
            # series would alert forever for a replica that no longer
            # exists). Under _LOCK, paired with _scrape_one's membership-
            # gated set, so an in-flight scrape cannot resurrect it.
            reg["peer_up"].remove_series(name)
    return removed


def mark_down(name: str, reason: str = "") -> None:
    """Flip a replica's liveness to DOWN immediately — failure detection
    (the training supervisor's hung-collective abort) must reach the fleet scrape NOW, not
    at the next failed scrape of the wedged rank. Sets the
    ``h2o3_fleet_peer_up`` series to 0 whether or not the name is a
    registered peer (a pod rank detected dead locally may only be
    registered at the aggregator) and records the reason on the peer row
    when one exists. Emits a Timeline event naming the replica."""
    reg = _registry()
    with _LOCK:
        reg["peer_up"].set(0.0, name)
        if name in _PEERS:
            _PEERS[name].update(up=False,
                                last_error=reason or "marked down")
    try:
        from .timeline import Timeline
        Timeline.record("fleet_peer_down", name, reason=reason)
    except Exception:
        pass
    try:
        from . import tracing
        tracing.event("fleet_peer_down", replica=name, reason=reason)
    except Exception:
        pass


def peers() -> List[Dict]:
    _parse_env_once()
    with _LOCK:
        return [dict(p) for p in _PEERS.values()]


def reset() -> None:
    """Drop registered peers (tests)."""
    global _ENV_PARSED
    with _LOCK:
        _PEERS.clear()
        _ENV_PARSED = True


def _retry_policy():
    from .retry import RetryPolicy

    return RetryPolicy(name="fleet", max_attempts=2,
                       deadline_s=env_float("H2O3_FLEET_DEADLINE_S", 8.0))


def _fetch_json(url: str) -> Dict:
    timeout = env_float("H2O3_FLEET_TIMEOUT_S", 3.0)

    def one():
        from . import faults

        faults.check("client.request", detail=url)
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json.loads(r.read().decode())

    return _retry_policy().call(one)


def _scrape_one(p: Dict) -> Tuple[str, Optional[Dict]]:
    reg = _registry()
    name = p["name"]
    reg["scrapes"].inc(1, name)
    t0 = time.perf_counter()
    state: Optional[Dict] = None
    err: Optional[str] = None
    try:
        state = _fetch_json(p["url"] + "/3/Metrics?format=json")
    except Exception as e:
        err = f"{type(e).__name__}: {e}"
        reg["scrape_errors"].inc(1, name)
    with _LOCK:
        # gauge update gated on CURRENT registration, under the registry
        # lock: an in-flight scrape of a peer that remove_peer just
        # deleted must not resurrect its peer_up series (the scrape
        # captured the peer list before the removal)
        if name in _PEERS:
            reg["peer_up"].set(1.0 if state is not None else 0.0, name)
            _PEERS[name].update(
                up=state is not None,
                last_scrape_ms=round((time.perf_counter() - t0) * 1e3, 2),
                last_error=err)
    return (name, state)


def _fan_out(fn, items: List) -> List:
    """Run `fn` over `items` concurrently, results in item order. Peers
    are independent HTTP targets: scraping them serially would make the
    fleet scrape's latency grow linearly with DOWN peers (each one costs
    its full retry deadline) — worst exactly when the scrape matters
    most, and long enough to trip a Prometheus scrape_timeout and lose
    the LIVE peers' data too."""
    if not items:
        return []
    if len(items) == 1:
        return [fn(items[0])]
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=min(8, len(items))) as ex:
        return list(ex.map(fn, items))


def scrape_states() -> List[Tuple[str, Optional[Dict]]]:
    """Pull every registered peer's lossless metric export (peers probed
    concurrently). Returns ``(name, state_or_None)`` per peer — None
    marks an unreachable peer (already counted + gauged; the merge turns
    it into peer_up 0)."""
    _parse_env_once()
    _registry()
    return _fan_out(_scrape_one, peers())


# -- merge semantics (pure functions — unit-tested without HTTP) -------------

def merge_states(states: List[Tuple[str, Optional[Dict]]]) -> Dict:
    """Fold per-replica `export_state` payloads into one fleet family set.

    Returns ``{families: {...}, peer_up: {replica: 0|1},
    dropped_series: int}`` — `families` mirrors the export_state schema
    (kind/help/labelnames/series[+bounds]), with counters summed,
    histogram buckets summed, and gauges carried per-replica under an
    appended ``replica`` label. ``peer_up`` covers every replica in
    `states` (None state → 0)."""
    families: "OrderedDict[str, Dict]" = OrderedDict()
    acc: Dict[str, Dict] = {}            # family -> {labels_tuple: slot}
    src_labels: Dict[str, List[str]] = {}  # family -> first-seen labelnames
    peer_up: "OrderedDict[str, int]" = OrderedDict()
    dropped = 0
    for replica, state in states:
        peer_up[replica] = 0 if state is None else 1
        if not state:
            continue
        for fname, fam in state.items():
            if fname == _PEER_UP:
                continue                 # rebuilt authoritatively below
            kind = fam.get("kind")
            ent = families.get(fname)
            if ent is None:
                src_labels[fname] = list(fam.get("labelnames") or [])
                ent = families[fname] = dict(
                    kind=kind, help=fam.get("help", ""),
                    labelnames=list(src_labels[fname]))
                if kind == "histogram":
                    ent["bounds"] = list(fam.get("bounds") or [])
                if kind == "gauge":
                    ent["labelnames"] = ent["labelnames"] + ["replica"]
                acc[fname] = {}
            elif (ent["kind"] != kind
                  or list(fam.get("labelnames") or []) != src_labels[fname]
                  or (kind == "histogram"
                      and list(fam.get("bounds") or []) != ent["bounds"])):
                # version-skewed replica: same name, different semantics
                # (kind, label arity, or histogram bounds) — keep the
                # first-seen shape, count the rest
                dropped += len(fam.get("series") or ())
                continue
            slots = acc[fname]
            for s in fam.get("series") or ():
                labels = list(s.get("labels") or [])
                if kind == "counter":
                    key = tuple(labels)
                    slot = slots.get(key)
                    if slot is None:
                        slot = slots[key] = dict(labels=labels, value=0.0)
                    slot["value"] += float(s.get("value") or 0.0)
                elif kind == "histogram":
                    key = tuple(labels)
                    slot = slots.get(key)
                    counts = list(s.get("counts") or [])
                    if slot is None:
                        slot = slots[key] = dict(
                            labels=labels, counts=[0] * len(counts),
                            n=0, sum=0.0, min=None, max=None)
                    if len(slot["counts"]) != len(counts):
                        dropped += 1
                        continue
                    slot["counts"] = [a + b for a, b in
                                      zip(slot["counts"], counts)]
                    slot["n"] += int(s.get("n") or 0)
                    slot["sum"] += float(s.get("sum") or 0.0)
                    for fld, fold in (("min", min), ("max", max)):
                        v = s.get(fld)
                        if v is not None:
                            slot[fld] = (v if slot[fld] is None
                                         else fold(slot[fld], v))
                else:                    # gauge: per-replica series
                    key = tuple(labels) + (replica,)
                    slots[key] = dict(labels=labels + [replica],
                                      value=float(s.get("value") or 0.0))
    for fname, slots in acc.items():
        families[fname]["series"] = list(slots.values())
    families[_PEER_UP] = dict(
        kind="gauge",
        help="1 when this replica answered the fleet scrape, 0 when "
             "unreachable",
        labelnames=["replica"],
        series=[dict(labels=[r], value=float(up))
                for r, up in peer_up.items()])
    return dict(families=families, peer_up=dict(peer_up),
                dropped_series=dropped)


def render_prometheus(merged: Dict) -> str:
    """Prometheus text exposition (0.0.4) of a `merge_states` result —
    the ``GET /3/Metrics?scope=fleet`` body."""
    lines: List[str] = []

    def label_str(names, values, extra: str = "") -> str:
        pairs = [f'{n}="{_escape_label(v)}"'
                 for n, v in zip(names, values)]
        if extra:
            pairs.append(extra)
        return "{" + ",".join(pairs) + "}" if pairs else ""

    for fname in sorted(merged["families"]):
        fam = merged["families"][fname]
        kind = fam["kind"]
        names = fam.get("labelnames") or []
        if kind == "counter":
            expo = fname if fname.endswith("_total") else fname + "_total"
            lines.append(f"# HELP {expo} {fam.get('help', '')}")
            lines.append(f"# TYPE {expo} counter")
            for s in sorted(fam.get("series") or (),
                            key=lambda s: s["labels"]):
                lines.append(f"{expo}{label_str(names, s['labels'])} "
                             f"{_fmt_value(s['value'])}")
        elif kind == "histogram":
            lines.append(f"# HELP {fname} {fam.get('help', '')}")
            lines.append(f"# TYPE {fname} histogram")
            bounds = fam.get("bounds") or []
            for s in sorted(fam.get("series") or (),
                            key=lambda s: s["labels"]):
                cum = 0
                for b, cnt in zip(bounds, s["counts"]):
                    cum += cnt
                    le = f'le="{_fmt_value(b)}"'
                    lines.append(f"{fname}_bucket"
                                 f"{label_str(names, s['labels'], le)} {cum}")
                inf_le = 'le="+Inf"'
                lines.append(f"{fname}_bucket"
                             f"{label_str(names, s['labels'], inf_le)}"
                             f" {s['n']}")
                lines.append(f"{fname}_sum{label_str(names, s['labels'])} "
                             f"{_fmt_value(s['sum'])}")
                lines.append(f"{fname}_count{label_str(names, s['labels'])} "
                             f"{s['n']}")
        else:
            lines.append(f"# HELP {fname} {fam.get('help', '')}")
            lines.append(f"# TYPE {fname} gauge")
            for s in sorted(fam.get("series") or (),
                            key=lambda s: s["labels"]):
                lines.append(f"{fname}{label_str(names, s['labels'])} "
                             f"{_fmt_value(s['value'])}")
    return "\n".join(lines) + "\n"


def fleet_metrics_text() -> str:
    """Scrape + merge + render: the whole fleet (this process included) in
    one Prometheus body."""
    states: List[Tuple[str, Optional[Dict]]] = [
        (self_name(), _reg.export_state())]
    states += scrape_states()
    return render_prometheus(merge_states(states))


# -- trace aggregation -------------------------------------------------------

def merge_traces(traces: List[Tuple[str, Optional[Dict]]]) -> Dict:
    """Merge per-replica Chrome-trace exports into one timeline: replica i
    becomes pid i+1 with a ``process_name`` metadata track named
    ``replica:<name>``; span/thread events keep their tids within the
    replica's pid. Unreachable replicas are listed in
    ``otherData.unreachable`` instead of vanishing."""
    events: List[Dict] = []
    unreachable: List[str] = []
    for i, (name, tr) in enumerate(traces):
        pid = i + 1
        if tr is None:
            unreachable.append(name)
            continue
        events.append(dict(name="process_name", ph="M", pid=pid, tid=0,
                           args=dict(name=f"replica:{name}")))
        for ev in tr.get("traceEvents") or ():
            ev = dict(ev)
            ev["pid"] = pid
            events.append(ev)
    return dict(traceEvents=events, displayTimeUnit="ms",
                otherData=dict(source="h2o3_tpu_fleet",
                               replicas=[n for n, _ in traces],
                               unreachable=unreachable))


def fleet_trace(trace_id: Optional[str] = None) -> Dict:
    """The ``GET /3/Trace?scope=fleet`` body: this process's spans plus
    every reachable peer's, one track per replica. With `trace_id`, one
    correlated cross-process request tree (the X-H2O3-Trace-Id the client
    minted travels to every replica it touched)."""
    from . import tracing

    _parse_env_once()
    traces: List[Tuple[str, Optional[Dict]]] = [
        (self_name(), tracing.export_chrome(trace_id))]
    q = f"?trace_id={urllib.parse.quote(trace_id)}" if trace_id else ""

    def one(p):
        try:
            return (p["name"], _fetch_json(p["url"] + "/3/Trace" + q))
        except Exception:
            return (p["name"], None)

    traces += _fan_out(one, peers())
    return merge_traces(traces)


# -- the /3/Fleet document ---------------------------------------------------

# metrics_registry.bucket_percentile applied to MERGED buckets is what
# makes fleet p99 exact over the fleet's observations — and sharing the
# registry's estimator means aggregator and per-replica percentiles can
# never drift apart on identical data
_bucket_percentile = _reg.bucket_percentile


def _counter_total(state: Dict, fname: str) -> float:
    fam = state.get(fname) or {}
    return float(sum(s.get("value") or 0.0 for s in fam.get("series") or ()))


def _serving_summary(state: Dict) -> Dict:
    """Per-replica serving essentials out of one export_state payload —
    the fields loadgen's fleet report needs."""
    out = dict(
        requests=_counter_total(state, "h2o3_serving_requests"),
        errors=_counter_total(state, "h2o3_serving_errors"),
        rejections=_counter_total(state, "h2o3_serving_rejections"),
        rest_requests=_counter_total(state, "h2o3_rest_requests"),
    )
    fam = state.get("h2o3_rest_request_ms") or {}
    for s in fam.get("series") or ():
        if list(s.get("labels") or []) == ["predict"]:
            out["predict_p99_ms"] = _bucket_percentile(
                fam.get("bounds") or [], s.get("counts") or [],
                int(s.get("n") or 0), 0.99, s.get("min"), s.get("max"))
            out["predict_count"] = int(s.get("n") or 0)
            break
    return out


def snapshot(scrape: bool = True) -> Dict:
    """The ``GET /3/Fleet`` / profiler-fold document: per-replica rows
    (liveness, scrape latency, serving counters, predict p99) + fleet
    totals with the bucket-merged fleet predict p99. ``scrape=False``
    reports registration state only — no network, and no registry
    export/merge either (the /3/Profiler fold polls this; it must stay
    O(peers), not O(metric series))."""
    _parse_env_once()
    reg = _registry()

    def _totals(rows: List[Dict]) -> Dict:
        up = sum(1 for r in rows if not r["is_self"] and r["up"])
        return dict(peers=len(rows) - 1, up=up,
                    scrapes=reg["scrapes"].total(),
                    scrape_errors=reg["scrape_errors"].total())

    if not scrape:
        rows = [dict(name=self_name(), url=None, up=1, is_self=True)]
        for p in peers():
            rows.append(dict(name=p["name"], url=p["url"],
                             up=1 if p.get("up") else 0, is_self=False,
                             last_scrape_ms=p.get("last_scrape_ms"),
                             last_error=p.get("last_error")))
        return dict(replica=self_name(), peers=rows, fleet={},
                    dropped_series=0, totals=_totals(rows))

    self_state = _reg.export_state()
    rows = [dict(name=self_name(), url=None, up=1,
                 is_self=True, **_serving_summary(self_state))]
    states: List[Tuple[str, Optional[Dict]]] = [(self_name(), self_state)]
    for name, state in scrape_states():
        with _LOCK:
            meta = dict(_PEERS.get(name) or {})
        row = dict(name=name, url=meta.get("url"),
                   up=1 if state is not None else 0, is_self=False,
                   last_scrape_ms=meta.get("last_scrape_ms"),
                   last_error=meta.get("last_error"))
        if state is not None:
            row.update(_serving_summary(state))
        rows.append(row)
        states.append((name, state))
    # fleet-merged predict latency: exact percentile over summed buckets
    merged = merge_states(states)
    fleet = dict(
        requests=sum(r.get("requests") or 0 for r in rows),
        errors=sum(r.get("errors") or 0 for r in rows),
        rejections=sum(r.get("rejections") or 0 for r in rows),
    )
    fam = merged["families"].get("h2o3_rest_request_ms") or {}
    for s in fam.get("series") or ():
        if list(s.get("labels") or []) == ["predict"]:
            fleet["predict_p99_ms"] = _bucket_percentile(
                fam.get("bounds") or [], s.get("counts") or [],
                int(s.get("n") or 0), 0.99, s.get("min"), s.get("max"))
            fleet["predict_count"] = int(s.get("n") or 0)
            break
    return dict(
        replica=self_name(),
        peers=rows,
        fleet=fleet,
        dropped_series=merged["dropped_series"],
        totals=_totals(rows),
    )


def register_with(aggregator_url: str, name: str, self_url: str) -> bool:
    """Self-registration against a remote aggregator (the launcher hook:
    a rank/replica announces its REST endpoint via ``POST /3/Fleet``).
    Returns False instead of raising when the aggregator is unreachable —
    bring-up order must not matter."""
    try:
        body = urllib.parse.urlencode(dict(name=name, url=self_url)).encode()
        req = urllib.request.Request(
            aggregator_url.rstrip("/") + "/3/Fleet", data=body)
        with urllib.request.urlopen(
                req, timeout=env_float("H2O3_FLEET_TIMEOUT_S", 3.0)) as r:
            r.read()
        return True
    except Exception:
        return False
