#!/usr/bin/env python
"""Load generator for the `/3/Predictions` serving path — closed- and
open-loop.

**Closed-loop** (`run_load`): N worker threads each issue M back-to-back
requests; each thread waits for its response before sending the next, so
offered load tracks service rate — the right shape for measuring the
micro-batcher's coalescing win.

**Open-loop** (`run_load_open`): requests arrive on a fixed schedule
(`rate` per second) regardless of how fast earlier ones complete — the
right shape for a serving-SLO lane, because a slow server faces the SAME
offered load a fast one does instead of being graded on a curve. Latency
bins into `LATENCY_MS_BOUNDS` below — the same fixed buckets as
`h2o3_tpu.runtime.metrics_registry.LATENCY_MS_BOUNDS` (equality is
pinned by a test) — so the reported p50/p95/p99 are bucket-comparable
with the serving histograms scraped at `GET /3/Metrics`.

The standalone CLI is STDLIB-ONLY: it must run from a loadgen host with
no jax/h2o3 installed, and must not import (and configure) jax as a side
effect in the loadgen process. When the platform is already loaded
in-process (the in-process test servers), every request is
additionally folded into the central registry
(`h2o3_loadgen_request_ms{mode=...}`) so a loadgen run is itself
scrapable.

Usage:
    python deploy/loadgen.py --port 54321 --model gbm_1 --frame fr_1 \\
        --threads 8 --requests 50               # closed-loop
    python deploy/loadgen.py --port 54321 --model gbm_1 --frame fr_1 \\
        --rate 50 --duration-s 10               # open-loop, 50 req/s
    python deploy/loadgen.py --port 54321 --model gbm_1 --frame fr_1 \\
        --rate 50 --duration-s 10 --router      # fleet router entry point

Importable: `run_load(...)` / `run_load_open(...)` return the stats dict
(the smoke tests in tests/test_serving.py and tests/test_observability.py
drive an in-process server through them).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from typing import Dict, List, Optional

if __package__ in (None, ""):  # `python deploy/loadgen.py` from anywhere
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


# literal copy of metrics_registry.LATENCY_MS_BOUNDS (this module cannot
# import the platform — see docstring); test_observability pins equality
LATENCY_MS_BOUNDS = (0.5, 1, 2, 5, 10, 25, 50, 100, 250, 500, 1000, 2500,
                     5000, 10000, 30000)


class _BucketHist:
    """Stdlib fixed-bound histogram over the shared latency buckets, with
    the same bucket-interpolated percentile estimate as the registry's
    Histogram — O(bounds) state, directly comparable with /3/Metrics."""

    def __init__(self, bounds=LATENCY_MS_BOUNDS):
        self.bounds = tuple(float(b) for b in bounds)
        self.counts = [0] * (len(self.bounds) + 1)
        self.n = 0
        self.total = 0.0
        self.vmin: Optional[float] = None
        self.vmax: Optional[float] = None

    def observe(self, v: float) -> None:
        i = len(self.bounds)
        for j, b in enumerate(self.bounds):
            if v <= b:
                i = j
                break
        self.counts[i] += 1
        self.n += 1
        self.total += v
        self.vmin = v if self.vmin is None else min(self.vmin, v)
        self.vmax = v if self.vmax is None else max(self.vmax, v)

    def percentile(self, q: float) -> Optional[float]:
        if self.n == 0:
            return None
        rank = q * (self.n - 1)
        cum = 0
        for i, cnt in enumerate(self.counts):
            if cnt == 0:
                continue
            if rank < cum + cnt:
                lo = self.bounds[i - 1] if i > 0 else (
                    self.vmin if self.vmin is not None else 0.0)
                hi = self.bounds[i] if i < len(self.bounds) else (
                    self.vmax if self.vmax is not None else lo)
                lo = max(lo, self.vmin) if self.vmin is not None else lo
                hi = min(hi, self.vmax) if self.vmax is not None else hi
                if hi <= lo:
                    return float(lo)
                frac = (rank - cum + 1) / cnt if cnt > 1 else 0.5
                frac = min(max(frac, 0.0), 1.0)
                return float(lo + (hi - lo) * frac)
            cum += cnt
        return self.vmax

    def summary(self) -> Dict:
        return dict(
            bounds=list(self.bounds), counts=list(self.counts), count=self.n,
            mean=round(self.total / self.n, 4) if self.n else None,
            min=self.vmin, max=self.vmax,
            p50=self.percentile(0.50), p95=self.percentile(0.95),
            p99=self.percentile(0.99),
        )


def _registry_hist():
    """The scrapable registry fold of every loadgen request — ONLY when
    the platform is already loaded in this process. The standalone CLI
    never imports h2o3_tpu (which would drag jax in and mutate its config
    as an import side effect); returns None there and callers skip the
    fold."""
    if "h2o3_tpu" not in sys.modules:
        return None
    from h2o3_tpu.runtime import metrics_registry as reg

    return reg.histogram(
        "h2o3_loadgen_request_ms",
        "loadgen request latency (ms), shared latency buckets",
        bounds=reg.LATENCY_MS_BOUNDS, labelnames=("mode",))


def _read_rss_bytes() -> Optional[int]:
    """Current resident set size — stdlib-only (/proc on Linux, ru_maxrss
    peak as the portable fallback). None when neither is readable."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        try:
            import resource

            return resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            return None


def _ledger_total_bytes() -> Optional[int]:
    """Ledger-attributed host+device bytes — ONLY when the platform is
    already loaded in this process (same stance as _registry_hist: the
    standalone CLI must stay stdlib-only). Uses the ledger's rate-limited
    cached pass, NOT a forced walk: the sample runs in the open-loop
    dispatch thread, and a deep accounting pass there would perturb the
    arrival schedule whose p99 this run exists to measure."""
    if "h2o3_tpu" not in sys.modules:
        return None
    try:
        from h2o3_tpu.runtime import memory_ledger as ml

        t = ml.refresh()["totals"]
        return int(t["host_bytes"]) + int(t["device_bytes"])
    except Exception:
        return None


def _stream_totals() -> Optional[Dict]:
    """Out-of-core stream totals (`streamed_bytes`, `resident_block_peak`)
    — ONLY when the platform already streamed in this process (same
    stdlib-only stance as _ledger_total_bytes); None otherwise so the
    standalone CLI report is unchanged."""
    bs = sys.modules.get("h2o3_tpu.models.block_store")
    if bs is None:
        return None
    try:
        t = bs.process_totals()
        return dict(t) if t.get("streamed_bytes") else None
    except Exception:
        return None


def _growth_bytes_per_min(samples: List[Dict],
                          field: str) -> Optional[float]:
    """Least-squares slope of `field` over the sampled run, in bytes per
    minute — the leak-canary verdict. None below two usable samples."""
    pts = [(s["t_s"], s[field]) for s in samples
           if s.get(field) is not None]
    if len(pts) < 2 or pts[-1][0] - pts[0][0] <= 0:
        return None
    n = len(pts)
    mt = sum(t for t, _ in pts) / n
    mv = sum(v for _, v in pts) / n
    denom = sum((t - mt) ** 2 for t, _ in pts)
    if denom <= 0:
        return None
    slope = sum((t - mt) * (v - mv) for t, v in pts) / denom   # bytes/s
    return round(slope * 60.0, 1)


def fleet_summary(host: str, port: int,
                  timeout_s: float = 10.0) -> Optional[Dict]:
    """Fleet fold of an aggregator target (`GET /3/Fleet`, stdlib-only):
    fleet-merged request/error totals + predict p99 and per-replica
    liveness/error counts. None when the target has no fleet surface (an
    older or single-process server) — the report simply omits the fleet
    section rather than failing the run."""
    url = f"http://{host}:{port}/3/Fleet"
    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as r:
            doc = json.loads(r.read().decode())
    except Exception:
        return None
    if "peers" not in doc:
        return None
    return dict(
        requests=doc.get("fleet", {}).get("requests"),
        errors=doc.get("fleet", {}).get("errors"),
        rejections=doc.get("fleet", {}).get("rejections"),
        predict_p99_ms=doc.get("fleet", {}).get("predict_p99_ms"),
        replicas_up=doc.get("totals", {}).get("up"),
        replicas=doc.get("totals", {}).get("peers"),
        per_replica=[dict(name=p.get("name"), up=p.get("up"),
                          requests=p.get("requests"),
                          errors=p.get("errors"),
                          rejections=p.get("rejections"),
                          predict_p99_ms=p.get("predict_p99_ms"))
                     for p in doc.get("peers", [])],
    )


def _fleet_delta_report(before: Optional[Dict], after: Optional[Dict],
                        wall_s: float) -> Optional[Dict]:
    """The loadgen summary's fleet section: the AFTER snapshot (liveness,
    per-replica error counts, fleet predict p99 over merged buckets) plus
    a fleet-scope throughput computed from the before/after request-count
    delta over this run's wall — counters are cumulative, so the delta is
    what THIS run drove through the fleet."""
    if after is None:
        return None
    out = dict(after)
    if (before is not None and before.get("requests") is not None
            and after.get("requests") is not None and wall_s > 0):
        # fleet totals only sum currently-REACHABLE replicas, so a peer
        # dying mid-run can shrink the after-snapshot below the before
        # one — floor deltas at 0 (a rate cannot be negative); the
        # replicas_up / per_replica fields carry the peer-loss signal
        out["throughput_rps"] = round(
            max(after["requests"] - before["requests"], 0) / wall_s, 2)
        for fld in ("errors", "rejections"):
            if before.get(fld) is not None and after.get(fld) is not None:
                out[f"{fld}_delta"] = max(after[fld] - before[fld], 0)
    return out


def _peer_skew(base_url: str, timeout_s: float = 10.0) -> Optional[Dict]:
    """One rank's collective-skew digest from its lossless registry
    export (`h2o3_collective_skew_ms`): every tag's series merged over
    the shared bounds, p50 via the same bucket interpolation as the
    registry, max from the exact per-series max. None when the rank
    recorded no instrumented fences (or is unreachable)."""
    url = base_url.rstrip("/") + "/3/Metrics?format=json"
    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as r:
            doc = json.loads(r.read().decode())
    except Exception:
        return None
    fam = doc.get("h2o3_collective_skew_ms")
    if not fam:
        return None
    h = _BucketHist(fam.get("bounds") or LATENCY_MS_BOUNDS)
    for s in fam.get("series") or ():
        for i, c in enumerate(list(s.get("counts") or ())[: len(h.counts)]):
            h.counts[i] += int(c)
        h.n += int(s.get("n") or 0)
        for fld, pick in (("min", min), ("max", max)):
            v = s.get(fld)
            if v is not None:
                cur = getattr(h, f"v{fld}")
                setattr(h, f"v{fld}", v if cur is None else pick(cur, v))
    if not h.n:
        return None
    return dict(fences=h.n, skew_p50_ms=h.percentile(0.50),
                skew_max_ms=h.vmax)


def ranks_summary(host: str, port: int,
                  timeout_s: float = 10.0) -> Optional[List[Dict]]:
    """Pod-rank fold of the --fleet report (ISSUE 18): one row per
    launcher-registered ``rank<N>`` peer — liveness (peer_up) plus that
    rank's own collective-skew p50/max scraped from its registry export.
    The aggregator itself is rank 0 (the launcher registers every OTHER
    rank against it). None when no rank peers exist — single-process
    fleets keep their old report shape."""
    try:
        with urllib.request.urlopen(f"http://{host}:{port}/3/Fleet",
                                    timeout=timeout_s) as r:
            doc = json.loads(r.read().decode())
    except Exception:
        return None
    rows = doc.get("peers") or []
    if not any(str(p.get("name", "")).startswith("rank")
               and not p.get("is_self") for p in rows):
        return None
    out = []
    for p in rows:
        name = str(p.get("name", ""))
        if not (name.startswith("rank") or p.get("is_self")):
            continue   # serving replicas: already in the fleet section
        row = dict(name=("rank0" if p.get("is_self") and
                         not name.startswith("rank") else name),
                   peer_up=1 if p.get("up") else 0)
        base = p.get("url") or f"http://{host}:{port}"
        if row["peer_up"]:
            skew = _peer_skew(base, timeout_s)
            if skew:
                row.update(skew)
        out.append(row)
    return out or None


def router_summary(host: str, port: int,
                   timeout_s: float = 10.0) -> Optional[Dict]:
    """Router fold of a fleet-router target (`GET /3/Router?probe=0`,
    stdlib-only): shed/failover/rollback counters, ring liveness, and the
    per-model live/canary/shadow version pointers. None when the target
    has no router surface — the report omits the section, same stance as
    fleet_summary."""
    url = f"http://{host}:{port}/3/Router?probe=0"
    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as r:
            doc = json.loads(r.read().decode())
    except Exception:
        return None
    if "ring" not in doc:
        return None
    totals = doc.get("totals") or {}
    return dict(
        totals=totals,
        replicas=len(doc.get("ring") or []),
        replicas_up=sum(1 for p in doc.get("ring") or [] if p.get("up")),
        drained=sum(1 for p in doc.get("ring") or [] if p.get("drained")),
        versions={m: dict(live=e.get("live"), canary=e.get("canary"),
                          canary_pct=e.get("canary_pct"),
                          shadow=e.get("shadow"))
                  for m, e in (doc.get("models") or {}).items()},
        canary_health=doc.get("canary_health") or {},
    )


def _router_lane_p99(host: str, port: int,
                     timeout_s: float = 10.0) -> Optional[Dict]:
    """Per-lane (live/canary/unversioned) p99 from the router's
    `h2o3_router_request_ms` histogram, via the JSON registry export —
    the per-version latency split of the run (stdlib bucket
    interpolation over the same shared bounds)."""
    url = f"http://{host}:{port}/3/Metrics?format=json"
    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as r:
            doc = json.loads(r.read().decode())
    except Exception:
        return None
    fam = doc.get("h2o3_router_request_ms")   # export_state is the
    if not fam:                               # family map itself
        return None
    out = {}
    for s in fam.get("series") or ():
        labels = s.get("labels") or []
        lane = labels[0] if labels else "all"
        h = _BucketHist(fam.get("bounds") or LATENCY_MS_BOUNDS)
        h.counts = list(s.get("counts") or h.counts)
        h.n = int(s.get("n") or 0)
        h.vmin, h.vmax = s.get("min"), s.get("max")
        out[lane] = dict(n=h.n, p99_ms=h.percentile(0.99))
    return out or None


def _router_delta_report(before: Optional[Dict], after: Optional[Dict],
                         wall_s: float, offered: int = 0,
                         lane_p99: Optional[Dict] = None) -> Optional[Dict]:
    """The loadgen summary's router section: the AFTER snapshot plus
    counter deltas over this run — shed rate vs offered load, failover/
    retry/drain counts, and rollback EVENTS (a rollback delta > 0 means a
    canary was auto-aborted mid-run). `lane_p99` carries the per-version
    latency split when the registry export is reachable."""
    if after is None:
        return None
    out = dict(after)
    bt = (before or {}).get("totals") or {}
    at = after.get("totals") or {}
    deltas = {}
    for fld in ("shed", "errors", "retries", "failovers", "drains",
                "rollbacks"):
        if at.get(fld) is not None:
            deltas[fld] = max(at[fld] - (bt.get(fld) or 0), 0)
    out["deltas"] = deltas
    if offered > 0 and "shed" in deltas:
        out["shed_rate"] = round(deltas["shed"] / offered, 4)
    out["rollback_events"] = deltas.get("rollbacks", 0)
    if lane_p99:
        out["lane_p99_ms"] = lane_p99
    return out


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return float("nan")
    i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


def _predict_url(host: str, port: int, model: str, frame: str,
                 router: bool = False) -> str:
    # router mode drives the fleet entry point (version split + failover)
    # instead of one replica's /3/Predictions
    base = "/3/Router/models/" if router else "/3/Predictions/models/"
    return (f"http://{host}:{port}{base}"
            f"{urllib.parse.quote(model)}/frames/"
            f"{urllib.parse.quote(frame)}")


def run_load(host: str, port: int, model: str, frame: str,
             threads: int = 8, requests: int = 50,
             duration_s: Optional[float] = None,
             timeout_s: float = 60.0, router: bool = False) -> Dict:
    """Drive the predict route closed-loop; returns the stats dict.

    `duration_s` caps wall-clock instead of request count when set (each
    thread stops issuing new requests once the deadline passes).
    `router=True` drives the fleet router entry point instead of a
    replica's predict route."""
    url = _predict_url(host, port, model, frame, router=router)
    lock = threading.Lock()
    lat_s: List[float] = []
    shed = [0]
    errors = [0]
    t_end = (time.monotonic() + duration_s) if duration_s else None
    reg_hist = _registry_hist()

    def worker():
        for _ in range(requests):
            if t_end is not None and time.monotonic() >= t_end:
                break
            t0 = time.monotonic()
            try:
                req = urllib.request.Request(url, data=b"")
                with urllib.request.urlopen(req, timeout=timeout_s) as r:
                    r.read()
                lat = time.monotonic() - t0
                if reg_hist is not None:
                    reg_hist.observe(lat * 1e3, "closed")
                with lock:
                    lat_s.append(lat)
            except urllib.error.HTTPError as e:
                e.read()
                with lock:
                    (shed if e.code == 429 else errors)[0] += 1
            except OSError:
                with lock:
                    errors[0] += 1

    t0 = time.monotonic()
    ts = [threading.Thread(target=worker, daemon=True)
          for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = max(time.monotonic() - t0, 1e-9)
    srt = sorted(lat_s)
    return dict(
        url=url, threads=threads, requests_per_thread=requests,
        completed=len(srt), shed_429=shed[0], errors=errors[0],
        wall_s=round(wall, 3),
        throughput_rps=round(len(srt) / wall, 2),
        p50_ms=round(_percentile(srt, 0.50) * 1e3, 3) if srt else None,
        p99_ms=round(_percentile(srt, 0.99) * 1e3, 3) if srt else None,
    )


def run_load_open(host: str, port: int, model: str, frame: str,
                  rate: float = 20.0, duration_s: float = 10.0,
                  timeout_s: float = 60.0, max_inflight: int = 256,
                  router: bool = False) -> Dict:
    """Drive the predict route open-loop at a fixed arrival rate.

    One dispatcher thread fires a request thread at each scheduled arrival
    (`t0 + i/rate`), never waiting for earlier responses — queueing delay
    shows up as latency, not as reduced load. `max_inflight` is the
    safety valve: arrivals beyond it are counted `dropped` (a dropped
    arrival means the server is more than `max_inflight` requests behind
    the schedule, itself an SLO verdict) instead of growing threads
    without bound.

    Percentiles come from the shared fixed latency buckets
    (LATENCY_MS_BOUNDS — the same bounds the serving histograms use), so
    they are directly comparable with `GET /3/Metrics` and with every
    other loadgen report; `hist_*` fields carry the raw bucket
    vector.

    Leak canary (sustained mode): RSS + memory-ledger totals are sampled
    once per decile of the arrival schedule, and the report carries the
    least-squares growth slopes (`mem_growth_bytes_per_min`,
    `ledger_growth_bytes_per_min`) — a sustained run whose memory climbs
    is a leak verdict even when every request succeeded. RSS sampling is
    stdlib-only; the ledger column stays None in the standalone CLI."""
    if rate <= 0:
        raise ValueError(f"open-loop rate must be > 0 req/s (got {rate})")
    url = _predict_url(host, port, model, frame, router=router)
    n_arrivals = max(int(rate * duration_s), 1)
    lock = threading.Lock()
    # per-run local histogram over the SAME shared bounds: the report must
    # cover this run only, while the registered family below (in-process
    # runs only) accumulates process-wide for the scrape surface
    hist = _BucketHist()
    reg_hist = _registry_hist()
    counts = dict(completed=0, shed_429=0, errors=0, dropped=0)
    inflight = [0]
    live: List[threading.Thread] = []

    def one_request():
        t_req = time.monotonic()
        try:
            req = urllib.request.Request(url, data=b"")
            with urllib.request.urlopen(req, timeout=timeout_s) as r:
                r.read()
            lat_ms = (time.monotonic() - t_req) * 1e3
            with lock:
                hist.observe(lat_ms)
            if reg_hist is not None:
                reg_hist.observe(lat_ms, "open")
            with lock:
                counts["completed"] += 1
        except urllib.error.HTTPError as e:
            e.read()
            with lock:
                counts["shed_429" if e.code == 429 else "errors"] += 1
        except OSError:
            with lock:
                counts["errors"] += 1
        finally:
            with lock:
                inflight[0] -= 1

    mem_samples: List[Dict] = []
    sample_every = max(n_arrivals // 10, 1)

    def _sample_mem(t0: float) -> None:
        mem_samples.append(dict(t_s=round(time.monotonic() - t0, 3),
                                rss_bytes=_read_rss_bytes(),
                                ledger_bytes=_ledger_total_bytes()))

    t0 = time.monotonic()
    for i in range(n_arrivals):
        if i % sample_every == 0:
            _sample_mem(t0)
        target = t0 + i / rate
        delay = target - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        with lock:
            if inflight[0] >= max_inflight:
                counts["dropped"] += 1
                continue
            inflight[0] += 1
        t = threading.Thread(target=one_request, daemon=True)
        t.start()
        live.append(t)
    # wall is the offered-load window (the arrival schedule), measured
    # BEFORE draining stragglers: one request hanging to its timeout must
    # show up as drain/latency, not deflate achieved_rps into a phantom
    # throughput collapse
    wall = max(time.monotonic() - t0, 1e-9)
    deadline = time.monotonic() + timeout_s + 5.0
    for t in live:
        t.join(timeout=max(deadline - time.monotonic(), 0.0))
    drain = max(time.monotonic() - t0 - wall, 0.0)
    _sample_mem(t0)   # final sample after the drain closes the series
    summary = hist.summary()
    offered = n_arrivals
    return dict(
        url=url, mode="open", rate_rps=rate,
        duration_s=round(duration_s, 3), offered=offered,
        completed=counts["completed"], shed_429=counts["shed_429"],
        errors=counts["errors"], dropped=counts["dropped"],
        wall_s=round(wall, 3), drain_s=round(drain, 3),
        achieved_rps=round(counts["completed"] / wall, 2),
        p50_ms=(round(summary["p50"], 3)
                if summary["p50"] is not None else None),
        p95_ms=(round(summary["p95"], 3)
                if summary["p95"] is not None else None),
        p99_ms=(round(summary["p99"], 3)
                if summary["p99"] is not None else None),
        mean_ms=summary["mean"], max_ms=summary["max"],
        hist_bounds_ms=summary["bounds"], hist_counts=summary["counts"],
        mem_samples=mem_samples,
        mem_growth_bytes_per_min=_growth_bytes_per_min(mem_samples,
                                                       "rss_bytes"),
        ledger_growth_bytes_per_min=_growth_bytes_per_min(mem_samples,
                                                          "ledger_bytes"),
        stream=_stream_totals(),
    )


def _merge_open_windows(windows: List[Dict]) -> Dict:
    """Fold several `run_load_open` reports into one: bucket counts add
    (same fixed bounds), percentiles re-estimated from the merged
    histogram, counters summed. The contended phase of a concurrent sweep
    is measured as repeated windows (the sweep's wall is not known up
    front), and the SLO verdict wants ONE p99 over all of them."""
    h = _BucketHist()
    out = dict(mode="open", windows=len(windows), completed=0, offered=0,
               shed_429=0, errors=0, dropped=0, wall_s=0.0)
    for w in windows:
        for i, c in enumerate(w.get("hist_counts") or []):
            h.counts[i] += int(c)
            h.n += int(c)
        if w.get("mean_ms") is not None and w.get("completed"):
            h.total += w["mean_ms"] * w["completed"]
        if w.get("max_ms") is not None:
            h.vmax = (w["max_ms"] if h.vmax is None
                      else max(h.vmax, w["max_ms"]))
        for k in ("completed", "offered", "shed_429", "errors", "dropped"):
            out[k] += int(w.get(k) or 0)
        out["wall_s"] = round(out["wall_s"] + (w.get("wall_s") or 0.0), 3)
    s = h.summary()
    out.update(p50_ms=(round(s["p50"], 3) if s["p50"] is not None else None),
               p95_ms=(round(s["p95"], 3) if s["p95"] is not None else None),
               p99_ms=(round(s["p99"], 3) if s["p99"] is not None else None),
               hist_bounds_ms=s["bounds"], hist_counts=s["counts"])
    if out["wall_s"]:
        out["achieved_rps"] = round(out["completed"] / out["wall_s"], 2)
    return out


def _run_sweep_inprocess(candidates: int, rows: int, ntrees: int,
                         out: Dict) -> None:
    """The training half of `--concurrent-sweep`: a GBM grid (depths 3..)
    through the TrainPool in THIS process — the same accelerator the
    serving path scores on. `score_tree_interval=1` gives per-tree chunk
    boundaries, i.e. the densest QoS yield cadence the tree driver offers.
    NOT stdlib-only (the plain CLI modes stay so): requires the platform
    importable where the server runs."""
    import numpy as np

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.runtime.trainpool import TrainPool

    rng = np.random.default_rng(11)
    X = rng.normal(size=(rows, 8))
    yv = (X @ rng.normal(size=8) + 0.5 * rng.normal(size=rows) > 0)
    fr = Frame.from_numpy(
        np.column_stack([X, yv.astype(float)]),
        names=[f"f{i}" for i in range(8)] + ["label"]).asfactor("label")

    def make(depth: int):
        def fit(job=None):
            est = H2OGradientBoostingEstimator(
                ntrees=ntrees, max_depth=depth, seed=42,
                score_tree_interval=1)
            est.train(y="label", training_frame=fr)
            return est

        return fit

    t0 = time.monotonic()
    pool = TrainPool(parallelism=1, label="qos_sweep")
    recs = pool.run([(f"gbm_depth{3 + i}", make(3 + i))
                     for i in range(candidates)])
    out["wall_s"] = round(time.monotonic() - t0, 3)
    out["candidates"] = candidates
    out["done"] = sum(1 for r in recs if r.status == "done")
    out["statuses"] = {r.name: r.status for r in recs}


def run_concurrent_sweep(host: str, port: int, model: str, frame: str,
                         rate: float, window_s: float = 8.0,
                         candidates: int = 4, sweep_rows: int = 20000,
                         sweep_ntrees: int = 10, timeout_s: float = 60.0,
                         max_inflight: int = 256, router: bool = False,
                         idle: bool = True) -> Dict:
    """`--concurrent-sweep`: the multi-tenant QoS measurement shape.

    Phase 1 (idle, optional): one open-loop window against a quiet server —
    the near-idle SLO baseline. Phase 2 (contended): the SAME open-loop
    load re-run in repeated `window_s` windows while an in-process
    `candidates`-way GBM grid sweep trains on the same accelerator; windows
    repeat until the sweep completes and fold into one histogram. The
    report carries split idle-vs-contended p50/p95/p99 plus the sweep's
    wall time (tests/test_qos.py's concurrent soak reads them).

    Requires the platform importable in this process (the sweep trains
    here); the plain closed/open CLI modes stay stdlib-only."""
    out: Dict = dict(mode="concurrent_sweep", rate_rps=rate,
                     window_s=window_s)
    if idle:
        out["idle"] = run_load_open(host, port, model, frame, rate=rate,
                                    duration_s=window_s, timeout_s=timeout_s,
                                    max_inflight=max_inflight, router=router)
    sweep: Dict = {}
    err: List[BaseException] = []

    def _sweep():
        try:
            _run_sweep_inprocess(candidates, sweep_rows, sweep_ntrees, sweep)
        except BaseException as e:   # surfaced in the report, not swallowed
            err.append(e)

    th = threading.Thread(target=_sweep, daemon=True,
                          name="loadgen-concurrent-sweep")
    t0 = time.monotonic()
    th.start()
    windows: List[Dict] = []
    # at least one contended window, then keep offering load until the
    # sweep lands (hard cap so a hung sweep cannot spin the loadgen
    # forever — the partial report still carries every finished window)
    while True:
        windows.append(run_load_open(host, port, model, frame, rate=rate,
                                     duration_s=window_s,
                                     timeout_s=timeout_s,
                                     max_inflight=max_inflight,
                                     router=router))
        if not th.is_alive():
            break
        if time.monotonic() - t0 > 1200:
            out["sweep_timeout"] = True
            break
    th.join(timeout=60.0)
    if err:
        sweep["error"] = f"{type(err[0]).__name__}: {err[0]}"
    out["contended"] = _merge_open_windows(windows)
    out["contended_windows"] = windows
    out["sweep"] = sweep
    out["completed"] = out["contended"]["completed"]
    idle_p99 = (out.get("idle") or {}).get("p99_ms")
    cont_p99 = out["contended"].get("p99_ms")
    if idle_p99 and cont_p99:
        out["p99_contended_over_idle"] = round(cont_p99 / idle_p99, 3)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--model", required=True, help="DKV model key")
    ap.add_argument("--frame", required=True, help="DKV frame key")
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--requests", type=int, default=50,
                    help="requests per thread (closed-loop)")
    ap.add_argument("--duration-s", type=float, default=None,
                    help="closed-loop: stop issuing after this many "
                         "seconds; open-loop: run length (default 10)")
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop arrival rate (req/s); setting this "
                         "selects open-loop mode")
    ap.add_argument("--max-inflight", type=int, default=256,
                    help="open-loop: arrivals beyond this many in flight "
                         "are dropped (overload safety valve)")
    ap.add_argument("--fleet", action="store_true",
                    help="target is a fleet aggregator: report fleet-"
                         "scope throughput/p99 and per-replica error "
                         "counts from GET /3/Fleet in the summary")
    ap.add_argument("--router", action="store_true",
                    help="drive the fleet router entry point "
                         "(/3/Router/models/..) instead of a replica's "
                         "/3/Predictions, and report shed rate, per-"
                         "version p99 split and rollback events from "
                         "GET /3/Router in the summary")
    ap.add_argument("--concurrent-sweep", action="store_true",
                    help="multi-tenant QoS mode: launch an in-process GBM "
                         "grid sweep and report split idle-vs-contended "
                         "p50/p95/p99 plus sweep wall time (open-loop; "
                         "requires --rate and the platform importable in "
                         "this process)")
    ap.add_argument("--sweep-candidates", type=int, default=4,
                    help="concurrent-sweep: grid size (default 4)")
    ap.add_argument("--sweep-rows", type=int, default=20000,
                    help="concurrent-sweep: synthetic training rows")
    ap.add_argument("--sweep-ntrees", type=int, default=10,
                    help="concurrent-sweep: trees per candidate")
    args = ap.parse_args()
    if args.rate is not None and args.rate <= 0:
        ap.error("--rate must be > 0 (requests per second)")
    if args.concurrent_sweep:
        if args.rate is None:
            ap.error("--concurrent-sweep is open-loop: set --rate")
        stats = run_concurrent_sweep(
            args.host, args.port, args.model, args.frame, rate=args.rate,
            window_s=args.duration_s or 8.0,
            candidates=args.sweep_candidates, sweep_rows=args.sweep_rows,
            sweep_ntrees=args.sweep_ntrees, max_inflight=args.max_inflight,
            router=args.router)
        print(json.dumps(stats, indent=2))
        return 0 if (stats["completed"]
                     and stats["sweep"].get("done")) else 1
    fleet_before = (fleet_summary(args.host, args.port)
                    if args.fleet else None)
    router_before = (router_summary(args.host, args.port)
                     if args.router else None)
    if args.rate is not None:
        stats = run_load_open(args.host, args.port, args.model, args.frame,
                              rate=args.rate,
                              duration_s=args.duration_s or 10.0,
                              max_inflight=args.max_inflight,
                              router=args.router)
    else:
        stats = run_load(args.host, args.port, args.model, args.frame,
                         threads=args.threads, requests=args.requests,
                         duration_s=args.duration_s, router=args.router)
    if args.fleet:
        stats["fleet"] = _fleet_delta_report(
            fleet_before, fleet_summary(args.host, args.port),
            stats.get("wall_s") or 0.0)
        rk = ranks_summary(args.host, args.port)
        if rk:
            stats["ranks"] = rk
    if args.router:
        offered = stats.get("offered") or (
            stats.get("completed", 0) + stats.get("shed_429", 0)
            + stats.get("errors", 0))
        stats["router"] = _router_delta_report(
            router_before, router_summary(args.host, args.port),
            stats.get("wall_s") or 0.0, offered=offered,
            lane_p99=_router_lane_p99(args.host, args.port))
    print(json.dumps(stats, indent=2))
    return 0 if stats["completed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
