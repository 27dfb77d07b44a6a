"""Serving fleet router — health/pressure-aware dispatch over N replicas.

The routing half of the serving fleet (docs/serving.md "Fleet serving"):
one stdlib-HTTP layer that fronts N serving replicas (the PR 13 fleet
peer registry names them) and owns rollout policy for the versioned
model registry (`serving/registry.py`). Per request:

1. **Admission at the router** — one fleet-wide in-flight token budget
   (``H2O3_ROUTER_MAX_INFLIGHT``) plus a fleet pressure gate: when every
   up replica's ``memory_ledger.pressure()`` gauge is above
   ``H2O3_ROUTER_SHED_PRESSURE``, new work sheds HERE with 429 +
   Retry-After instead of N per-replica 429s racing each other.
2. **Version split** — the DKV model key is rewritten to the registry's
   live version (``m`` → ``m@v3``); a running canary takes its split
   percent of requests (deterministic: request sequence mod 100, so a
   10% canary gets exactly every 10th-ish request, not a coin flip).
3. **Least-loaded dispatch** — replicas ranked by local in-flight count,
   then scraped pressure, then bucket-merged predict p99 (both from the
   `/3/Fleet` scrape machinery, refreshed at most every
   ``H2O3_ROUTER_REFRESH_S``); drained replicas are skipped.
4. **Failover** — a connection error or 5xx marks the replica; after
   ``H2O3_ROUTER_DRAIN_ERRORS`` consecutive failures it drains from the
   ring for ``H2O3_ROUTER_DRAIN_COOLDOWN_S``. The in-flight request
   retries on a peer (`runtime/retry.is_transient` classification,
   unified `retry.record` accounting) — a replica killed mid-load costs
   latency, never a caller-visible error.
5. **Canary health** — per-lane windows (requests/errors/latency
   buckets) since the canary started; when the canary's error rate or
   p99 breaches the live lane's by the configured ratios, the registry
   rolls it back automatically (``h2o3_router_rollbacks_total`` + a
   timeline event + the /3/Router document tell the story).
6. **Shadow scoring** — mirror requests to the shadow version on a
   daemon thread, optionally compare prediction heads, never return
   shadow results to the caller.

Replica spans join router spans: the forward carries the request's
``X-H2O3-Trace-Id``, so ``GET /3/Trace?scope=fleet&trace_id=`` shows one
tree across processes. Surfaces: ``GET/POST /3/Router`` (RouterV3),
``h2o3_router_*`` registry families, `runtime/profiler.router_stats`.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from typing import Dict, List, Optional, Tuple

from ..runtime import env_float as _env_float
from ..runtime import env_int as _env_int
from .admission import RejectedError
from .metrics import LatencyHistogram
from .registry import get_registry, versioned_key

__all__ = ["RouterConfig", "Router", "get_router", "peek_router",
           "reset_router"]

from dataclasses import dataclass


@dataclass(frozen=True)
class RouterConfig:
    """Router knobs (``H2O3_ROUTER_*`` — docs/serving.md has the table)."""

    max_inflight: int = 256        # fleet-wide in-flight token budget
    retry_after_s: float = 1.0     # Retry-After hint on router 429s
    shed_pressure: float = 0.97    # shed when EVERY up replica is above
    #                                this pressure (0 disables)
    refresh_s: float = 2.0         # min seconds between fleet scrapes
    timeout_s: float = 60.0        # per-forward HTTP timeout
    max_attempts: int = 3          # distinct replicas tried per request
    drain_errors: int = 3          # consecutive errors before a drain
    drain_cooldown_s: float = 5.0  # drained-replica dwell before a probe
    canary_pct: float = 10.0       # default canary split (POST can override)
    canary_min_samples: int = 20   # canary requests before health verdicts
    canary_err_ratio: float = 2.0  # rollback: canary err rate vs live's
    canary_err_tol: float = 0.02   # absolute err-rate floor added to the
    #                                ratio (a 0-error baseline still
    #                                tolerates 2% canary errors)
    canary_p99_ratio: float = 2.0  # rollback: canary p99 vs live p99
    shadow_compare_rows: int = 10  # prediction head rows diffed per shadow
    #                                mirror (0 skips the comparison)
    shadow_max_inflight: int = 8   # concurrent shadow mirrors (beyond →
    #                                dropped, counted — never backpressure)

    @staticmethod
    def from_env() -> "RouterConfig":
        return RouterConfig(
            max_inflight=_env_int("H2O3_ROUTER_MAX_INFLIGHT", 256),
            retry_after_s=_env_float("H2O3_ROUTER_RETRY_AFTER_S", 1.0),
            shed_pressure=_env_float("H2O3_ROUTER_SHED_PRESSURE", 0.97),
            refresh_s=_env_float("H2O3_ROUTER_REFRESH_S", 2.0),
            timeout_s=_env_float("H2O3_ROUTER_TIMEOUT_S", 60.0),
            max_attempts=_env_int("H2O3_ROUTER_ATTEMPTS", 3),
            drain_errors=_env_int("H2O3_ROUTER_DRAIN_ERRORS", 3),
            drain_cooldown_s=_env_float("H2O3_ROUTER_DRAIN_COOLDOWN_S", 5.0),
            canary_pct=_env_float("H2O3_ROUTER_CANARY_PCT", 10.0),
            canary_min_samples=_env_int("H2O3_ROUTER_CANARY_MIN_SAMPLES",
                                        20),
            canary_err_ratio=_env_float("H2O3_ROUTER_CANARY_ERR_RATIO", 2.0),
            canary_err_tol=_env_float("H2O3_ROUTER_CANARY_ERR_TOL", 0.02),
            canary_p99_ratio=_env_float("H2O3_ROUTER_CANARY_P99_RATIO", 2.0),
            shadow_compare_rows=_env_int("H2O3_ROUTER_SHADOW_COMPARE_ROWS",
                                         10),
            shadow_max_inflight=_env_int("H2O3_ROUTER_SHADOW_MAX_INFLIGHT",
                                         8),
        )


# counters mirrored into the central registry AND kept as local totals for
# the /3/Router document (the ServingMetrics dual-write pattern); every
# totals field below is bind_rest_field-declared so the metrics-consistency
# test covers the router surface
_COUNTERS = ("requests", "errors", "shed", "retries", "failovers", "drains",
             "rollbacks", "warm_loads", "shadow_requests", "shadow_errors",
             "shadow_mismatches", "shadow_dropped")

_REGISTRY: Dict = {}


def _router_registry() -> Dict:
    if not _REGISTRY:
        from ..runtime import metrics_registry as reg

        _REGISTRY.update(
            requests=reg.counter("h2o3_router_requests",
                                 "router-dispatched requests, per lane "
                                 "(live/canary/unversioned)",
                                 labelnames=("lane",)),
            errors=reg.counter("h2o3_router_errors",
                               "router requests that failed on every "
                               "attempted replica, per lane",
                               labelnames=("lane",)),
            shed=reg.counter("h2o3_router_shed",
                             "requests shed at the router (429), by reason "
                             "(budget/pressure/no_replicas)",
                             labelnames=("reason",)),
            retries=reg.counter("h2o3_router_retries",
                                "same-request forwards past the first "
                                "replica (failover + replica-429 hops)"),
            failovers=reg.counter("h2o3_router_failovers",
                                  "forwards that failed on a replica and "
                                  "moved to a peer", labelnames=("replica",)),
            drains=reg.counter("h2o3_router_drains",
                               "replicas drained from the ring after "
                               "consecutive errors", labelnames=("replica",)),
            rollbacks=reg.counter("h2o3_router_rollbacks",
                                  "canary auto-rollbacks, per model",
                                  labelnames=("model",)),
            warm_loads=reg.counter("h2o3_router_warm_loads",
                                   "replica warm-loads orchestrated by the "
                                   "router", labelnames=("replica",)),
            shadow=reg.counter("h2o3_router_shadow",
                               "shadow-scoring events "
                               "(requests/errors/mismatches/dropped)",
                               labelnames=("event",)),
            request_ms=reg.histogram("h2o3_router_request_ms",
                                     "router end-to-end request wall (ms), "
                                     "per lane",
                                     bounds=reg.LATENCY_MS_BOUNDS,
                                     labelnames=("lane",)),
        )
        for c in _COUNTERS:
            fam = ("h2o3_router_shadow" if c.startswith("shadow_")
                   else f"h2o3_router_{c}")
            reg.bind_rest_field("router", f"totals.{c}", fam)
    return _REGISTRY


class _Replica:
    """Router-local view of one ring member."""

    __slots__ = ("name", "url", "inflight", "consecutive_errors",
                 "drained_until", "pressure", "predict_p99_ms", "up")

    def __init__(self, name: str, url: str):
        self.name = name
        self.url = url
        self.inflight = 0
        self.consecutive_errors = 0
        self.drained_until = 0.0
        self.pressure: Optional[float] = None
        self.predict_p99_ms: Optional[float] = None
        self.up: Optional[bool] = None

    def describe(self) -> Dict:
        return dict(name=self.name, url=self.url,
                    up=(1 if self.up else 0) if self.up is not None
                    else None,
                    drained=self.drained_until > time.monotonic(),
                    inflight=self.inflight,
                    consecutive_errors=self.consecutive_errors,
                    pressure=self.pressure,
                    predict_p99_ms=self.predict_p99_ms)


class _Lane:
    """One traffic lane's health window (live vs canary since canary
    start) — error rate + bucket percentiles over the shared bounds."""

    __slots__ = ("n", "errors", "hist")

    def __init__(self):
        from ..runtime import metrics_registry as reg

        self.n = 0
        self.errors = 0
        self.hist = LatencyHistogram(reg.LATENCY_MS_BOUNDS)

    def record(self, ok: bool, lat_ms: Optional[float]) -> None:
        self.n += 1
        if not ok:
            self.errors += 1
        if lat_ms is not None:
            self.hist.record(lat_ms)

    def err_rate(self) -> float:
        return self.errors / self.n if self.n else 0.0

    def p99(self) -> Optional[float]:
        from ..runtime import metrics_registry as reg

        h = self.hist
        if not h.n:
            return None
        return reg.bucket_percentile(h.bounds, h.counts, h.n, 0.99,
                                     h.vmin, h.vmax)

    def describe(self) -> Dict:
        return dict(n=self.n, errors=self.errors,
                    err_rate=round(self.err_rate(), 4), p99_ms=self.p99())


class Router:
    """The routing layer: ring + admission + version split + failover."""

    def __init__(self, config: Optional[RouterConfig] = None):
        self.config = config or RouterConfig.from_env()
        self.registry = get_registry()
        self._lock = threading.Lock()
        self._replicas: Dict[str, _Replica] = {}
        self._inflight = 0
        self._shadow_inflight = 0
        self._seq = 0
        self._last_refresh = 0.0
        self._counters = {c: 0 for c in _COUNTERS}
        # model -> {"live": _Lane, "canary": _Lane, "since": ts} while a
        # canary runs; dropped on promote/rollback
        self._canary_windows: Dict[str, Dict] = {}
        _router_registry()

    # -- accounting ----------------------------------------------------------
    def _bump(self, counter: str, *labels) -> None:
        with self._lock:
            self._counters[counter] += 1
        reg = _router_registry()
        if counter.startswith("shadow_"):
            reg["shadow"].inc(1, counter[len("shadow_"):])
        else:
            reg[counter].inc(1, *labels)

    # -- ring state ----------------------------------------------------------
    def _ring(self) -> List[_Replica]:
        """Sync the router-local replica table with the fleet registry
        (the single source of ring membership)."""
        from ..runtime import fleet

        rows = fleet.peers()
        with self._lock:
            names = set()
            for p in rows:
                names.add(p["name"])
                r = self._replicas.get(p["name"])
                if r is None:
                    r = self._replicas[p["name"]] = _Replica(p["name"],
                                                             p["url"])
                r.url = p["url"]
                if p.get("up") is not None:
                    r.up = bool(p["up"])
            for gone in set(self._replicas) - names:
                del self._replicas[gone]
            return list(self._replicas.values())

    def refresh(self, force: bool = False) -> None:
        """Scrape the fleet (rate-limited) and fold per-replica pressure +
        predict p99 into the ring. Rides `fleet.scrape_states`, so
        `h2o3_fleet_peer_up` flips as a side effect — a dead replica is
        marked down on the shared liveness gauge by the same pass that
        drops it from dispatch."""
        now = time.monotonic()
        with self._lock:
            if not force and now - self._last_refresh < self.config.refresh_s:
                return
            self._last_refresh = now
        from ..runtime import fleet

        self._ring()
        for name, state in fleet.scrape_states():
            with self._lock:
                r = self._replicas.get(name)
                if r is None:
                    continue
                r.up = state is not None
                if state is None:
                    continue
                fam = state.get("h2o3_memory_pressure") or {}
                for s in fam.get("series") or ():
                    r.pressure = float(s.get("value") or 0.0)
                    break
                r.predict_p99_ms = fleet._serving_summary(state) \
                    .get("predict_p99_ms")

    def _candidates(self) -> List[_Replica]:
        """Dispatch order: up, undrained replicas first, least-loaded
        first — local in-flight count dominates (it is per-request fresh),
        then scraped pressure, then scraped predict p99 (both at most
        `refresh_s` stale). A replica past its drain cooldown re-enters
        the ring at the back as its own probe: if it is still sick, the
        request that probes it retries on a healthy peer."""
        now = time.monotonic()
        with self._lock:
            reps = list(self._replicas.values())

        def score(r: _Replica) -> Tuple:
            drained = r.drained_until > now
            down = r.up is False
            return (down, drained, r.inflight,
                    r.pressure if r.pressure is not None else 0.0,
                    r.predict_p99_ms if r.predict_p99_ms is not None
                    else 0.0)

        return sorted(reps, key=score)

    def _mark_result(self, r: _Replica, ok: bool) -> None:
        cfg = self.config
        with self._lock:
            if ok:
                r.consecutive_errors = 0
                r.up = True
                return
            r.consecutive_errors += 1
            drain = (r.consecutive_errors >= cfg.drain_errors
                     and r.drained_until <= time.monotonic())
            if drain:
                r.drained_until = time.monotonic() + cfg.drain_cooldown_s
                r.consecutive_errors = 0
        if drain:
            self._bump("drains", r.name)
            from ..runtime import tracing
            from ..runtime.timeline import Timeline

            Timeline.record("router", f"drain {r.name}",
                            cooldown_s=cfg.drain_cooldown_s)
            tracing.event("router_drain", replica=r.name)

    # -- version split -------------------------------------------------------
    def _pick_version(self, model: str,
                      seq: int) -> Tuple[Optional[str], str]:
        """(version, lane): the registry's live version unless the canary
        split claims this request. Deterministic — request seq mod 100
        against the split percent — so a canary at x% sees x% of traffic
        exactly, independent of arrival timing."""
        cv, pct = self.registry.canary(model)
        if cv is not None and pct > 0 and (seq % 100) < pct:
            return cv, "canary"
        live = self.registry.live(model)
        if live is not None:
            return live, "live"
        if cv is not None:
            # canary with no live baseline: the non-canary share passes
            # through to the unversioned key
            return None, "unversioned"
        return None, "unversioned"

    # -- the dispatch --------------------------------------------------------
    def route(self, model: str, frame: str,
              params: Optional[Dict] = None,
              trace_id: Optional[str] = None) -> Dict:
        """Route one predict request; returns the replica's response
        document. Raises RejectedError on shed, `urllib.error.HTTPError`
        to mirror a replica's 4xx/exhausted 5xx, OSError when every
        attempted replica was unreachable."""
        cfg = self.config
        self.refresh()
        with self._lock:
            seq = self._seq
            self._seq += 1
            if self._inflight >= cfg.max_inflight:
                shed_reason = "budget"
            else:
                shed_reason = self._pressure_shed_locked()
            if shed_reason is None:
                self._inflight += 1
        if shed_reason is not None:
            self._bump("shed", shed_reason)
            from ..runtime import tracing

            tracing.event("router_shed", reason=shed_reason)
            raise RejectedError(
                f"router shed ({shed_reason}): "
                f"{self._inflight}/{cfg.max_inflight} in flight",
                retry_after_s=cfg.retry_after_s)
        version, lane = self._pick_version(model, seq)
        key = versioned_key(model, version) if version else model
        win = self._lane_window(model, lane)
        t0 = time.perf_counter()
        try:
            doc, replica = self._dispatch(key, frame, params, trace_id)
        except urllib.error.HTTPError as e:
            if e.code < 500 and e.code != 429:
                raise          # the request's own 4xx — not a lane failure
            self._record_lane(model, lane, win, ok=False, lat_ms=None)
            self._bump("errors", lane)
            raise
        except RejectedError:
            raise              # shed (already counted), not a lane failure
        except Exception:
            self._record_lane(model, lane, win, ok=False, lat_ms=None)
            self._bump("errors", lane)
            raise
        finally:
            with self._lock:
                self._inflight -= 1
        lat_ms = (time.perf_counter() - t0) * 1e3
        self._bump("requests", lane)
        _router_registry()["request_ms"].observe(lat_ms, lane)
        self._record_lane(model, lane, win, ok=True, lat_ms=lat_ms)
        if lane != "canary":
            self._maybe_shadow(model, frame, params, trace_id,
                               doc, replica)
        return doc

    def _pressure_shed_locked(self) -> Optional[str]:
        """Fleet pressure gate (callers hold the lock): shed only when
        every replica we believe is up reports pressure at/above the
        threshold — one hot replica is a ranking problem, a hot FLEET is
        an admission problem."""
        cfg = self.config
        if cfg.shed_pressure <= 0:
            return None
        ups = [r for r in self._replicas.values()
               if r.up is not False and r.pressure is not None]
        if ups and all(r.pressure >= cfg.shed_pressure for r in ups):
            return "pressure"
        return None

    def _dispatch(self, key: str, frame: str, params: Optional[Dict],
                  trace_id: Optional[str]) -> Tuple[Dict, _Replica]:
        """Forward to the best replica, failing over across peers. The
        caller sees an error only when every attempted replica failed."""
        from ..runtime import retry as retrylib

        cfg = self.config
        order = self._candidates()
        if not order:
            self._bump("shed", "no_replicas")
            raise RejectedError("router has no registered replicas "
                                "(POST /3/Fleet to add ring members)",
                                retry_after_s=cfg.retry_after_s)
        last_err: Optional[BaseException] = None
        for i, r in enumerate(order[:max(cfg.max_attempts, 1)]):
            if i > 0:
                self._bump("retries")
                retrylib.record("router", "retries")
            with self._lock:
                r.inflight += 1
            try:
                doc = self._forward_one(r, key, frame, params, trace_id)
                self._mark_result(r, ok=True)
                return doc, r
            except urllib.error.HTTPError as e:
                e.read()
                if e.code < 500 and e.code != 429:
                    # the request's own fault: replica is healthy, mirror
                    # the 4xx to the caller unchanged
                    self._mark_result(r, ok=True)
                    raise
                # replica-shed 429s hop to a less-loaded peer; 5xx marks
                # the replica on its way out
                self._mark_result(r, ok=e.code == 429)
                if e.code >= 500:
                    self._bump("failovers", r.name)
                last_err = e
            except OSError as e:
                # connection-level failure — the killed-replica path
                self._mark_result(r, ok=False)
                self._bump("failovers", r.name)
                last_err = e
                # a dead socket means the scrape view is stale: refresh
                # now so peer_up flips and ranking stops proposing it
                self.refresh(force=True)
            finally:
                with self._lock:
                    r.inflight -= 1
        assert last_err is not None
        retrylib.record("router", "attempts_exhausted")
        raise last_err

    def _forward_one(self, r: _Replica, key: str, frame: str,
                     params: Optional[Dict],
                     trace_id: Optional[str]) -> Dict:
        from ..runtime import faults, tracing

        url = (f"{r.url}/3/Predictions/models/"
               f"{urllib.parse.quote(key, safe='')}/frames/"
               f"{urllib.parse.quote(frame, safe='')}")
        body = urllib.parse.urlencode(params or {}).encode()
        headers = {}
        if trace_id:
            headers["X-H2O3-Trace-Id"] = trace_id
        with tracing.span(f"forward:{r.name}", kind="router",
                          trace_id=trace_id, replica=r.name, model=key):
            faults.check("router.forward", detail=f"{r.name}:{key}")
            req = urllib.request.Request(url, data=body, headers=headers)
            # serving-class QoS dispatch: in a single-process fleet the
            # forward scores in THIS runtime — the gate must see it
            from ..runtime import qos as _qos

            with _qos.serving_dispatch(f"router:{key}"):
                with urllib.request.urlopen(
                        req, timeout=self.config.timeout_s) as resp:
                    return json.loads(resp.read().decode())

    # -- canary health -------------------------------------------------------
    def _lane_window(self, model: str, lane: str) -> Optional[_Lane]:
        cv, _pct = self.registry.canary(model)
        with self._lock:
            if cv is None:
                self._canary_windows.pop(model, None)
                return None
            w = self._canary_windows.get(model)
            if w is None or w["version"] != cv:
                w = self._canary_windows[model] = dict(
                    version=cv, since=time.time(),
                    live=_Lane(), canary=_Lane())
            return w.get(lane if lane == "canary" else "live")

    def _record_lane(self, model: str, lane: str, win: Optional[_Lane],
                     ok: bool, lat_ms: Optional[float]) -> None:
        if win is None:
            return
        with self._lock:
            win.record(ok, lat_ms)
        if lane == "canary":
            self._maybe_rollback(model)

    def _maybe_rollback(self, model: str) -> None:
        """Auto-rollback verdict after each canary-lane request: with at
        least `canary_min_samples` canary observations, the canary's
        error rate must stay under (live's × err_ratio + err_tol), and —
        when the live lane has enough samples to make the comparison
        meaningful — its p99 under live's × p99_ratio."""
        cfg = self.config
        with self._lock:
            w = self._canary_windows.get(model)
            if w is None:
                return
            can, base = w["canary"], w["live"]
            if can.n < cfg.canary_min_samples:
                return
            reason = None
            err_bound = base.err_rate() * cfg.canary_err_ratio \
                + cfg.canary_err_tol
            if can.err_rate() > err_bound:
                reason = (f"error rate {can.err_rate():.3f} > "
                          f"{err_bound:.3f} (live {base.err_rate():.3f})")
            elif base.n >= cfg.canary_min_samples:
                bp, cp = base.p99(), can.p99()
                if (bp is not None and cp is not None and bp > 0
                        and cp > bp * cfg.canary_p99_ratio):
                    reason = (f"p99 {cp:.1f}ms > {cfg.canary_p99_ratio}x "
                              f"live {bp:.1f}ms")
            if reason is None:
                return
            version = w["version"]
            del self._canary_windows[model]
        self.registry.rollback(model, reason=f"auto: {reason}")
        self._bump("rollbacks", model)
        from ..runtime import tracing

        tracing.event("router_rollback", model=model, version=version,
                      reason=reason)

    # -- shadow scoring ------------------------------------------------------
    def _maybe_shadow(self, model: str, frame: str, params: Optional[Dict],
                      trace_id: Optional[str], primary_doc: Dict,
                      primary_replica: _Replica) -> None:
        sv = self.registry.shadow(model)
        if sv is None:
            return
        with self._lock:
            if self._shadow_inflight >= self.config.shadow_max_inflight:
                drop = True
            else:
                drop = False
                self._shadow_inflight += 1
        if drop:
            self._bump("shadow_dropped")
            return
        skey = versioned_key(model, sv)
        pkey = ((primary_doc.get("predictions_frame") or {}).get("name")
                if isinstance(primary_doc, dict) else None)
        t = threading.Thread(
            target=self._shadow_one,
            args=(skey, frame, params, trace_id, pkey, primary_replica),
            daemon=True, name=f"h2o3tpu-shadow-{model}")
        t.start()

    def _shadow_one(self, skey: str, frame: str, params: Optional[Dict],
                    trace_id: Optional[str], primary_pred_key: Optional[str],
                    primary_replica: _Replica) -> None:
        """Mirror one request to the shadow version. Results never reach
        the caller; a differing prediction head bumps
        `h2o3_router_shadow{event="mismatches"}` + a timeline event."""
        try:
            self._bump("shadow_requests")
            order = self._candidates()
            if not order:
                raise OSError("no replicas")
            r = order[0]
            doc = self._forward_one(r, skey, frame, params, trace_id)
            rows = self.config.shadow_compare_rows
            if rows > 0 and primary_pred_key:
                skey_pred = (doc.get("predictions_frame") or {}).get("name")
                a = self._pred_head(primary_replica, primary_pred_key, rows)
                b = self._pred_head(r, skey_pred, rows) if skey_pred \
                    else None
                if a is not None and b is not None and a != b:
                    self._bump("shadow_mismatches")
                    from ..runtime.timeline import Timeline

                    Timeline.record("router", f"shadow mismatch {skey}",
                                    frame=frame, rows=rows)
        except Exception:
            self._bump("shadow_errors")
        finally:
            with self._lock:
                self._shadow_inflight -= 1

    def _pred_head(self, r: _Replica, pred_key: str,
                   rows: int) -> Optional[List]:
        """First `rows` values of the prediction column, fetched from the
        replica that scored it (None when unreadable — an unreadable head
        is a shadow ERROR path, never a mismatch verdict)."""
        try:
            url = (f"{r.url}/3/Frames/"
                   f"{urllib.parse.quote(pred_key, safe='')}")
            with urllib.request.urlopen(
                    url, timeout=self.config.timeout_s) as resp:
                doc = json.loads(resp.read().decode())
            for col in doc.get("columns") or ():
                if col.get("label") == "predict":
                    return list(col.get("data") or ())[:rows]
        except Exception:
            return None
        return None

    # -- warm orchestration --------------------------------------------------
    def warm(self, model: str, version: str,
             frame: Optional[str] = None) -> Dict:
        """Fan the published artifact out to every replica's scorer cache
        (``POST /3/Serving/warm``) BEFORE any traffic flips — each
        replica loads the mojo into its DKV under the versioned key and
        primes the compiled-scorer cache against `frame`, reporting its
        XLA trace delta. Per-replica results land on the registry record
        (the warm-load pin asserts a later first predict traces
        nothing)."""
        from ..runtime import fleet
        from ..runtime.retry import RetryPolicy

        artifact = self.registry.artifact(model, version)
        key = versioned_key(model, version)
        body = urllib.parse.urlencode(
            dict(path=artifact, model=key,
                 **(dict(frame=frame) if frame else {}))).encode()
        policy = RetryPolicy(name="router", max_attempts=2,
                             deadline_s=self.config.timeout_s)

        def one(r: _Replica) -> Tuple[str, Dict]:
            def post():
                req = urllib.request.Request(r.url + "/3/Serving/warm",
                                             data=body)
                with urllib.request.urlopen(
                        req, timeout=self.config.timeout_s) as resp:
                    return json.loads(resp.read().decode())

            try:
                out = policy.call(post)
                self._bump("warm_loads", r.name)
                self.registry.record_warm(model, version, r.name, out)
                return (r.name, dict(ok=True, **out))
            except Exception as e:
                return (r.name, dict(ok=False,
                                     error=f"{type(e).__name__}: {e}"))

        results = dict(fleet._fan_out(one, self._ring()))
        return dict(model=model, version=version, artifact=artifact,
                    replicas=results,
                    warmed=sum(1 for v in results.values() if v.get("ok")))

    # -- read side -----------------------------------------------------------
    def snapshot(self, probe: bool = False) -> Dict:
        """The ``GET /3/Router`` document: ring, per-model versions +
        split, canary health windows, counters, config. `probe=True`
        forces a fleet refresh first."""
        if probe:
            self.refresh(force=True)
        else:
            self._ring()
        with self._lock:
            ring = [r.describe() for r in self._replicas.values()]
            totals = dict(self._counters)
            windows = {m: dict(version=w["version"], since=w["since"],
                               live=w["live"].describe(),
                               canary=w["canary"].describe())
                       for m, w in self._canary_windows.items()}
            inflight = self._inflight
        cfg = self.config
        return dict(
            ring=ring,
            inflight=inflight,
            totals=totals,
            models=self.registry.snapshot()["models"],
            canary_health=windows,
            config=dict(max_inflight=cfg.max_inflight,
                        shed_pressure=cfg.shed_pressure,
                        refresh_s=cfg.refresh_s,
                        max_attempts=cfg.max_attempts,
                        drain_errors=cfg.drain_errors,
                        drain_cooldown_s=cfg.drain_cooldown_s,
                        canary_pct=cfg.canary_pct,
                        canary_min_samples=cfg.canary_min_samples,
                        canary_err_ratio=cfg.canary_err_ratio,
                        canary_p99_ratio=cfg.canary_p99_ratio,
                        shadow_compare_rows=cfg.shadow_compare_rows),
        )


_router: Optional[Router] = None
_router_lock = threading.Lock()


def get_router() -> Router:
    """The process-wide router (lazily built from env config)."""
    global _router
    with _router_lock:
        if _router is None:
            _router = Router()
        return _router


def peek_router() -> Optional[Router]:
    """The router if one exists — profiler readers must not
    instantiate a routing layer just to report that there isn't one."""
    return _router


def reset_router(config: Optional[RouterConfig] = None) -> Router:
    """Swap in a fresh router (tests / config reload)."""
    global _router
    with _router_lock:
        _router = Router(config)
        return _router
