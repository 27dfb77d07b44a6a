"""Central metrics registry — the one scrape surface for every subsystem.

Before this module, observability was five disconnected cumulative-counter
snapshots (serving / ingest / munge / training / faults) with no time
series, no percentiles and no machine-scrapable surface. The registry is
the spine underneath them: every subsystem registers its counters, gauges
and histograms HERE, and three read surfaces are derived from the one
store —

- ``prometheus_text()`` → ``GET /3/Metrics`` (Prometheus/OpenMetrics text
  exposition: ``# HELP``/``# TYPE`` lines, ``_total`` counter suffixes,
  ``_bucket{le=...}``/``_sum``/``_count`` histogram series);
- ``snapshot()`` → the JSON fold in ``/3/Profiler``;
- per-metric reads (``Counter.value()``, ``Histogram.percentile(q)``,
  ``Counter.rate(window_s)``) for tests and operators.

Semantics follow Prometheus, not the legacy snapshot modules: registry
counters are MONOTONE for the life of the process (module-level ``reset()``
helpers reset the REST-snapshot state, never the scrape surface), so two
scrapes always see non-decreasing counters. The legacy ``/3/*/metrics``
endpoints stay byte-compatible — their modules dual-write (their resettable
snapshot state AND the registry) and declare which REST field each registry
metric backs via ``bind_rest_field``; the metrics-consistency test walks
those bindings so a new counter can never ship outside the scrape surface.

Cost discipline (the idle-overhead acceptance pin): one ``threading.Lock``
per metric child, a handful of float/int adds per record, and a ring-buffer
time-series sample AT MOST once per ``H2O3_METRICS_RING_INTERVAL_S``
(default 1 s) — no background thread, no per-request allocation beyond the
occasional (ts, value) tuple.

Naming convention (docs/observability.md): ``h2o3_<subsystem>_<what>`` +
unit suffix (``_total`` for counters, ``_ms``/``_s``/``_bytes`` inside
histogram/gauge names). Labels are a fixed tuple per family, declared at
registration.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import env_float, env_int

__all__ = ["Counter", "Gauge", "Histogram", "counter", "gauge", "histogram",
           "get", "names", "snapshot", "prometheus_text", "export_state",
           "bind_rest_field", "rest_bindings", "register_collect_hook",
           "bucket_percentile", "LATENCY_MS_BOUNDS"]

# shared fixed latency buckets (ms): serving, loadgen and REST request
# histograms all bin into the same bounds so percentiles are comparable
# across surfaces ("the shared histogram buckets" of the loadgen satellite)
LATENCY_MS_BOUNDS = (0.5, 1, 2, 5, 10, 25, 50, 100, 250, 500, 1000, 2500,
                     5000, 10000, 30000)

_RING_LEN = env_int("H2O3_METRICS_RING", 240)
_RING_INTERVAL_S = env_float("H2O3_METRICS_RING_INTERVAL_S", 1.0)
# label cardinality bound per family: registry series are monotone for
# the life of the process, so an unbounded label (uuid-suffixed model
# keys on a fleet that trains/serves/deletes forever) would grow memory
# and the scrape body without limit — past the cap, new label tuples
# collapse into one "_overflow" series (totals stay correct; per-label
# resolution is what saturates)
_MAX_SERIES = env_int("H2O3_METRICS_MAX_SERIES", 256)
_OVERFLOW = "_overflow"


def bucket_percentile(bounds, counts, n, q, vmin=None, vmax=None):
    """Bucket-interpolated q-quantile (q in [0,1]) over raw (bounds,
    counts): linear interpolation within the owning bucket, min/max
    clamping the open-ended buckets. The ONE estimator — shared by
    `Histogram.percentile` and the fleet aggregator's merged-bucket
    percentiles (runtime/fleet), so a per-replica p99 and the fleet p99
    can never disagree on identical data."""
    if not n:
        return None
    rank = q * (n - 1)
    cum = 0
    for i, cnt in enumerate(counts):
        if cnt == 0:
            continue
        if rank < cum + cnt:
            lo = bounds[i - 1] if i > 0 else (
                vmin if vmin is not None else 0.0)
            hi = bounds[i] if i < len(bounds) else (
                vmax if vmax is not None else lo)
            lo = max(lo, vmin) if vmin is not None else lo
            hi = min(hi, vmax) if vmax is not None else hi
            if hi <= lo:
                return float(lo)
            frac = (rank - cum + 1) / cnt if cnt > 1 else 0.5
            frac = min(max(frac, 0.0), 1.0)
            return float(lo + (hi - lo) * frac)
        cum += cnt
    return vmax


def _sanitize_name(name: str) -> str:
    out = []
    for i, ch in enumerate(name):
        ok = ch.isascii() and (ch.isalpha() or ch == "_"
                               or (ch.isdigit() and i > 0))
        out.append(ch if ok else "_")
    return "".join(out)


def _escape_label(v: str) -> str:
    return (str(v).replace("\\", r"\\").replace("\n", r"\n")
            .replace('"', r'\"'))


def _fmt_value(v: float) -> str:
    if v != v:
        return "NaN"
    if v in (float("inf"), float("-inf")):
        return "+Inf" if v > 0 else "-Inf"
    f = float(v)
    return repr(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


class _Child:
    """One labeled series of a family: the actual mutation target. Every
    update takes this child's own lock and nothing else — the registry
    lock guards only registration, so a counter add never contends with a
    scrape or another family."""

    __slots__ = ("labels", "_lock", "_v", "_ring", "_t_sample")

    def __init__(self, labels: Tuple[str, ...]):
        self.labels = labels
        self._lock = threading.Lock()
        self._v = 0.0
        self._ring: Optional[deque] = None
        self._t_sample = 0.0

    def _add(self, by: float, ring: bool) -> None:
        with self._lock:
            self._v += by
            if ring:
                now = time.time()
                if now - self._t_sample >= _RING_INTERVAL_S:
                    if self._ring is None:
                        self._ring = deque(maxlen=_RING_LEN)
                    self._ring.append((now, self._v))
                    self._t_sample = now

    def _set(self, v: float) -> None:
        with self._lock:
            self._v = float(v)

    def value(self) -> float:
        with self._lock:
            return self._v

    def rate(self, window_s: float = 60.0) -> Optional[float]:
        """Windowed per-second rate from the ring-buffer time series, or
        None before two samples land inside the window."""
        with self._lock:
            if not self._ring or len(self._ring) < 2:
                return None
            now, v_now = time.time(), self._v
            cutoff = now - window_s
            base = None
            for t, v in self._ring:
                if t >= cutoff:
                    base = (t, v)
                    break
            if base is None or now - base[0] <= 1e-9:
                return None
            return (v_now - base[1]) / (now - base[0])

    def series(self) -> List[Tuple[float, float]]:
        with self._lock:
            return list(self._ring or ())


class _Metric:
    """One metric family: name + help + fixed label names + children."""

    kind = "untyped"

    def __init__(self, name: str, help: str, labelnames: Sequence[str] = ()):
        self.name = _sanitize_name(name)
        self.help = help
        self.labelnames = tuple(labelnames)
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], _Child] = {}

    def _child(self, labelvalues: Tuple[str, ...]) -> _Child:
        if len(labelvalues) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, got "
                f"{labelvalues}")
        c = self._children.get(labelvalues)
        if c is None:
            with self._lock:
                c = self._children.get(labelvalues)
                if c is None:
                    if self.labelnames and len(self._children) >= _MAX_SERIES:
                        labelvalues = (_OVERFLOW,) * len(self.labelnames)
                        c = self._children.get(labelvalues)
                        if c is None:
                            c = self._children[labelvalues] = \
                                self._make_child(labelvalues)
                    else:
                        c = self._children[labelvalues] = self._make_child(
                            labelvalues)
        return c

    def _make_child(self, labelvalues: Tuple[str, ...]) -> _Child:
        return _Child(labelvalues)

    def children(self) -> Dict[Tuple[str, ...], _Child]:
        with self._lock:
            return dict(self._children)

    def _label_str(self, labelvalues: Tuple[str, ...],
                   extra: str = "") -> str:
        pairs = [f'{n}="{_escape_label(v)}"'
                 for n, v in zip(self.labelnames, labelvalues)]
        if extra:
            pairs.append(extra)
        return "{" + ",".join(pairs) + "}" if pairs else ""


class Counter(_Metric):
    """Monotone counter (optionally labeled) with a bounded ring-buffer
    time series per child for windowed rates."""

    kind = "counter"

    def inc(self, by: float = 1.0, *labelvalues) -> None:
        if by < 0:
            raise ValueError(f"{self.name}: counters only go up (by={by})")
        self._child(tuple(str(v) for v in labelvalues))._add(by, ring=True)

    def value(self, *labelvalues) -> float:
        key = tuple(str(v) for v in labelvalues)
        if key not in self._children:
            return 0.0
        return self._child(key).value()

    def total(self) -> float:
        return sum(c.value() for c in self.children().values())

    def rate(self, window_s: float = 60.0, *labelvalues) -> Optional[float]:
        key = tuple(str(v) for v in labelvalues)
        if key not in self._children:
            return None
        return self._child(key).rate(window_s)

    def expo_lines(self) -> List[str]:
        name = self.name if self.name.endswith("_total") \
            else self.name + "_total"
        out = [f"# HELP {name} {self.help}", f"# TYPE {name} counter"]
        kids = self.children() or ({(): _Child(())} if not self.labelnames
                                   else {})
        for lv, c in sorted(kids.items()):
            out.append(f"{name}{self._label_str(lv)} {_fmt_value(c.value())}")
        return out


class Gauge(_Metric):
    """Settable value, or a callback sampled at scrape time."""

    kind = "gauge"

    def __init__(self, name: str, help: str, labelnames: Sequence[str] = (),
                 fn: Optional[Callable[[], float]] = None):
        super().__init__(name, help, labelnames)
        if fn is not None and labelnames:
            raise ValueError("callback gauges cannot be labeled")
        self._fn = fn

    def set(self, v: float, *labelvalues) -> None:
        self._child(tuple(str(x) for x in labelvalues))._set(v)

    def remove_series(self, *labelvalues) -> bool:
        """Drop one labeled series. A gauge is CURRENT state — a series
        whose subject no longer exists (a deregistered fleet peer, say)
        must leave the scrape rather than freeze at its last value.
        Counters stay monotone for the life of the process; only gauges
        expose removal."""
        key = tuple(str(x) for x in labelvalues)
        with self._lock:
            return self._children.pop(key, None) is not None

    def value(self, *labelvalues) -> float:
        if self._fn is not None:
            try:
                return float(self._fn())
            except Exception:
                return float("nan")
        key = tuple(str(v) for v in labelvalues)
        if key not in self._children:
            return 0.0
        return self._child(key).value()

    def expo_lines(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} gauge"]
        if self._fn is not None:
            out.append(f"{self.name} {_fmt_value(self.value())}")
            return out
        kids = self.children() or ({(): _Child(())} if not self.labelnames
                                   else {})
        for lv, c in sorted(kids.items()):
            out.append(
                f"{self.name}{self._label_str(lv)} {_fmt_value(c.value())}")
        return out


class _HistChild(_Child):
    __slots__ = ("counts", "n", "total", "vmin", "vmax")

    def __init__(self, labels: Tuple[str, ...], nbuckets: int):
        super().__init__(labels)
        self.counts = [0] * nbuckets
        self.n = 0
        self.total = 0.0
        self.vmin: Optional[float] = None
        self.vmax: Optional[float] = None


class Histogram(_Metric):
    """Fixed-bound histogram: counts per bucket + running sum/min/max.

    The state is O(len(bounds)) regardless of observation count, so a
    snapshot is cheap and percentiles are estimated by linear interpolation
    inside the owning bucket (tested against numpy within bucket-width
    tolerance). The last bucket is +Inf overflow."""

    kind = "histogram"

    def __init__(self, name: str, help: str,
                 bounds: Sequence[float] = LATENCY_MS_BOUNDS,
                 labelnames: Sequence[str] = ()):
        super().__init__(name, help, labelnames)
        b = tuple(float(x) for x in bounds)
        if list(b) != sorted(b) or len(set(b)) != len(b):
            raise ValueError(f"{name}: bounds must be strictly increasing")
        self.bounds = b

    def _make_child(self, labelvalues: Tuple[str, ...]) -> _HistChild:
        return _HistChild(labelvalues, len(self.bounds) + 1)

    def observe(self, v: float, *labelvalues) -> None:
        c = self._child(tuple(str(x) for x in labelvalues))
        i = len(self.bounds)
        for j, b in enumerate(self.bounds):
            if v <= b:
                i = j
                break
        with c._lock:
            c.counts[i] += 1
            c.n += 1
            c.total += v
            c.vmin = v if c.vmin is None else min(c.vmin, v)
            c.vmax = v if c.vmax is None else max(c.vmax, v)

    def _counts(self, *labelvalues) -> Tuple[List[int], int, float,
                                             Optional[float],
                                             Optional[float]]:
        # read path must not materialize a series: probing an unknown
        # label (typo'd model key, dashboard helper) would otherwise add
        # a permanent all-zero family child to the scrape and burn a slot
        # of the series-cardinality cap
        key = tuple(str(x) for x in labelvalues)
        c = self._children.get(key)
        if c is None:
            return [0] * (len(self.bounds) + 1), 0, 0.0, None, None
        with c._lock:
            return list(c.counts), c.n, c.total, c.vmin, c.vmax

    def percentile(self, q: float, *labelvalues) -> Optional[float]:
        """Estimate the q-quantile (q in [0,1]) by linear interpolation
        within the owning bucket; min/max clamp the open-ended buckets."""
        counts, n, _total, vmin, vmax = self._counts(*labelvalues)
        return bucket_percentile(self.bounds, counts, n, q, vmin, vmax)

    def summary(self, *labelvalues) -> Dict:
        """The legacy LatencyHistogram.snapshot() shape + percentiles, so
        /3/Serving/metrics histograms stay byte-compatible where they were
        and gain p50/p95/p99 where they're new."""
        counts, n, total, vmin, vmax = self._counts(*labelvalues)
        return dict(
            bounds=list(self.bounds), counts=counts, count=n,
            mean=round(total / n, 4) if n else None,
            min=vmin, max=vmax,
            p50=self.percentile(0.50, *labelvalues),
            p95=self.percentile(0.95, *labelvalues),
            p99=self.percentile(0.99, *labelvalues),
        )

    def expo_lines(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} histogram"]
        for lv, c in sorted(self.children().items()):
            with c._lock:
                counts, n, total = list(c.counts), c.n, c.total
            cum = 0
            for b, cnt in zip(self.bounds, counts):
                cum += cnt
                le = f'le="{_fmt_value(b)}"'
                out.append(f"{self.name}_bucket"
                           f"{self._label_str(lv, le)} {cum}")
            inf_le = 'le="+Inf"'
            out.append(f"{self.name}_bucket"
                       f"{self._label_str(lv, inf_le)} {n}")
            out.append(f"{self.name}_sum{self._label_str(lv)} "
                       f"{_fmt_value(total)}")
            out.append(f"{self.name}_count{self._label_str(lv)} {n}")
        return out


# -- the registry -------------------------------------------------------------

_LOCK = threading.Lock()
_METRICS: Dict[str, _Metric] = {}
# endpoint → {field_path: metric_name}: which registry metric backs each
# REST snapshot field (the metrics-consistency test walks this)
_REST_BINDINGS: Dict[str, Dict[str, str]] = {}


def _register(cls, name: str, help: str, **kw) -> _Metric:
    name = _sanitize_name(name)
    with _LOCK:
        m = _METRICS.get(name)
        if m is not None:
            if type(m) is not cls:
                raise ValueError(
                    f"metric {name!r} already registered as {m.kind}")
            return m
        m = _METRICS[name] = cls(name, help, **kw)
        return m


def counter(name: str, help: str = "",
            labelnames: Sequence[str] = ()) -> Counter:
    """Get-or-create a counter family (idempotent by name)."""
    return _register(Counter, name, help, labelnames=labelnames)


def gauge(name: str, help: str = "", labelnames: Sequence[str] = (),
          fn: Optional[Callable[[], float]] = None) -> Gauge:
    return _register(Gauge, name, help, labelnames=labelnames, fn=fn)


def histogram(name: str, help: str = "",
              bounds: Sequence[float] = LATENCY_MS_BOUNDS,
              labelnames: Sequence[str] = ()) -> Histogram:
    return _register(Histogram, name, help, bounds=bounds,
                     labelnames=labelnames)


def get(name: str) -> Optional[_Metric]:
    with _LOCK:
        return _METRICS.get(_sanitize_name(name))


def names() -> List[str]:
    with _LOCK:
        return sorted(_METRICS)


def bind_rest_field(endpoint: str, field_path: str, metric_name: str) -> None:
    """Declare that `field_path` of `/3/{endpoint}/metrics` is backed by
    registry metric `metric_name` — the contract the metrics-consistency
    test enforces (every declared field's metric must exist AND appear in
    GET /3/Metrics; every counter-ish snapshot field must be declared)."""
    with _LOCK:
        _REST_BINDINGS.setdefault(endpoint, {})[field_path] = \
            _sanitize_name(metric_name)


def rest_bindings() -> Dict[str, Dict[str, str]]:
    with _LOCK:
        return {k: dict(v) for k, v in _REST_BINDINGS.items()}


# pull-model gauges: hooks run at the top of every scrape/snapshot so
# subsystems that account state on demand (the memory ledger) refresh
# their set-gauges no staler than one scrape — the Prometheus custom-
# collector stance without per-family collector plumbing
_COLLECT_HOOKS: List[Callable[[], None]] = []


def register_collect_hook(fn: Callable[[], None]) -> None:
    """Run `fn` before every prometheus_text()/snapshot() read (idempotent
    by identity). Hooks must be cheap or self-rate-limited; a raising hook
    is skipped, never fails the scrape."""
    with _LOCK:
        if fn not in _COLLECT_HOOKS:
            _COLLECT_HOOKS.append(fn)


def _run_collect_hooks() -> None:
    with _LOCK:
        hooks = list(_COLLECT_HOOKS)
    for fn in hooks:
        try:
            fn()
        except Exception:
            pass


def snapshot() -> Dict:
    """JSON view of every family (the /3/Profiler `metrics` fold): value
    per child for counters/gauges, summary for histograms, plus 60s
    windowed rates where a time series exists."""
    _run_collect_hooks()
    with _LOCK:
        metrics = dict(_METRICS)
    out: Dict[str, Dict] = {}
    for name, m in sorted(metrics.items()):
        fam: Dict = dict(kind=m.kind, help=m.help)
        if isinstance(m, Histogram):
            fam["series"] = {
                ",".join(lv) or "": m.summary(*lv)
                for lv in m.children()}
        elif isinstance(m, Gauge) and m._fn is not None:
            fam["value"] = m.value()
        else:
            ser = {}
            for lv, c in m.children().items():
                d: Dict = dict(value=c.value())
                r = c.rate(60.0)
                if r is not None:
                    d["rate_1m"] = round(r, 3)
                ser[",".join(lv) or ""] = d
            fam["series"] = ser
        out[name] = fam
    return out


def export_state() -> Dict:
    """LOSSLESS JSON view of every family — the cross-process aggregation
    payload (``GET /3/Metrics?format=json``, consumed by runtime/fleet).

    Unlike `snapshot()` (a human/profiler fold whose label tuples are
    joined into display strings and whose histograms carry derived
    percentiles), this export preserves exactly what merging needs:
    labelnames + raw per-child label value lists, raw counter/gauge
    values, and histogram bounds + per-bucket counts + sum/min/max — so an
    aggregator can SUM counters, bucket-merge histograms (keeping
    p50/p95/p99 exact over the merged buckets) and keep gauges per-peer."""
    _run_collect_hooks()
    with _LOCK:
        metrics = dict(_METRICS)
    out: Dict[str, Dict] = {}
    for name, m in sorted(metrics.items()):
        fam: Dict = dict(kind=m.kind, help=m.help,
                         labelnames=list(m.labelnames))
        if isinstance(m, Histogram):
            fam["bounds"] = list(m.bounds)
            series = []
            for lv, c in sorted(m.children().items()):
                with c._lock:
                    series.append(dict(labels=list(lv),
                                       counts=list(c.counts), n=c.n,
                                       sum=c.total, min=c.vmin, max=c.vmax))
            fam["series"] = series
        elif isinstance(m, Gauge) and m._fn is not None:
            fam["series"] = [dict(labels=[], value=m.value())]
        else:
            fam["series"] = [dict(labels=list(lv), value=c.value())
                             for lv, c in sorted(m.children().items())]
        out[name] = fam
    return out


def prometheus_text() -> str:
    """The GET /3/Metrics body — Prometheus text exposition format 0.0.4.

    Families are emitted sorted by name, each with exactly one HELP/TYPE
    pair; label-less counters that never fired still expose a 0 sample so
    dashboards can alert on absence-of-traffic rather than absence-of-
    metric."""
    _run_collect_hooks()
    with _LOCK:
        metrics = dict(_METRICS)
    lines: List[str] = []
    for _name, m in sorted(metrics.items()):
        lines.extend(m.expo_lines())
    return "\n".join(lines) + "\n"
