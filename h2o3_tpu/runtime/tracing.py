"""Request/job tracing — the span engine of the observability spine.

Every REST request opens a ROOT span (trace id minted by the client and
propagated via the ``X-H2O3-Trace-Id`` header, or minted server-side when
absent); training Jobs, trainpool candidates, serving batch dispatches,
ingest parses and munge ops open CHILD spans; retry attempts and fired
fault injections annotate the owning span as zero-duration events. The
result is one correlated tree per user action instead of five disconnected
counter snapshots — ``GET /3/Trace`` exports any trace as Chrome-trace/
Perfetto JSON, and recent span summaries fold into ``GET /3/Timeline``.

Design:

- spans parent through a THREAD-LOCAL stack (`span()` nests naturally in
  one thread); crossing a thread boundary is explicit — the spawning side
  captures `current()` (or just the ids) and the worker re-attaches with
  ``attach(trace_id, parent_id)``. `Job` objects carry ``trace_id`` for
  the REST→worker hop, `_Pending` carries it for the batcher hop.
- finished spans land in one bounded ring (``H2O3_TRACE_SPANS``, default
  4096) — O(1) append under a single lock, oldest evicted first, so
  sustained traffic cannot grow the host (same stance as the Timeline
  ring). An UNSAMPLED fraction is not implemented: span volume here is
  per-request/per-op, not per-row.
- ops whose instrumentation already measures wall-clock (ingest/munge
  stats modules, the compile pipeline's listener in `runtime/phases.py`:
  ``xla.compile`` / ``xla.cache_load``) register retroactively via
  ``record_span`` instead of wrapping their hot paths twice; what is too
  frequent to be a span each (Vec rollups, jax's trace and lowering events)
  adds itself to an attr of the innermost open span with ``tally``.
- the ring evicts its oldest span when full, and counts it: ``dropped()``
  and ``h2o3_trace_spans_dropped_total``. A reader of the ring that finds
  0 knows that no span of the tree it reads was evicted; an operator who
  finds it rising knows ``H2O3_TRACE_SPANS`` is too small for the traffic.
- ONE primitive, two sinks: `span()` also holds a
  ``jax.profiler.TraceAnnotation(name)`` open for its lifetime. With no
  profiler session that is a flag test; under one (`profiler.trace()`, the
  benchmark's ``--trace 1``) the span is an event on ``/host:CPU`` on the
  SAME clock as the device planes, so a device-idle gap is named by the
  innermost span that covers it with no offset arithmetic. Readers match
  names exactly: a span NAME is a static string, what varies (ids, algo,
  rows) goes into ``attrs``. The fit's span tree (`train`, `train.fit`,
  `fit.design`, `fit.iterate`, ...) is listed in docs/observability.md.

Metric fold: ``h2o3_trace_spans_total{kind}`` counts completed spans per
kind in the central registry, so span volume itself is scrapable;
``h2o3_trace_spans_dropped_total`` counts the spans the ring evicted.
"""

from __future__ import annotations

import os
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from . import env_int

__all__ = ["Span", "span", "attach", "current", "current_trace_id",
           "new_trace_id", "event", "tally", "record_span", "export_chrome",
           "summaries", "clear", "span_count", "dropped"]

_MAX_SPANS = env_int("H2O3_TRACE_SPANS", 4096)
_MAX_EVENTS_PER_SPAN = 64

_LOCK = threading.Lock()
_SPANS: deque = deque(maxlen=_MAX_SPANS)
_DROPPED = 0          # spans the full ring evicted since the last clear()
_TLS = threading.local()


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def _new_span_id() -> str:
    return uuid.uuid4().hex[:8]


class Span:
    """One timed operation. Mutable while open; immutable once recorded."""

    __slots__ = ("name", "kind", "trace_id", "span_id", "parent_id",
                 "t_wall", "t0", "duration_s", "attrs", "events", "thread")

    def __init__(self, name: str, kind: str = "span",
                 trace_id: Optional[str] = None,
                 parent_id: Optional[str] = None,
                 attrs: Optional[Dict] = None):
        self.name = name
        self.kind = kind
        self.trace_id = trace_id or new_trace_id()
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        self.t_wall = time.time()
        self.t0 = time.perf_counter()
        self.duration_s: Optional[float] = None
        self.attrs: Dict = dict(attrs or {})
        self.events: List[Dict] = []
        self.thread = threading.current_thread().name

    def annotate(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def add_event(self, name: str, **attrs) -> None:
        if len(self.events) < _MAX_EVENTS_PER_SPAN:
            ev = dict(name=name, ts=time.time())
            if attrs:
                ev.update(attrs)
            self.events.append(ev)

    def to_dict(self) -> Dict:
        return dict(name=self.name, kind=self.kind, trace_id=self.trace_id,
                    span_id=self.span_id, parent_id=self.parent_id,
                    ts=self.t_wall,
                    duration_s=(round(self.duration_s, 6)
                                if self.duration_s is not None else None),
                    thread=self.thread, attrs=dict(self.attrs),
                    events=list(self.events))


def _stack() -> List[Span]:
    s = getattr(_TLS, "stack", None)
    if s is None:
        s = _TLS.stack = []
    return s


def current() -> Optional[Span]:
    """The innermost open span on this thread, or None."""
    s = getattr(_TLS, "stack", None)
    return s[-1] if s else None


def current_trace_id() -> Optional[str]:
    sp = current()
    return sp.trace_id if sp is not None else None


_COUNTERS = None


def _record(sp: Span) -> None:
    global _COUNTERS, _DROPPED
    with _LOCK:
        evicts = len(_SPANS) == _SPANS.maxlen
        if evicts:
            _DROPPED += 1
        _SPANS.append(sp)
    # registry fold; the families are memoized so ending a span never takes
    # the registry's registration lock (deferred first resolve: tracing
    # must stay importable before metrics_registry)
    c = _COUNTERS
    if c is None:
        from . import metrics_registry as _reg

        c = _COUNTERS = (
            _reg.counter("h2o3_trace_spans", "completed trace spans",
                         labelnames=("kind",)),
            _reg.counter("h2o3_trace_spans_dropped",
                         "spans the full ring (H2O3_TRACE_SPANS) evicted"))
    c[0].inc(1, sp.kind)
    if evicts:
        c[1].inc()


@contextmanager
def span(name: str, kind: str = "span", trace_id: Optional[str] = None,
         parent_id: Optional[str] = None, **attrs):
    """Open a span as a child of this thread's current span (or of the
    explicit trace_id/parent_id for cross-thread hops); record it on exit.
    Exceptions mark the span ``error`` and propagate. For its lifetime the
    span is also a `TraceAnnotation` of the same name: an event on the
    profiler's host plane whenever a profiler session is running."""
    cur = current()
    if trace_id is None and cur is not None:
        trace_id = cur.trace_id
        if parent_id is None:
            parent_id = cur.span_id
    sp = Span(name, kind=kind, trace_id=trace_id, parent_id=parent_id,
              attrs=attrs)
    st = _stack()
    st.append(sp)
    try:
        with TraceAnnotation(name):
            yield sp
    except BaseException as e:
        sp.attrs["error"] = f"{type(e).__name__}: {e}"
        raise
    finally:
        st.pop()
        sp.duration_s = time.perf_counter() - sp.t0
        _record(sp)


@contextmanager
def attach(trace_id: Optional[str], parent_id: Optional[str] = None,
           name: str = "attached", kind: str = "span", **attrs):
    """Worker-thread re-entry point: continue `trace_id` on this thread.
    No-op passthrough (no span recorded) when trace_id is falsy — callers
    wrap unconditionally and un-traced work stays un-traced."""
    if not trace_id:
        yield None
        return
    with span(name, kind=kind, trace_id=trace_id,
              parent_id=parent_id, **attrs) as sp:
        yield sp


def event(name: str, **attrs) -> None:
    """Annotate the current span with a zero-duration event (retry
    attempts, fired fault injections). Silently dropped when no span is
    open — hardening paths run identically traced or not."""
    sp = current()
    if sp is not None:
        sp.add_event(name, **attrs)


def tally(name: str, value=1) -> None:
    """Add `value` to attr `name` of the current span (created at 0): for
    what happens too often inside a span to be a span or an event each —
    a Vec's rollup reads, the seconds jax spends tracing and lowering.
    Does nothing when no span is open."""
    sp = current()
    if sp is not None:
        sp.attrs[name] = sp.attrs.get(name, 0) + value


def record_span(name: str, duration_s: float, kind: str = "span",
                trace_id: Optional[str] = None,
                parent_id: Optional[str] = None,
                t_wall: Optional[float] = None, **attrs) -> Span:
    """Retroactively record an already-measured operation (ingest parses,
    munge ops — their stats modules time the work themselves). Parents to
    the current span when no explicit ids are given."""
    cur = current()
    if trace_id is None and cur is not None:
        trace_id = cur.trace_id
        if parent_id is None:
            parent_id = cur.span_id
    sp = Span(name, kind=kind, trace_id=trace_id, parent_id=parent_id,
              attrs=attrs)
    sp.duration_s = float(duration_s)
    if t_wall is not None:
        sp.t_wall = float(t_wall)
    else:
        sp.t_wall = time.time() - sp.duration_s
    _record(sp)
    return sp


# -- read side ----------------------------------------------------------------

def _snapshot_spans() -> List[Span]:
    with _LOCK:
        return list(_SPANS)


def span_count() -> int:
    with _LOCK:
        return len(_SPANS)


def dropped() -> int:
    """Spans the full ring has evicted since the last `clear()`: 0 says
    every span recorded since then is still in `spans()`."""
    with _LOCK:
        return _DROPPED


def spans(trace_id: Optional[str] = None, n: Optional[int] = None
          ) -> List[Dict]:
    """Recorded spans (oldest first), optionally filtered to one trace."""
    out = [s for s in _snapshot_spans()
           if trace_id is None or s.trace_id == trace_id]
    if n is not None:
        out = out[-n:]
    return [s.to_dict() for s in out]


def summaries(n: int = 50) -> List[Dict]:
    """Compact recent-span lines for the /3/Timeline fold."""
    out = []
    for s in _snapshot_spans()[-n:]:
        d = dict(ts=round(s.t_wall, 3), name=s.name, kind=s.kind,
                 trace_id=s.trace_id,
                 duration_ms=(round(s.duration_s * 1e3, 3)
                              if s.duration_s is not None else None))
        if "error" in s.attrs:
            d["error"] = s.attrs["error"]
        out.append(d)
    return out


def export_chrome(trace_id: Optional[str] = None) -> Dict:
    """Chrome-trace (Perfetto-loadable) JSON object: one complete ("X")
    event per span with trace/span ids in args, one instant ("i") event
    per span annotation. Load at ui.perfetto.dev or chrome://tracing."""
    pid = os.getpid()
    events: List[Dict] = []
    tids: Dict[str, int] = {}
    for s in _snapshot_spans():
        if trace_id is not None and s.trace_id != trace_id:
            continue
        tid = tids.setdefault(s.thread, len(tids) + 1)
        ts_us = s.t_wall * 1e6
        args = dict(trace_id=s.trace_id, span_id=s.span_id,
                    parent_id=s.parent_id, **s.attrs)
        events.append(dict(
            name=s.name, cat=s.kind, ph="X", ts=ts_us,
            dur=max((s.duration_s or 0.0) * 1e6, 1.0),
            pid=pid, tid=tid, args=args))
        for ev in s.events:
            events.append(dict(
                name=ev["name"], cat=s.kind, ph="i", s="t",
                ts=ev.get("ts", s.t_wall) * 1e6, pid=pid, tid=tid,
                args={k: v for k, v in ev.items()
                      if k not in ("name", "ts")}))
    meta = [dict(name="thread_name", ph="M", pid=pid, tid=tid,
                 args=dict(name=thread))
            for thread, tid in tids.items()]
    return dict(traceEvents=meta + events, displayTimeUnit="ms",
                otherData=dict(source="h2o3_tpu", trace_id=trace_id))


def clear() -> None:
    """Drop recorded spans (tests). Open spans on live threads are
    unaffected — they record on exit as usual."""
    global _DROPPED
    with _LOCK:
        _SPANS.clear()
        _DROPPED = 0
