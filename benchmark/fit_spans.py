"""The program's fit spans, as the profiler recorded them, to per-fit numbers.

`h2o3_tpu/runtime/tracing.py` `span()` holds a `jax.profiler.TraceAnnotation`
of the span's name open for its lifetime, so under the benchmark's profiler
session every span of a fit (`train`, `train.fit`, `fit.design`, ...; the
tree is in docs/observability.md) is an event on `/host:CPU`, on the clock of
the device planes. The profiler keeps no parent ids: a span's children are
the program spans of the same thread that lie inside its interval. Only
whole `train` spans inside the `bench.window` event are read, and every
reader divides by the `train` spans counted. Where the program opens no such
span (a parent commit from before them) every function here finds nothing
and the readers return None."""

from __future__ import annotations

import re

from reduce_trace import union_seconds

WINDOW = "bench.window"
# the names the program's span tree uses; whatever else is on the host plane
# (PjitFunction, transfers, the runtime's own events) is not a span of it
PROGRAM_SPAN = re.compile(r"^(train|fit|metrics|frame)(\.[A-Za-z0-9_]+)*$")


def window(trace):
    """(start, end) of the window event, or None."""
    found = [(s, s + d) for _, n, s, d in trace.host if n == WINDOW]
    return found[0] if found else None


def trains(trace) -> list:
    """(thread, start, end) of the whole `train` spans inside the window."""
    win = window(trace)
    if win is None:
        return []
    return sorted((t, s, s + d) for t, n, s, d in trace.host
                  if n == "train" and s >= win[0] and s + d <= win[1])


def inside(trace, name: str, parents: list) -> list:
    """(thread, start, end) of the spans called `name` that lie inside one of
    `parents` on its thread."""
    return sorted((t, s, s + d) for t, n, s, d in trace.host if n == name
                  and any(t == pt and s >= ps and s + d <= pe
                          for pt, ps, pe in parents))


def seconds(spans: list) -> float:
    return sum(e - s for _, s, e in spans)


def self_seconds(trace, spans: list) -> float:
    """Summed self time: each span's duration less the part of its interval
    that program spans inside it cover (choosing-metrics, section 4)."""
    program = [(t, s, d) for t, n, s, d in trace.host
               if PROGRAM_SPAN.match(n)]
    total = 0.0
    for pt, ps, pe in spans:
        kids = [(s, d) for t, s, d in program if t == pt and s >= ps
                and s + d <= pe and (s, s + d) != (ps, pe)]
        total += (pe - ps) - union_seconds(kids)
    return total


def per_fit_ms(trace, name: str):
    """Milliseconds a fit inside the spans called `name`, or None."""
    fits = trains(trace)
    found = inside(trace, name, fits)
    if not fits or not found:
        return None
    return 1e3 * seconds(found) / len(fits)
