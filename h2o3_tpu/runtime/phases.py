"""Per-phase wall-clock and byte accounting.

Decomposes a training run's wall-clock into the phases that matter on an
accelerator — {h2d_s, compile_s, deserialize_s,
trace_s, compute_s (residual), bytes_h2d} — so "fast" is auditable per
phase instead of one conflated number (the reference logs per-stage Timer
lines, `water/util/Timer` + `water/H2O` timeline; here the decomposition
feeds `/3/Training/metrics` and the per-candidate sinks).

Two sources:
- jax monitoring events (always cheap): `backend_compile_duration` →
  compile, `jaxpr_trace/`mlir_module` → trace, persistent-cache
  retrievals → deserialize. The same listener puts them on the span tree
  (runtime/tracing.py): a compile request that ended is one span,
  ``xla.compile`` or ``xla.cache_load``, under whatever span the requesting
  thread holds open; trace and lowering seconds tally on that span as the
  attr ``xla_trace_s``.
- explicit instrumentation at the few fat host→device transfer points
  (`accounted_h2d`), which records their bytes. device_put is async and a
  production run overlaps its transfers, so no transfer is timed here.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict, defaultdict, deque
from contextlib import contextmanager

from . import tracing

_LOCK = threading.Lock()
_SECS: dict = defaultdict(float)
_BYTES: dict = defaultdict(int)
_installed = False

# -- XLA compile/retrace tracker ---------------------------------------------
# Counts compiles / traces / persistent-cache retrievals per program
# signature, so "warm cache never re-traces" is a pinned counter instead of
# a monkeypatch test, and the fused-GBM work can prove per-level retrace
# count == 0. A "retrace" is any trace event for a signature that has
# already traced at least once this process.
_XLA_LOCK = threading.Lock()
_XLA_TOTALS = dict(compiles=0, traces=0, retraces=0, cache_retrievals=0,
                   persistent_cache_hits=0, persistent_cache_misses=0)
_XLA_PER_SIG: "OrderedDict[str, dict]" = OrderedDict()
_XLA_SIG_CAP = 512
# the program whose compile request the current thread made last (see
# _CompileTap): the compile / cache-retrieval duration events that follow
# on the same thread belong to it. `loaded` is set between a request's
# cache-retrieval event and its closing backend_compile event
_PROG_TLS = threading.local()
_COMPILER_LOGGER = "jax._src.compiler"
_CACHE_MSGS = ("Persistent compilation cache hit for",
               "PERSISTENT COMPILATION CACHE MISS for")


class _CompileTap(logging.Filter):
    """Program identity from what the installed jax (0.9) offers.

    The monitoring listeners get only `fun_name`: two shape buckets, or
    two static configurations, of one function would be one signature and
    the second a fabricated retrace. True identity is the key jax computes
    for its persistent compilation cache — a digest of the lowered module,
    the compile options and the devices — and jax offers it in one place:
    the hit/miss record `compile_or_get_cached` logs for every compile
    request, whose ARGS are (module name, cache key). This filter sits on
    that logger, reads those args, and keeps the logger's output as it
    was: records below the level the logger had before the tap are dropped
    here, everything else passes untouched.

    A program reaches the compile step exactly when jax's tracing cache
    missed (lowering and compilation are cached on the traced jaxpr), so
    "the same key requested twice in this process" is "the identical
    program was traced again" — the retrace this tracker pins, whichever
    span is open and whether the cause was a dropped cache or a fresh
    closure jitted per call. Inner jits traced as part of an outer program
    never compile on their own: they count in the `traces` total only.
    Two traces that differ only in what lowering erases (weak types) are
    one module and count as one program — it was compiled twice.
    With the compilation cache disabled jax computes no key; events then
    fall to their `fun_name` and no retrace is counted (missing a real
    retrace beats fabricating one into a pinned counter)."""

    def __init__(self, passthrough_level: int):
        super().__init__()
        self.passthrough_level = passthrough_level

    def filter(self, record) -> bool:
        msg = record.msg
        if (isinstance(msg, str) and msg.startswith(_CACHE_MSGS)
                and isinstance(record.args, tuple) and len(record.args) == 2):
            sig = str(record.args[1])
            _PROG_TLS.name, _PROG_TLS.sig = str(record.args[0]), sig
            _xla_count("traces", sig, total=False)
        return record.levelno >= self.passthrough_level


def _event_signature(fun_name) -> str:
    """Signature for a compile / cache-retrieval duration event: the
    program this thread just requested when the event names it (or names
    nothing — cache retrievals carry no name), else the bare `fun_name`
    jax passed, else one shared bucket that never counts retraces."""
    sig = getattr(_PROG_TLS, "sig", None)
    if sig is not None and (not fun_name
                            or _PROG_TLS.name == str(fun_name)):
        return sig
    return str(fun_name) if fun_name else "unattributed"


_XLA_REG: dict = {}


def _xla_counters() -> dict:
    """Memoized registry families for the XLA event counters — counting a
    compile-pipeline event must not take the registry's registration lock
    (same stance as every other subsystem's memoized _registry())."""
    if not _XLA_REG:
        from . import metrics_registry as _reg

        for kind in ("compiles", "traces", "cache_retrievals"):
            _XLA_REG[kind] = _reg.counter(
                f"h2o3_xla_{kind}",
                f"XLA compile-pipeline {kind} observed via jax monitoring")
        _XLA_REG["retraces"] = _reg.counter(
            "h2o3_xla_retraces",
            "trace events for an already-traced program signature "
            "(a warm path must keep this flat)")
    return _XLA_REG


def _xla_count(kind: str, sig: "str | None", total: bool = True) -> None:
    """Count one compile-pipeline event: in the process totals (`total`),
    and under its program signature when one is known (`sig`). A `traces`
    count for a signature that already has one is a retrace."""
    retraced = False
    with _XLA_LOCK:
        if total:
            _XLA_TOTALS[kind] += 1
        d = None
        if sig is not None:
            d = _XLA_PER_SIG.get(sig)
            if d is None:
                d = _XLA_PER_SIG[sig] = dict(compiles=0, traces=0,
                                             retraces=0, cache_retrievals=0)
                while len(_XLA_PER_SIG) > _XLA_SIG_CAP:
                    _XLA_PER_SIG.popitem(last=False)
        if d is not None and kind in d:
            if (kind == "traces" and d["traces"] >= 1
                    and sig != "unattributed"):
                retraced = True
                d["retraces"] += 1
                _XLA_TOTALS["retraces"] += 1
            d[kind] += 1
    reg = _xla_counters()
    if total:
        reg[kind].inc()
    if retraced:
        reg["retraces"].inc()
        # candidate/batch/request correlation lives on the span as an event
        # annotation, NOT in the signature — span names must not leak into
        # program identity
        tracing.event("xla_retrace", sig=sig)


def _tally_trace(duration: float) -> None:
    """A trace or lowering event just ended on this thread: tally on the
    open span, as ``xla_trace_s``, the seconds of it that no earlier event
    has already reported. jax times a jit's trace with the traces of the
    jits inside it, each of which ended, and reported itself, first; so the
    events since this one began are its inner ones, and what is left of it
    after theirs is its own. Summed, the tallies never exceed the thread's
    wall-clock (the `trace` bucket, which adds whole durations, does)."""
    seen = getattr(_PROG_TLS, "traces", None)
    if seen is None:
        # (start, duration) of recent events; bounded: an entry matters
        # only until the trace that encloses it ends
        seen = _PROG_TLS.traces = deque(maxlen=256)
    start = time.time() - duration      # jax times them on this clock
    inner = 0.0
    while seen and seen[-1][0] >= start:
        inner += seen.pop()[1]
    seen.append((start, duration))
    tracing.tally("xla_trace_s", max(duration - inner, 0.0))


def _request_span(duration: float, fun_name) -> None:
    """One compile request of this thread has ended: record it as a span
    under the span the thread holds open (the first call of a program:
    ``iterate.dispatch``, ``design.upload``, ``fit.metrics``, ...).

    jax times the WHOLE request as `backend_compile_duration`, whether the
    persistent cache answered it or the compiler did; a cache hit announces
    itself just before, with a retrieval event on the same thread. So a
    request is ``xla.cache_load`` (key, read, deserialize) when one came,
    ``xla.compile`` (key, miss, compile, cache write) when none did — one
    span a request, never both. `program` is the module's name and `sig`
    its cache key as the `_CompileTap` read them for this request; with the
    compilation cache off jax logs neither, and `program` is what the event
    itself carries."""
    name = getattr(_PROG_TLS, "name", None)
    sig = getattr(_PROG_TLS, "sig", None)
    loaded = getattr(_PROG_TLS, "loaded", False)
    _PROG_TLS.name = _PROG_TLS.sig = None
    _PROG_TLS.loaded = False
    tracing.record_span("xla.cache_load" if loaded else "xla.compile",
                        duration, kind="xla",
                        program=name if name is not None else str(fun_name),
                        sig=sig)


def xla_counts() -> dict:
    """Cumulative compile/trace/retrace/cache totals (the benchmark's
    compile counts + the warm-path counter pins)."""
    with _XLA_LOCK:
        return dict(_XLA_TOTALS)


def xla_snapshot() -> dict:
    """Totals + per-program-signature breakdown (most recent signatures
    first, bounded)."""
    with _XLA_LOCK:
        sigs = {k: dict(v) for k, v in reversed(_XLA_PER_SIG.items())}
        return dict(totals=dict(_XLA_TOTALS), signatures=sigs)

# per-candidate attribution: a training worker (runtime/trainpool.py)
# installs a thread-local sink around one candidate's fit, and every add()
# from that thread (driver phase marks AND the jax monitoring listener,
# which fires in the dispatching thread) is mirrored into it — so
# /3/Training/metrics can report per-candidate h2d/compile/host_prep even
# when several candidates train concurrently.
_TLS = threading.local()


@contextmanager
def candidate_sink():
    """Install a thread-local phase sink; yields {'secs': {}, 'bytes': {}}."""
    d = {"secs": {}, "bytes": {}}
    prev = getattr(_TLS, "sink", None)
    _TLS.sink = d
    try:
        yield d
    finally:
        _TLS.sink = prev

# phases the jax monitoring listener owns; a training-driver phase mark
# subtracts their concurrent growth so compilation isn't booked as compute
COMPILE_KEYS = ("compile", "trace", "deserialize")


def add(phase: str, secs: float = 0.0, nbytes: int = 0) -> None:
    with _LOCK:
        _SECS[phase] += secs
        if nbytes:
            _BYTES[phase] += nbytes
    sink = getattr(_TLS, "sink", None)
    if sink is not None:   # thread-local — no lock needed
        sink["secs"][phase] = sink["secs"].get(phase, 0.0) + secs
        if nbytes:
            sink["bytes"][phase] = sink["bytes"].get(phase, 0) + nbytes


def reset() -> None:
    with _LOCK:
        _SECS.clear()
        _BYTES.clear()


def totals(keys) -> float:
    """Sum of accumulated seconds over the given phase keys."""
    with _LOCK:
        return sum(_SECS.get(k, 0.0) for k in keys)


def snapshot() -> dict:
    """Accumulated seconds per phase + bytes for transfer phases."""
    with _LOCK:
        out = {f"{k}_s": round(v, 3) for k, v in _SECS.items()}
        out.update({f"bytes_{k}": v for k, v in _BYTES.items()})
        return out


@contextmanager
def timed(phase: str, nbytes: int = 0):
    t0 = time.perf_counter()
    try:
        yield
    finally:
        add(phase, time.perf_counter() - t0, nbytes)


def accounted_h2d(thunk, nbytes: int):
    """Run `thunk()` (a host→device transfer, possibly fused with a small
    on-device expand program) untouched and book its `nbytes` to ``h2d``."""
    add("h2d", 0.0, nbytes)
    return thunk()


def add_mark(name: str, secs: float) -> None:
    """Fold a training-driver phase boundary (shared_tree._Phase.mark) into
    the canonical phase buckets."""
    if name == "device_put":
        phase = "h2d"
    elif name.endswith("_D2H"):
        phase = "d2h"
    elif name.startswith("chunk_") or name in ("train_loop_dispatch",
                                               "forest_devkeep"):
        phase = "compute"
    elif name in ("frame_to_matrix", "build_bins", "forest_unpack"):
        phase = "host_prep"
    elif name == "training_metrics":
        phase = "metrics"
    else:
        phase = "other"
    add(phase, secs)


def install_listener() -> None:
    """Register the jax monitoring listener (idempotent).

    Maps compile-pipeline event durations onto phases: backend compilation,
    host-side trace/lowering, and persistent-cache executable retrieval
    (the 'deserialize' cost of a cache-warm run).
    """
    global _installed
    with _LOCK:
        if _installed:
            return
        _installed = True
    from jax import monitoring

    lg = logging.getLogger(_COMPILER_LOGGER)
    lg.addFilter(_CompileTap(lg.getEffectiveLevel()))
    lg.setLevel(logging.DEBUG)

    def _on(event: str, duration: float, fun_name=None, **kw) -> None:
        if "backend_compile" in event:
            add("compile", duration)
            _xla_count("compiles", _event_signature(fun_name))
            _request_span(duration, fun_name)
        elif "jaxpr_trace" in event:
            # the total counts every trace jax ran; the per-program count
            # (and the retrace pin) is taken at the compile request, where
            # the program's identity is known — see _CompileTap. Every
            # inner jit of a first fit traces: too many to be spans, so
            # their seconds tally on the open span
            add("trace", duration)
            _xla_count("traces", None)
            _tally_trace(duration)
        elif "mlir_module" in event:
            add("trace", duration)
            _tally_trace(duration)
        elif "cache_retrieval" in event or "deserialize" in event:
            add("deserialize", duration)
            _xla_count("cache_retrievals", _event_signature(fun_name))
            _PROG_TLS.loaded = True

    def _on_event(event: str, **kw) -> None:
        # persistent compilation-cache hit/miss counts (no duration)
        if "compilation_cache/cache_hits" in event:
            with _XLA_LOCK:
                _XLA_TOTALS["persistent_cache_hits"] += 1
        elif "compilation_cache/cache_misses" in event:
            with _XLA_LOCK:
                _XLA_TOTALS["persistent_cache_misses"] += 1

    monitoring.register_event_duration_secs_listener(_on)
    monitoring.register_event_listener(_on_event)
