"""Munge observability — per-op stage timings + throughput counters.

The vectorized munging engine (frame/rapids.py merge/group-by/pivot/table,
frame/frame.py apply-over-rows, the rapids_expr time/string prims) records
one entry per completed op: input/output rows, wall seconds and the
per-stage split (e.g. merge's factorize / combine / match / assemble — the
stages of `AstMerge`'s radix join, `water/rapids/ast/prims/mungers/
AstMerge.java`). Readers:

- `GET /3/Munge/metrics` and the `munge` section of `/3/Profiler`
  (via runtime/profiler.munge_stats) serve `snapshot()`;
- `runtime/phases.py` receives the same marks under ``munge_<op>`` keys,
  so its phase decomposition covers munging next to ingest and
  h2d/compile/compute.

`path` tags how the op executed: "vectorized" (the columnar kernels),
"fallback" (a vectorized attempt that dropped to the exact per-row loop —
e.g. a row callable that doesn't vectorize), or "legacy" (the seed path,
forced by ``H2O3_MUNGE_LEGACY=1``).
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

_LOCK = threading.Lock()
_TOTALS = dict(ops=0, rows_in=0, rows_out=0, secs=0.0)
_PER_OP: Dict[str, Dict] = {}
_LAST: Dict = {}


_REGISTRY = None


def _registry():
    """Central-registry counters backing the /3/Munge/metrics totals
    (scraped at GET /3/Metrics; per-op detail labeled by op/path).
    Memoized — this runs on every munge op."""
    global _REGISTRY
    if _REGISTRY is not None:
        return _REGISTRY
    from ..runtime import metrics_registry as reg

    c = {
        "ops": reg.counter("h2o3_munge_ops", "completed munge ops",
                           labelnames=("op", "path")),
        "errors": reg.counter("h2o3_munge_errors", "munge ops that raised",
                              labelnames=("op",)),
        "rows_in": reg.counter("h2o3_munge_rows_in", "input rows munged"),
        "rows_out": reg.counter("h2o3_munge_rows_out",
                                "output rows produced"),
        "secs": reg.counter("h2o3_munge_seconds",
                            "wall seconds spent in munge ops"),
    }
    for field, metric in (("totals.ops", "h2o3_munge_ops"),
                          ("totals.rows_in", "h2o3_munge_rows_in"),
                          ("totals.rows_out", "h2o3_munge_rows_out"),
                          ("totals.secs", "h2o3_munge_seconds")):
        reg.bind_rest_field("munge", field, metric)
    _REGISTRY = c
    return c


def legacy_enabled() -> bool:
    """True when ``H2O3_MUNGE_LEGACY=1`` forces the seed per-row paths
    (the bit-exact comparator the parity tests diff against)."""
    return os.environ.get("H2O3_MUNGE_LEGACY", "").lower() in (
        "1", "true", "yes")


@contextmanager
def stage(marks: Dict[str, float], name: str):
    """Accumulate wall-clock of one munge stage into `marks[name]`."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        marks[name] = marks.get(name, 0.0) + (time.perf_counter() - t0)


def record(op: str, rows_in: int, rows_out: int, secs: float,
           stages: Optional[Dict[str, float]] = None,
           path: str = "vectorized", error: bool = False) -> None:
    """Book one finished munge op into the cumulative totals + per-op
    counters + `last`, and forward the wall-clock to runtime/phases as
    ``munge_<op>``. Ops that RAISED book with ``error=True`` and
    rows_out=0 — a failed call must not fabricate throughput."""
    from ..runtime import phases as _phz

    _phz.add(f"munge_{op}", secs)
    secs = max(secs, 1e-9)
    entry = dict(
        op=op, rows_in=int(rows_in), rows_out=int(rows_out),
        secs=round(secs, 6),
        rows_per_s=round(rows_in / secs, 1),
        path=path,
        stages={k: round(v, 6) for k, v in (stages or {}).items()},
    )
    if error:
        entry["error"] = True
    reg = _registry()
    reg["ops"].inc(1, op, path)
    if error:
        reg["errors"].inc(1, op)
    reg["rows_in"].inc(int(rows_in))
    reg["rows_out"].inc(int(rows_out))
    reg["secs"].inc(secs)
    from ..runtime import tracing as _tracing

    _tracing.record_span(f"munge:{op}", secs, kind="munge",
                         rows_in=int(rows_in), rows_out=int(rows_out),
                         path=path, **(dict(error=True) if error else {}))
    with _LOCK:
        _TOTALS["ops"] += 1
        _TOTALS["rows_in"] += int(rows_in)
        _TOTALS["rows_out"] += int(rows_out)
        _TOTALS["secs"] += secs
        po = _PER_OP.setdefault(op, dict(calls=0, errors=0, rows_in=0,
                                         rows_out=0, secs=0.0, paths={}))
        po["calls"] += 1
        if error:
            po["errors"] += 1
        po["rows_in"] += int(rows_in)
        po["rows_out"] += int(rows_out)
        po["secs"] += secs
        po["paths"][path] = po["paths"].get(path, 0) + 1
        _LAST.clear()
        _LAST.update(entry)


@contextmanager
def op(name: str, rows_in: int, stages: Optional[Dict[str, float]] = None,
       path: str = "vectorized"):
    """Time one munge op; the caller sets ``out['rows_out']`` (defaults to
    rows_in) and may retag ``out['path']`` before the block exits. An op
    that raises books rows_out=0 with ``error=True``."""
    out = dict(rows_out=rows_in, path=path)
    t0 = time.perf_counter()
    try:
        yield out
    except BaseException:
        record(name, rows_in, 0, time.perf_counter() - t0, stages=stages,
               path=out.get("path", path), error=True)
        raise
    record(name, rows_in, out.get("rows_out", rows_in),
           time.perf_counter() - t0, stages=stages,
           path=out.get("path", path))


def snapshot() -> Dict:
    """Cumulative + per-op + last-op counters (the /3/Munge/metrics body)."""
    with _LOCK:
        totals = dict(_TOTALS)
        per_op = {k: dict(v, paths=dict(v["paths"]))
                  for k, v in _PER_OP.items()}
        last: Optional[Dict] = dict(_LAST) if _LAST else None
    secs = max(totals["secs"], 1e-9)
    for v in per_op.values():
        v["secs"] = round(v["secs"], 6)
        v["rows_per_s"] = round(v["rows_in"] / max(v["secs"], 1e-9), 1)
    return dict(
        totals=dict(
            ops=totals["ops"], rows_in=totals["rows_in"],
            rows_out=totals["rows_out"], secs=round(totals["secs"], 6),
            rows_per_s=round(totals["rows_in"] / secs, 1),
        ),
        ops=per_op,
        last=last,
    )


def reset() -> None:
    with _LOCK:
        _TOTALS.update(ops=0, rows_in=0, rows_out=0, secs=0.0)
        _PER_OP.clear()
        _LAST.clear()
