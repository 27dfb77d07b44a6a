"""Ingest observability — per-parse phase timings + throughput counters.

The parse pipeline (frame/parse.py, frame/distributed_parse.py) records one
entry per completed parse: rows, bytes, wall seconds and the per-phase
split (setup / read / tokenize / coerce / intern / place — the stages of
`ParseDataset`'s progress reporting, `water/parser/ParseDataset.java`
Job progress units). Readers:

- `GET /3/Ingest/metrics` and the `ingest` section of `/3/Profiler`
  (via runtime/profiler.ingest_stats) serve `snapshot()`;
- `runtime/phases.py` receives the same marks under ``ingest_<stage>``
  keys, so its phase decomposition covers ingest next to
  h2d/compile/compute.

Phase bucketing: "coerce" books columns that resolve numeric/time (the
vectorized astype-with-NA-masking pass), "intern" books enum/string
columns (the categorical intern, and on the distributed path the phase-2
domain-union collectives too).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

_LOCK = threading.Lock()
_TOTALS = dict(parses=0, rows=0, bytes=0, secs=0.0)
_LAST: Dict = {}

PHASE_ORDER = ("setup", "read", "tokenize", "coerce", "intern", "place")


_REGISTRY = None


def _registry():
    """Central-registry counters backing the /3/Ingest/metrics totals (the
    scrape surface at GET /3/Metrics). Registered lazily (memoized — this
    runs per parse) and bound to the REST fields they back so the
    metrics-consistency test can hold the two surfaces together."""
    global _REGISTRY
    if _REGISTRY is not None:
        return _REGISTRY
    from ..runtime import metrics_registry as reg

    c = {
        "parses": reg.counter("h2o3_ingest_parses",
                              "completed CSV parses"),
        "rows": reg.counter("h2o3_ingest_rows", "rows ingested"),
        "bytes": reg.counter("h2o3_ingest_bytes", "bytes ingested"),
        "secs": reg.counter("h2o3_ingest_seconds",
                            "wall seconds spent parsing"),
    }
    for field, metric in (("totals.parses", "h2o3_ingest_parses"),
                          ("totals.rows", "h2o3_ingest_rows"),
                          ("totals.bytes", "h2o3_ingest_bytes"),
                          ("totals.secs", "h2o3_ingest_seconds")):
        reg.bind_rest_field("ingest", field, metric)
    _REGISTRY = c
    return c


@contextmanager
def stage(marks: Dict[str, float], name: str):
    """Accumulate wall-clock of one parse stage into `marks[name]`."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        marks[name] = marks.get(name, 0.0) + (time.perf_counter() - t0)


def record(path: str, rows: int, nbytes: int, secs: float,
           phases: Dict[str, float], n_chunks: int = 1, n_threads: int = 1,
           native: bool = False, distributed: bool = False,
           legacy: bool = False) -> None:
    """Book one finished parse into the cumulative totals + `last`, and
    forward the stage marks to runtime/phases as ``ingest_*``."""
    from ..runtime import phases as _phz

    for k, v in phases.items():
        _phz.add(f"ingest_{k}", v,
                 nbytes=nbytes if k == "tokenize" else 0)
    secs = max(secs, 1e-9)
    entry = dict(
        path=path, rows=int(rows), bytes=int(nbytes),
        secs=round(secs, 4),
        rows_per_s=round(rows / secs, 1),
        bytes_per_s=round(nbytes / secs, 1),
        n_chunks=int(n_chunks), n_threads=int(n_threads),
        native=bool(native), distributed=bool(distributed),
        phases={k: round(phases.get(k, 0.0), 4)
                for k in PHASE_ORDER if k in phases},
    )
    if legacy:
        entry["legacy"] = True
    with _LOCK:
        _TOTALS["parses"] += 1
        _TOTALS["rows"] += int(rows)
        _TOTALS["bytes"] += int(nbytes)
        _TOTALS["secs"] += secs
        _LAST.clear()
        _LAST.update(entry)
    # observability spine: monotone registry counters (GET /3/Metrics) +
    # a retroactive child span of whatever request/job ran this parse
    reg = _registry()
    reg["parses"].inc(1)
    reg["rows"].inc(int(rows))
    reg["bytes"].inc(int(nbytes))
    reg["secs"].inc(secs)
    from ..runtime import tracing as _tracing

    _tracing.record_span(f"ingest:{path}", secs, kind="ingest",
                         rows=int(rows), bytes=int(nbytes),
                         n_chunks=int(n_chunks), native=bool(native))


def snapshot() -> Dict:
    """Cumulative + last-parse counters (the /3/Ingest/metrics body)."""
    with _LOCK:
        totals = dict(_TOTALS)
        last: Optional[Dict] = dict(_LAST) if _LAST else None
    secs = max(totals["secs"], 1e-9)
    out = dict(
        totals=dict(
            parses=totals["parses"], rows=totals["rows"],
            bytes=totals["bytes"], secs=round(totals["secs"], 4),
            rows_per_s=round(totals["rows"] / secs, 1),
            bytes_per_s=round(totals["bytes"] / secs, 1),
        ),
        last=last,
    )
    return out


def reset() -> None:
    with _LOCK:
        _TOTALS.update(parses=0, rows=0, bytes=0, secs=0.0)
        _LAST.clear()
