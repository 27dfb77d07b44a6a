"""Profiler — stack sampling + XLA trace capture.

Reference parity: `/3/Profiler` (`water/api/ProfilerHandler.java` +
`water/util/JProfile.java`) collects stack-trace samples from every node —
here `stack_samples()` snapshots all Python threads of this process (one
process per TPU host). `trace()` wraps `jax.profiler` (perfetto/tensorboard
capture, with the program's spans on the same clock) — strictly stronger
than the reference's sampler for device time.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import traceback
from collections import Counter
from typing import Dict, List


def stack_samples(depth: int = 20) -> List[Dict]:
    """One stack snapshot per live thread (the JProfile node sample)."""
    out = []
    names = {t.ident: t.name for t in threading.enumerate()}
    for tid, frame in sys._current_frames().items():
        stack = traceback.format_stack(frame)[-depth:]
        out.append(dict(thread=names.get(tid, str(tid)), stack=stack))
    return out


def profile(nsamples: int = 10, interval: float = 0.02, depth: int = 10) -> List[Dict]:
    """Repeated sampling aggregated by stack — the /3/Profiler table."""
    import time

    counts: Counter = Counter()
    for _ in range(nsamples):
        for s in stack_samples(depth):
            counts["".join(s["stack"])] += 1
        time.sleep(interval)
    return [dict(stack=k, count=v) for k, v in counts.most_common()]


def serving_stats() -> Dict:
    """Serving-subsystem observability folded into the profiler surface:
    `/3/Profiler` reports host stacks AND the scoring path's counters/
    latency histograms in one document. Never instantiates the serving
    engine — a profiler read on a training-only cluster reports absence."""
    from ..serving import peek_engine

    eng = peek_engine()
    if eng is None:
        return dict(active=False)
    out = eng.snapshot()
    out["active"] = True
    return out


def ingest_stats() -> Dict:
    """Ingest-pipeline observability folded into the profiler surface
    (mirrors `serving_stats`): cumulative + last-parse rows/s, bytes/s and
    the per-phase split (setup/read/tokenize/coerce/intern/place) recorded
    by frame/ingest_stats. Pure counter read — never triggers a parse."""
    from ..frame import ingest_stats as stats

    out = stats.snapshot()
    out["active"] = out["totals"]["parses"] > 0
    return out


def munge_stats() -> Dict:
    """Munging-engine observability folded into the profiler surface
    (mirrors `ingest_stats`): cumulative + per-op + last-op rows/s and the
    per-stage split (e.g. merge's factorize/combine/match/assemble)
    recorded by frame/munge_stats. Pure counter read — never runs an op."""
    from ..frame import munge_stats as stats

    out = stats.snapshot()
    out["active"] = out["totals"]["ops"] > 0
    return out


def training_stats() -> Dict:
    """Multi-model training observability folded into the profiler surface
    (mirrors `serving_stats`): train-pool occupancy, per-candidate phase
    splits, CV fold reuse counters and the dataset-artifact cache. Pure
    counter read — never trains anything."""
    from ..models import dataset_cache
    from . import trainpool

    out = trainpool.snapshot()
    out["cache"] = dataset_cache.snapshot()
    return out


def fault_stats() -> Dict:
    """Hardening observability folded into the profiler surface: armed
    fault-injection points + fire counts (runtime/faults) and the shared
    retry-policy counters (runtime/retry). Pure counter read."""
    from . import faults, retry

    out = dict(faults=faults.snapshot(), retry=retry.snapshot())
    out["active"] = bool(out["faults"]["active"]
                         or out["retry"]["totals"]["calls"])
    return out


def tree_stats() -> Dict:
    """Tree-kernel observability folded into the profiler surface
    (ISSUE 7 satellite): the per-fit histogram kernel plans recorded by
    `ops.histogram.record_fit_plan` (method, pallas row_chunk and padded
    bin width, pack bits, VMEM-pressure fallbacks per level) plus the
    cumulative dispatch
    counters — `build_histograms`' auto-dispatch made visible. Pure
    counter read — never builds a histogram."""
    from ..ops import histogram

    out = histogram.kernel_stats()
    out["active"] = bool(out["plans"]) or bool(out["dispatch"])
    return out


def est_stats() -> Dict:
    """Estimator-engine observability folded into the profiler surface
    (ISSUE 15): the per-fit plans recorded by
    `models.estimator_engine.record_fit` (algo, fused/legacy path,
    on-device iterations, converged flag, standardized-matrix cache
    hit/miss, shard count) plus the cumulative dispatch/iteration
    counters. Pure counter read — never fits anything."""
    from ..models import estimator_engine

    out = estimator_engine.est_stats()
    out["active"] = bool(out["plans"]) or bool(out["dispatch"])
    return out


def xla_stats() -> Dict:
    """XLA compile/trace/retrace counters folded into the profiler surface
    (runtime/phases tracker): totals + per-program-signature breakdown.
    Pure counter read."""
    from . import phases

    out = phases.xla_snapshot()
    out["active"] = any(out["totals"].values())
    return out


def memory_stats() -> Dict:
    """Memory-ledger fold (ISSUE 8): per-owner host/device bytes, by-kind
    totals, watermarks, pressure vs budget, leak report, and the device
    probe reconciliation — the same document GET /3/Memory serves, but
    from the rate-limited cached pass (force=False): a dashboard polling
    /3/Profiler never pays more than one accounting walk per
    H2O3_MEM_REFRESH_S interval."""
    from . import memory_ledger

    out = memory_ledger.snapshot(force=False)
    out["active"] = out["totals"]["owner_count"] > 0
    return out


def fleet_stats() -> Dict:
    """Fleet-aggregation fold (ISSUE 13): registered peers + last scrape
    status + scrape counters. `scrape=False` — a profiler read must never
    block on peer HTTP round-trips; GET /3/Fleet is the probing surface."""
    from . import fleet

    out = fleet.snapshot(scrape=False)
    out["active"] = bool(out["totals"]["peers"])
    return out


def router_stats() -> Dict:
    """Serving-fleet-router fold (ISSUE 16): ring + version table + shed/
    rollback counters. Peeks — a profiler read must never instantiate a
    routing layer (or fan out to replicas) just to report there isn't
    one; `probe=False` keeps it scrape-free like fleet_stats."""
    from ..serving.router import peek_router

    r = peek_router()
    if r is None:
        return dict(active=False)
    out = r.snapshot(probe=False)
    out["active"] = bool(out["ring"]) or bool(out["models"])
    return out


def qos_stats() -> Dict:
    """Multi-tenant QoS fold (ISSUE 19): gate state (who holds it —
    serving/training/idle), cumulative yield/wait totals, admission
    throttle state and the live knobs. Pure counter read — never waits
    at the gate."""
    from . import qos

    out = qos.stats()
    t = out.get("totals", {})
    out["active"] = bool(out.get("enabled") or t.get("yields")
                         or t.get("serving_dispatches"))
    return out


def registry_stats() -> Dict:
    """The central metrics registry's JSON view (counters/gauges/histogram
    summaries + windowed rates) — the /3/Profiler fold of the same store
    GET /3/Metrics scrapes as Prometheus text."""
    from . import metrics_registry

    return metrics_registry.snapshot()


def tracing_stats(n: int = 20) -> Dict:
    """Recent span summaries (the /3/Timeline fold, also available here)."""
    from . import tracing

    return dict(recorded=tracing.span_count(),
                recent=tracing.summaries(n))


@contextlib.contextmanager
def trace(log_dir: str):
    """`with profiler.trace(dir):` around a `train()` / `predict()` — one
    `jax.profiler` capture of the device planes AND the program's own
    spans (`runtime/tracing.py`: every `tracing.span` is a
    `TraceAnnotation`, so `train`, `fit.design`, `fit.iterate`, ... are
    events on `/host:CPU`, on the clock of the device planes).

    Started with the options the benchmark uses: the python tracer OFF (a
    whole fit of python frames is GBs), host tracer level 2. The capture
    lands at ``<log_dir>/plugins/profile/<timestamp>/<host>.xplane.pb``;
    open it in Perfetto / TensorBoard's profile plugin, or reduce it with
    `benchmark/reduce_trace.py` `load(path)`."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
