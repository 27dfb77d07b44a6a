"""Schema registry — parameter metadata for every model builder.

Reference parity: `water/api/Schema.java` + `water/api/schemas3/*.java` and
the `/3/Metadata/schemas` endpoint that `h2o-bindings/bin/gen_python.py`
consumes to generate the client estimators. Here the single source of truth
is each estimator's `_param_defaults` (no codegen — SURVEY.md §2.6), and this
module renders the same metadata shape over REST.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Type


def _algo_registry() -> Dict[str, Type]:
    from .. import estimators as est

    reg = {}
    for name in est.__all__:
        cls = getattr(est, name)
        reg[cls.algo] = cls
    return reg


_registry_cache: Optional[Dict[str, Type]] = None


def algo_registry() -> Dict[str, Type]:
    global _registry_cache
    if _registry_cache is None:
        _registry_cache = _algo_registry()
    return _registry_cache


def _field_schema(name: str, default) -> Dict:
    t = type(default).__name__ if default is not None else "any"
    return dict(name=name, type=t, default_value=default, required=False)


def schema_for(algo: str) -> Dict:
    cls = algo_registry().get(algo)
    if cls is None:
        raise KeyError(algo)
    fields = [
        _field_schema(k, v)
        for k, v in {**cls._common_defaults, **cls._param_defaults}.items()
    ]
    return dict(
        algo=algo,
        name=f"{cls.__name__}V3",
        supervised=cls.supervised,
        parameters=fields,
    )


def all_schemas() -> List[Dict]:
    return [schema_for(a) for a in sorted(algo_registry())]


SERVING_SCHEMA_NAME = "ServingMetricsV3"
INGEST_SCHEMA_NAME = "IngestMetricsV3"
MUNGE_SCHEMA_NAME = "MungeMetricsV3"
TRAINING_SCHEMA_NAME = "TrainingMetricsV3"
OBSERVABILITY_SCHEMA_NAME = "ObservabilityV3"
MEMORY_SCHEMA_NAME = "MemoryV3"
ROUTER_SCHEMA_NAME = "RouterV3"
SUPERVISOR_SCHEMA_NAME = "SupervisorV3"

# the per-subsystem JSON metrics endpoints whose counter fields must be
# backed by central-registry metrics (metrics_registry.bind_rest_field);
# the metrics-consistency test walks these against GET /3/Metrics
METRICS_ENDPOINTS = {
    "serving": "/3/Serving/metrics",
    "ingest": "/3/Ingest/metrics",
    "munge": "/3/Munge/metrics",
    "training": "/3/Training/metrics",
    "memory": "/3/Memory",
    "fleet": "/3/Fleet?probe=0",
    "router": "/3/Router?probe=0",
    "supervisor": "/3/Supervisor",
}


def observability_schema() -> Dict:
    """Field metadata of the observability-spine surfaces
    (docs/observability.md mirrors this)."""
    fields = [
        ("GET /3/Metrics", "text/plain",
         "Prometheus text exposition (0.0.4) of the central metrics"
         " registry: every subsystem counter/gauge/histogram, HELP/TYPE"
         " lines, _total counter suffixes, _bucket/_sum/_count histogram"
         " series — the machine-scrapable surface"),
        ("GET /3/Trace?trace_id=", "TraceEventsJSON",
         "Chrome-trace/Perfetto JSON of recorded spans: request (root,"
         " trace id from the X-H2O3-Trace-Id header), job, candidate,"
         " batch, ingest and munge spans with retry/fault annotations"),
        ("GET /3/Timeline?since=&n=", "TimelineV3",
         "bounded event ring + recent span summaries; every event carries"
         " a monotone seq — pass the returned cursor back as since= for"
         " incremental tailing"),
        ("X-H2O3-Trace-Id", "header",
         "client-minted (or server-minted when absent) trace id,"
         " propagated into Jobs/candidates/batches and echoed on every"
         " response"),
        ("GET /3/Metrics?scope=fleet", "text/plain",
         "fleet-merged Prometheus exposition: every registered peer"
         " scraped (RetryPolicy) and merged — counters summed, histogram"
         " buckets summed (exact fleet percentiles), gauges per-replica"
         " under a replica label, unreachable peers as explicit"
         " h2o3_fleet_peer_up 0 series"),
        ("GET /3/Metrics?format=json", "JSON",
         "lossless registry export (labelnames, raw label tuples, raw"
         " histogram buckets + sum/min/max) — the payload fleet"
         " aggregators scrape and merge"),
        ("GET /3/Trace?scope=fleet", "TraceEventsJSON",
         "every replica's span export merged into one Chrome-trace"
         " timeline, one process_name track per replica"),
        ("GET/POST/DELETE /3/Fleet", "FleetV3",
         "peer registry + fleet fold: per-replica liveness, serving"
         " counters and predict p99, fleet-merged totals (the loadgen"
         " --fleet report source)"),
    ]
    return dict(
        name=OBSERVABILITY_SCHEMA_NAME,
        fields=[dict(name=n, type=t, help=h) for n, t, h in fields],
    )


def memory_schema() -> Dict:
    """Field metadata of the `GET /3/Memory` document (the memory
    ledger's observability schema — docs/observability.md "Memory
    accounting" mirrors this)."""
    fields = [
        ("totals", "MemoryTotals",
         "ledger-attributed bytes: host_bytes, device_bytes,"
         " leaked_bytes (dead owners whose buffers persist + DKV keys a"
         " failed job left behind), unaccounted_device_bytes (device"
         " probe minus attributed — the reconciliation remainder),"
         " owner_count"),
        ("owners", "list<OwnerBytes>",
         "per-owner breakdown (owner id, kind, host/device bytes, dead"
         " flag), largest first; owner ids follow the taxonomy"
         " dkv:<key> / dataset_cache:<fp>:<layer> / scorer:<model>:<kind>"
         " / ingest:<what>"),
        ("by_kind", "map<owner_kind, KindBytes>",
         "host/device bytes + owner count aggregated per owner kind"
         " (frame, model, dkv, dataset_cache, scorer, ingest) — the same"
         " aggregation scraped as h2o3_memory_bytes{owner_kind,space}"),
        ("watermarks", "MemoryWatermarks",
         "high watermark of host/device/total attributed bytes plus the"
         " top-3 owners captured at the combined peak (the bench-record"
         " memory embed reads this)"),
        ("pressure", "MemoryPressure",
         "pressure in [0,1]: max(host bytes vs H2O3_MEM_BUDGET_MB or"
         " MemTotal, device bytes vs device capacity); serving admission"
         " sheds at H2O3_SERVING_SHED_PRESSURE, dataset_cache evicts at"
         " H2O3_MEM_EVICT_PRESSURE, crossings of"
         " H2O3_MEM_PRESSURE_THRESHOLD are traced"),
        ("device", "DeviceProbe",
         "what the runtime actually holds: per-device memory_stats()"
         " where the backend reports them, else a live-buffer census"
         " (CPU fallback); the unattributed delta is reported as"
         " owner_kind=unaccounted — never silently absorbed"),
        ("leaks", "list<LeakReport>",
         "live leak report: owners whose referent died but whose buffers"
         " persist, and FAILED/CANCELLED jobs whose dest key still holds"
         " a model/frame; entries clear when the bytes are released"),
    ]
    return dict(
        name=MEMORY_SCHEMA_NAME,
        fields=[dict(name=n, type=t, help=h) for n, t, h in fields],
    )


def router_schema() -> Dict:
    """Field metadata of the `GET /3/Router` document (the serving fleet
    router's observability schema — docs/serving.md "Fleet serving"
    mirrors this)."""
    fields = [
        ("ring", "list<ReplicaState>",
         "the dispatch ring: per-replica name/url, up (from the fleet"
         " scrape, the h2o3_fleet_peer_up source), drained flag,"
         " router-local inflight count, consecutive_errors, scraped"
         " memory pressure and predict p99 — the least-loaded ordering"
         " ranks on (up, drained, inflight, pressure, p99)"),
        ("inflight", "int",
         "requests currently inside the router's fleet-wide token budget"
         " (sheds with 429 at H2O3_ROUTER_MAX_INFLIGHT)"),
        ("totals", "RouterTotals",
         "cumulative router counters: requests/errors (per-lane in the"
         " registry), shed (budget/pressure/no_replicas), retries,"
         " failovers, drains, rollbacks, warm_loads, shadow_* — every"
         " field is bind_rest_field-backed by an h2o3_router_* family"),
        ("models", "map<model, VersionTable>",
         "the registry fold: per-model live/canary/shadow pointers,"
         " canary_pct, and every version's state (published → warm →"
         " canary → live → retired/failed), artifact path and per-replica"
         " warm-load reports"),
        ("canary_health", "map<model, CanaryWindow>",
         "while a canary runs: per-lane (live vs canary) request/error"
         " counts and bucket p99 since the canary started — the inputs of"
         " the auto-rollback verdict"),
        ("config", "RouterConfig",
         "the H2O3_ROUTER_* knobs in effect (admission budget, drain"
         " thresholds, canary ratios, shadow compare depth)"),
    ]
    return dict(
        name=ROUTER_SCHEMA_NAME,
        fields=[dict(name=n, type=t, help=h) for n, t, h in fields],
    )


def supervisor_schema() -> Dict:
    """Field metadata of the `GET /3/Supervisor` document (the elastic
    training supervisor's observability schema — docs/robustness.md
    "Recovery matrix" mirrors this)."""
    fields = [
        ("state", "string",
         "supervisor state machine: idle (no supervised fit) / watching"
         " (a fit is inside its loop) / aborted (the last fence breach"
         " has not been superseded by a new fit)"),
        ("fit", "FitInfo",
         "the supervised fit in flight: tag (tree/estkmeans/estglm),"
         " run fingerprint, total steps, start timestamp"),
        ("heartbeat", "Heartbeat",
         "last liveness pulse from inside a supervised loop (chunk/"
         "segment/stream-block boundary): tag, step, timestamp — the"
         " background watcher reads its age"),
        ("last_abort", "AbortRecord",
         "most recent hung-collective abort: tag, detection latency (s),"
         " suspect ranks marked down, timestamp"),
        ("last_resume", "ResumeRecord",
         "most recent mid-fit checkpoint restore: tag, restored step,"
         " timestamp"),
        ("last_ckpt", "CkptRecord",
         "most recent committed snapshot: path, step, save wall (s)"),
        ("totals", "SupervisorTotals",
         "cumulative counters, each bind_rest_field-backed by an"
         " h2o3_supervisor_* family: aborts, resumes, ckpt_saves,"
         " ckpt_rejects (torn/wrong-fingerprint/incomplete-rank-set files"
         " skipped at restore), marked_down"),
        ("detect_ms", "histogram",
         "failure detection latency (ms): fence dispatch to abort"),
        ("config", "SupervisorConfig",
         "resolved knobs: ckpt_enabled (H2O3_CKPT), ckpt_dir"
         " (H2O3_CKPT_DIR), ckpt_trees (H2O3_CKPT_TREES),"
         " fence_deadline_s (H2O3_FENCE_DEADLINE_S), watcher (background"
         " failure watcher running)"),
    ]
    return dict(
        name=SUPERVISOR_SCHEMA_NAME,
        fields=[dict(name=n, type=t, help=h) for n, t, h in fields],
    )


def training_metrics_schema() -> Dict:
    """Field metadata of the `GET /3/Training/metrics` document (the
    multi-model training engine's observability schema — docs/training.md
    mirrors this)."""
    fields = [
        ("totals", "TrainingTotals",
         "cumulative pool counters since start (or reset): pools run,"
         " candidates submitted/completed/failed/cancelled/skipped,"
         " busy worker-seconds and pool wall-seconds"),
        ("cv", "CvReuseStats",
         "cross-validation fold accounting: reuse_folds (parent binned-"
         "matrix sliced per fold) vs rebin_folds (seed per-fold re-bin,"
         " H2O3_CV_REBIN=1 or non-tree builders)"),
        ("candidates", "list<CandidateStats>",
         "the most recent candidate builds: name/label/status/wall_s, the"
         " per-candidate phase split (host_prep/h2d/compile/trace/compute/"
         "metrics seconds, attributed via runtime/phases thread-local"
         " sinks) and bytes_h2d"),
        ("last_pool", "PoolStats",
         "the most recent sweep: parallelism (requested and effective —"
         " clouds that must serialize training degrade to 1), n_jobs,"
         " done/failed/cancelled/skipped, wall_s, busy_s and occupancy ="
         " busy/(wall×parallelism)"),
        ("cache", "DatasetCacheStats",
         "the dataset-artifact cache (models/dataset_cache.py): hits/"
         "misses per layer (matrix/bins/device/blocks/std/targets),"
         " evictions, live entries,"
         " resident bytes, enabled flag"),
        ("totals.retried", "int",
         "candidate build attempts re-run after a TRANSIENT failure"
         " (runtime/retry classification; bounded by"
         " H2O3_TRAIN_CAND_RETRIES and the shared retry budget)"),
        ("totals.watchdog_cancelled", "int",
         "candidates cancelled by the per-candidate watchdog deadline"
         " (H2O3_TRAIN_CAND_DEADLINE_S)"),
        ("totals.resumed", "int",
         "sweep candidates satisfied from checkpoint records instead of"
         " retrained (grid recovery_dir auto-resume, AutoML"
         " checkpoint_dir — docs/robustness.md)"),
        ("totals.resumed_mid_fit", "int",
         "fits that restored a MID-FIT checkpoint and continued past"
         " tree/iteration 0 (runtime/supervisor, H2O3_CKPT_DIR —"
         " docs/robustness.md 'Recovery matrix')"),
        ("retry", "RetryStats",
         "shared retry-policy counters per policy (persist/client/"
         "trainpool): calls, retries, recovered, permanent_failures,"
         " deadline/attempts/budget exhaustions"),
        ("faults", "FaultStats",
         "armed fault-injection points + fire counts (runtime/faults;"
         " default off — GET/POST/DELETE /3/Faults)"),
        ("active", "boolean", "false until the first pooled sweep runs"),
    ]
    return dict(
        name=TRAINING_SCHEMA_NAME,
        fields=[dict(name=n, type=t, help=h) for n, t, h in fields],
    )


def munge_metrics_schema() -> Dict:
    """Field metadata of the `GET /3/Munge/metrics` document (the
    vectorized munging engine's observability schema — docs/munging.md
    mirrors this)."""
    fields = [
        ("totals", "MungeTotals",
         "cumulative ops/rows_in/rows_out/secs + derived rows_per_s over"
         " every munge op since start (or reset)"),
        ("ops", "map<op, MungeOpStats>",
         "per-op calls/errors/rows_in/rows_out/secs/rows_per_s + path"
         " counts (merge, group_by, pivot, table, apply_rows, moment,"
         " as_date, num_valid_substrings); a call that raised counts in"
         " errors with rows_out 0"),
        ("ops.*.paths", "map<string,int>",
         "how calls executed: vectorized (columnar kernels), fallback"
         " (exact per-row loop — after a failed vectorized attempt, or"
         " where vectorization doesn't apply: non-UTC moment, asDate on"
         " a non-string/enum column, 0-row apply), legacy"
         " (H2O3_MUNGE_LEGACY=1 seed path)"),
        ("last", "MungeOpStats",
         "the most recent op, or null before the first one"),
        ("last.rows_per_s", "double", "input rows / wall seconds"),
        ("last.stages", "map<string,double>",
         "per-stage seconds — merge books factorize / combine / match /"
         " assemble (same buckets runtime/phases records as munge_*)"),
        ("active", "boolean", "false until the first munge op happens"),
    ]
    return dict(
        name=MUNGE_SCHEMA_NAME,
        fields=[dict(name=n, type=t, help=h) for n, t, h in fields],
    )


def ingest_metrics_schema() -> Dict:
    """Field metadata of the `GET /3/Ingest/metrics` document (the chunked
    parse pipeline's observability schema — docs/ingest.md mirrors this)."""
    fields = [
        ("totals", "IngestTotals",
         "cumulative parses/rows/bytes/secs + derived rows_per_s,"
         " bytes_per_s over every parse since start (or reset)"),
        ("last", "IngestParseStats",
         "the most recent parse, or null before the first one"),
        ("last.rows_per_s", "double", "rows / wall seconds of that parse"),
        ("last.bytes_per_s", "double", "bytes / wall seconds of that parse"),
        ("last.n_chunks", "int",
         "byte chunks (or line blocks on the distributed path) tokenized"),
        ("last.n_threads", "int", "thread-pool workers used for phase 1"),
        ("last.native", "boolean",
         "true when the C++ per-chunk tokenizer handled the file"),
        ("last.distributed", "boolean",
         "true for the multi-process byte-range path"),
        ("last.phases", "map<string,double>",
         "per-stage seconds: setup / read / tokenize / coerce / intern /"
         " place (same buckets runtime/phases records as ingest_*)"),
        ("active", "boolean", "false until the first parse happens"),
    ]
    return dict(
        name=INGEST_SCHEMA_NAME,
        fields=[dict(name=n, type=t, help=h) for n, t, h in fields],
    )


def serving_metrics_schema() -> Dict:
    """Field metadata of the `GET /3/Serving/metrics` document (the serving
    subsystem's observability schema — docs/serving.md mirrors this)."""
    fields = [
        ("models", "map<model_key, ModelServingStats>",
         "per-model counters + histograms"),
        ("models.*.counters", "map<string,int>",
         "requests/rejections/errors, batches/batched_requests/batched_rows,"
         " compiles/cache_hits"),
        ("models.*.histograms.queue_wait_ms", "histogram",
         "request dwell in the micro-batch queue"),
        ("models.*.histograms.device_ms", "histogram",
         "scoring-call wall time per batch (includes compile on cold"
         " buckets)"),
        ("models.*.histograms.batch_size", "histogram",
         "requests coalesced per device batch"),
        ("models.*.counters (failover)", "map<string,int>",
         "scorer_faults (device/XLA errors), quarantines (poisoned"
         " executables evicted), scorer_rebuilds (rebuild-once succeeded),"
         " breaker_opens, fallback_scores (batches served by the"
         " compiled-CPU fallback)"),
        ("totals", "map<string,int>", "counters summed over all models"),
        ("cache", "CacheStats",
         "compiled-scorer LRU: capacity/size/hits/misses/evictions +"
         " per-entry warm row buckets"),
        ("admission", "AdmissionStats",
         "in-flight counts vs the global and per-model bounds"),
        ("failover", "FailoverStats",
         "per-(model, output_kind) circuit breakers (state/opens/time to"
         " half-open probe) + live CPU-fallback scorers"
         " (docs/robustness.md 'Serving failover')"),
        ("config", "ServingConfig", "the active knob values"),
    ]
    return dict(
        name=SERVING_SCHEMA_NAME,
        fields=[dict(name=n, type=t, help=h) for n, t, h in fields],
    )
