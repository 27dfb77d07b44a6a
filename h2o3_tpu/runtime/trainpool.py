"""Train pool — bounded scheduler for multi-model training sweeps.

Grid search (`models/grid.py`) and AutoML (`automl/automl.py`) submit
candidate model builds here instead of looping sequentially. With
``parallelism=N`` (the upstream `H2OGridSearch` knob) up to N candidates
are in flight at once: the device serializes their actual train-step
executions, but each candidate's HOST work — frame→matrix expansion,
binning, bit-packing, metrics, scoring-history, checkpoint serialization —
overlaps with its siblings' device compute, the training analog of the
serving micro-batcher's overlap. Results come back in SUBMISSION order, so
``parallelism=4`` produces the same model list (and therefore the same
leaderboard) as ``parallelism=1``; training itself is seed-deterministic.

Safety: on meshes where concurrent jobs are genuinely unsafe (multi-device
XLA:CPU thunk pools, multi-process clouds — `mesh.must_serialize_training`)
the pool degrades to sequential in-thread execution. It must NOT take
`mesh.training_guard()` from worker threads: the REST grid handler already
holds that RLock around the whole sweep, and its own workers would
deadlock against it.

Error isolation + hardening (docs/robustness.md):

* one candidate's exception is captured on its record (the sweep
  continues); `JobCancelled` marks the record cancelled;
* TRANSIENT failures (connection drops, device/XLA runtime errors —
  `runtime/retry.is_transient`) are retried up to
  ``H2O3_TRAIN_CAND_RETRIES`` times (default 1) against the shared retry
  budget; permanent errors (bad params) fail fast on the first attempt;
* an optional per-candidate WATCHDOG deadline
  (``H2O3_TRAIN_CAND_DEADLINE_S``, or ``TrainPool(candidate_deadline_s=)``)
  cancels a runaway candidate at its next scoring boundary and records it
  failed — one wedged build cannot absorb a whole sweep's wall-clock;
* a failed/cancelled candidate's PARTIAL artifacts are deleted from the
  DKV (the model key its child job registered) so a crashed sweep does not
  leak half-built models into `h2o.ls`;
* `SweepCheckpoint` persists per-candidate completion records so a killed
  sweep re-submitted with the same params skips already-trained candidates
  (the reference's `hex.grid` recovery; grid recovery_dir state and
  AutoML ``checkpoint_dir`` both ride it — counters land in ``resumed``).

Each candidate gets a child `Job` whose cancel check also consults the
sweep's parent job, so the existing `POST /3/Jobs/{id}/cancel` route on a
REST-driven grid stops in-flight candidates at their next scoring boundary
and skips the not-yet-started ones.

Observability: per-candidate wall seconds plus the phase split attributed
through `runtime/phases.candidate_sink` (h2d / compile / trace / host_prep
/ compute / metrics and h2d bytes), pool occupancy (busy worker-seconds ÷
wall·parallelism), CV fold reuse/rebin counters, and the hardening
counters (retried / watchdog_cancelled / resumed + the shared retry-policy
stats) — served at ``GET /3/Training/metrics`` (TrainingMetricsV3) and
folded into ``/3/Profiler`` via `runtime/profiler.training_stats`.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import faults as _faults
from . import phases as _phases
from . import retry as _retry
from . import tracing as _tracing

# candidate phase keys surfaced per record (subset of runtime/phases keys)
_CAND_PHASES = ("host_prep", "h2d", "compile", "trace", "deserialize",
                "compute", "metrics", "d2h")

_LOCK = threading.Lock()
_TOTALS = dict(pools=0, submitted=0, completed=0, failed=0, cancelled=0,
               skipped=0, retried=0, watchdog_cancelled=0, resumed=0,
               resumed_mid_fit=0, busy_s=0.0, wall_s=0.0)
_CV = dict(reuse_folds=0, rebin_folds=0)
_CANDIDATES: deque = deque(maxlen=int(os.environ.get(
    "H2O3_TRAIN_CANDIDATE_LOG", 64)))
_LAST_POOL: Dict = {}


_TOTAL_FIELDS = ("pools", "submitted", "completed", "failed", "cancelled",
                 "skipped", "retried", "watchdog_cancelled", "resumed",
                 "resumed_mid_fit")


_REGISTRY = None


def _registry():
    """Central-registry counters backing /3/Training/metrics totals + CV
    fold accounting (GET /3/Metrics scrape surface). Memoized — this runs
    per CV fold and per pool."""
    global _REGISTRY
    if _REGISTRY is not None:
        return _REGISTRY
    from . import metrics_registry as reg

    c = {f: reg.counter(f"h2o3_train_{f}",
                        f"train pool {f.replace('_', ' ')}")
         for f in _TOTAL_FIELDS}
    c["busy_s"] = reg.counter("h2o3_train_busy_seconds",
                              "busy worker-seconds across pool candidates")
    c["wall_s"] = reg.counter("h2o3_train_wall_seconds",
                              "pool wall-seconds")
    c["cv"] = reg.counter("h2o3_train_cv_folds",
                          "CV folds by preparation mode",
                          labelnames=("mode",))
    for f in _TOTAL_FIELDS:
        reg.bind_rest_field("training", f"totals.{f}", f"h2o3_train_{f}")
    reg.bind_rest_field("training", "totals.busy_s",
                        "h2o3_train_busy_seconds")
    reg.bind_rest_field("training", "totals.wall_s",
                        "h2o3_train_wall_seconds")
    reg.bind_rest_field("training", "cv.reuse_folds", "h2o3_train_cv_folds")
    reg.bind_rest_field("training", "cv.rebin_folds", "h2o3_train_cv_folds")
    _REGISTRY = c
    return c


def record_cv_fold(reused: bool) -> None:
    with _LOCK:
        _CV["reuse_folds" if reused else "rebin_folds"] += 1
    _registry()["cv"].inc(1, "reuse" if reused else "rebin")


def record_resumed(n: int = 1) -> None:
    """Sweep candidates satisfied from a checkpoint instead of retrained
    (grid recovery_dir auto-resume + AutoML checkpoint_dir)."""
    with _LOCK:
        _TOTALS["resumed"] += n
    _registry()["resumed"].inc(n)


def bump_total(field: str, n: int = 1) -> None:
    """Increment one /3/Training/metrics total by name from another
    subsystem (the supervisor bumps ``resumed_mid_fit`` when a fit
    restores a mid-fit snapshot)."""
    if field not in _TOTAL_FIELDS:
        raise KeyError(f"unknown train total {field!r}")
    with _LOCK:
        _TOTALS[field] += n
    _registry()[field].inc(n)


@dataclass
class JobRecord:
    """Outcome of one submitted candidate, in submission order."""

    name: str
    status: str = "pending"   # pending/done/failed/cancelled/skipped/resumed
    result: object = None
    error: Optional[str] = None
    exception: Optional[BaseException] = None
    wall_s: float = 0.0
    retries: int = 0
    phases: Dict[str, float] = field(default_factory=dict)
    bytes_h2d: int = 0

    @property
    def ok(self) -> bool:
        return self.status in ("done", "resumed")


class SweepCheckpoint:
    """Per-candidate completion records of one sweep, persisted as JSON.

    ``mark(key, payload)`` is atomic (tmp + os.replace) after every
    completion, so a sweep killed mid-flight leaves a readable record; a
    re-submitted sweep with the same id skips `completed()` candidates.
    The payload shape is the CALLER's (grid stores combo params + artifact
    file, AutoML stores leaderboard metrics + artifact file).

    ``fingerprint`` (a JSON-safe dict of the sweep's identity — response,
    features, seed, data shape, ...) guards against restoring someone
    else's records: candidate names like ``GBM_1`` are constants, so
    without it a checkpoint written for dataset A would silently serve
    A's models under a re-run on dataset B. A stored file whose
    fingerprint differs is treated as "no records".

    **In-flight records** (mid-fit resume rider): ``mark_inflight(key,
    info)`` persists that a candidate STARTED and where its fit-level
    checkpoints live (the supervisor's run fingerprint + checkpoint dir).
    A sweep killed mid-candidate therefore leaves a pointer a re-run can
    follow: the candidate retrains, its fit restores the newest valid
    mid-fit snapshot via that fingerprint, and only the uncheckpointed
    tail is rebuilt (``totals.resumed_mid_fit``). ``mark`` clears the
    key's in-flight record — a completed candidate needs no pointer."""

    def __init__(self, directory: str, sweep_id: str,
                 fingerprint: Optional[Dict] = None):
        self.directory = directory
        self.sweep_id = sweep_id
        self.fingerprint = fingerprint
        self.path = os.path.join(directory, f"{sweep_id}.sweep.json")
        self._lock = threading.Lock()
        self._records: Dict[str, Dict] = {}
        self._inflight: Dict[str, Dict] = {}
        if os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    data = json.load(f)
                if data.get("sweep_id") != sweep_id:
                    pass
                elif (fingerprint is not None
                        and data.get("fingerprint") != fingerprint):
                    from .log import Log

                    Log.warn(
                        f"sweep checkpoint {self.path}: stored fingerprint "
                        "does not match this run (different data/response/"
                        "seed?); ignoring its records")
                else:
                    self._records = dict(data.get("candidates") or {})
                    self._inflight = dict(data.get("inflight") or {})
            except (ValueError, OSError):
                # a torn/corrupt checkpoint means "no records", not a crash
                self._records = {}

    def completed(self, key: str) -> Optional[Dict]:
        with self._lock:
            return self._records.get(key)

    def keys(self) -> List[str]:
        with self._lock:
            return list(self._records)

    def inflight(self, key: Optional[str] = None):
        """The interrupted-candidate pointers the prior run left behind
        (all of them, or one key's)."""
        with self._lock:
            if key is not None:
                return self._inflight.get(key)
            return dict(self._inflight)

    def _write_locked(self) -> None:
        os.makedirs(self.directory, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(dict(sweep_id=self.sweep_id,
                           fingerprint=self.fingerprint,
                           candidates=self._records,
                           inflight=self._inflight), f)
        os.replace(tmp, self.path)

    def mark(self, key: str, payload: Dict) -> None:
        with self._lock:
            self._records[key] = payload
            self._inflight.pop(key, None)
            self._write_locked()

    def mark_inflight(self, key: str, info: Optional[Dict] = None) -> None:
        with self._lock:
            self._inflight[key] = dict(info or {}, ts=time.time())
            self._write_locked()

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)


def _child_job(dest: str, parent=None):
    """Per-candidate Job whose cancel check also consults the sweep's
    parent job (REST cancel on the grid job reaches running candidates)."""
    from ..models.model_base import Job

    class _J(Job):
        def check_cancelled(self):
            if parent is not None and parent.cancel_requested:
                self.cancel_requested = True
            Job.check_cancelled(self)

    return _J(dest=dest, description="train-pool candidate").start()


def _cleanup_partial(job) -> int:
    """Remove a failed/cancelled candidate's partial model artifacts from
    the DKV: the model key its job registered (job.result — set by
    model_base.train right after DKV.put) and the job's own dest key if a
    model landed under it. Only H2OModel values are touched."""
    from ..models.model_base import H2OModel
    from .dkv import DKV

    from . import memory_ledger

    removed = 0
    for k in {getattr(job, "result", None), getattr(job, "dest", None)}:
        if k and isinstance(DKV.get(k), H2OModel):
            DKV.remove(k)
            removed += 1
        if k:
            # leak canary: job_end no-ops when the key is gone (the normal
            # case right after the remove above); if a future path leaves
            # a partial model behind, it surfaces in the memory ledger's
            # leak report instead of silently leaking into h2o.ls
            memory_ledger.job_end(k, "FAILED")
    return removed


class TrainPool:
    """Run candidate build functions with bounded parallelism.

    ``items`` are ``(name, fn)`` where ``fn(job)`` builds and returns one
    model/estimator; ``job`` is the pool-created child Job (wire it in as
    the estimator's ``_external_job`` so cancellation — REST cancel AND the
    watchdog deadline — reaches the driver's scoring-boundary safe points).
    """

    def __init__(self, parallelism: int = 1, label: str = "train",
                 parent_job=None, candidate_retries: Optional[int] = None,
                 candidate_deadline_s: Optional[float] = None):
        self.parallelism = max(int(parallelism or 1), 1)
        self.label = label
        self.parent_job = parent_job
        from . import env_float, env_int

        self.candidate_retries = max(
            candidate_retries if candidate_retries is not None
            else env_int("H2O3_TRAIN_CAND_RETRIES", 1), 0)
        self.candidate_deadline_s = (
            candidate_deadline_s if candidate_deadline_s is not None
            else env_float("H2O3_TRAIN_CAND_DEADLINE_S", 0.0))

    def _effective_parallelism(self) -> int:
        if self.parallelism <= 1:
            return 1
        from ..parallel import mesh as cloudlib

        cloudlib.cloud()  # resolve the lazy default before deciding
        if cloudlib.must_serialize_training():
            return 1
        return self.parallelism

    def _run_candidate(self, rec: JobRecord, name: str,
                       fn: Callable) -> None:
        """One candidate: up to 1+retries attempts, each under a fresh
        child job and (when configured) a watchdog timer."""
        from ..models.model_base import JobCancelled

        deadline = self.candidate_deadline_s
        max_tries = 1 + self.candidate_retries
        attempt = 0
        while True:
            attempt += 1
            job = _child_job(f"{self.label}_{name}", parent=self.parent_job)
            watchdog = None
            if deadline > 0:
                def _fire(j=job):
                    j._watchdog_fired = True
                    j.cancel()

                watchdog = threading.Timer(deadline, _fire)
                watchdog.daemon = True
                watchdog.start()
            try:
                _faults.check("trainpool.candidate", name)
                rec.result = fn(job)
                rec.status = "done"
                return
            except JobCancelled:
                if getattr(job, "_watchdog_fired", False):
                    with _LOCK:
                        _TOTALS["watchdog_cancelled"] += 1
                    _registry()["watchdog_cancelled"].inc()
                    _tracing.event("watchdog_cancelled",
                                   deadline_s=deadline)
                    _cleanup_partial(job)
                    # mid-fit resume: with fit checkpointing active the
                    # re-attempt restores the newest snapshot and finishes
                    # the tail instead of retraining from tree 0 — so a
                    # watchdog kill is worth retrying (runtime/supervisor)
                    from . import supervisor as _sup

                    if (attempt < max_tries and _sup.ckpt_enabled()
                            and _sup.ckpt_dir()
                            and _retry.default_budget().try_spend()):
                        rec.retries += 1
                        _retry.record("trainpool", "retries")
                        with _LOCK:
                            _TOTALS["retried"] += 1
                        _registry()["retried"].inc()
                        _tracing.event("retry", attempt=attempt,
                                       error="watchdog_cancelled")
                        continue
                    rec.status = "failed"
                    rec.error = (f"candidate exceeded its {deadline:g}s "
                                 "watchdog deadline and was cancelled")
                    return
                rec.status = "cancelled"
                _cleanup_partial(job)
                return
            except Exception as e:  # error isolation: sweep continues
                _cleanup_partial(job)
                if (attempt < max_tries and _retry.is_transient(e)
                        and _retry.default_budget().try_spend()):
                    rec.retries += 1
                    _retry.record("trainpool", "retries")
                    with _LOCK:
                        _TOTALS["retried"] += 1
                    _registry()["retried"].inc()
                    _tracing.event("retry", attempt=attempt,
                                   error=f"{type(e).__name__}: {e}")
                    continue
                rec.status = "failed"
                rec.error = str(e)
                rec.exception = e
                return
            finally:
                if watchdog is not None:
                    watchdog.cancel()

    def run(self, items: Sequence[Tuple[str, Callable]],
            stop_when: Optional[Callable[[], bool]] = None
            ) -> List[JobRecord]:
        records = [JobRecord(name=name) for name, _ in items]
        par = self._effective_parallelism()
        t0 = time.perf_counter()
        # trace correlation: candidates run on pool worker threads, so the
        # submitting thread's span (the REST job span, usually) is captured
        # here and re-attached per candidate — every candidate span shares
        # the request's trace id
        parent_span = _tracing.current()
        trace_id = (parent_span.trace_id if parent_span is not None
                    else getattr(self.parent_job, "trace_id", None))
        parent_id = (parent_span.span_id if parent_span is not None
                     else None)

        def _one(i: int) -> None:
            rec = records[i]
            name, fn = items[i]
            if self.parent_job is not None \
                    and self.parent_job.cancel_requested:
                rec.status = "cancelled"
                return
            if stop_when is not None and stop_when():
                rec.status = "skipped"
                return
            # QoS admission throttle (hysteresis over ledger pressure +
            # live serving p99): hold the candidate back while the device
            # is contended; bounded wait, booked to the qos_wait phase
            from . import qos as _qos

            _qos.admission_gate(name)
            t1 = time.perf_counter()
            with _tracing.span(f"candidate:{name}", kind="candidate",
                               trace_id=trace_id, parent_id=parent_id,
                               label=self.label) as sp, \
                    _phases.candidate_sink() as sink:
                self._run_candidate(rec, name, fn)
                sp.annotate(status=rec.status)
            rec.wall_s = time.perf_counter() - t1
            secs = sink["secs"]
            rec.phases = {k: round(secs[k], 4) for k in _CAND_PHASES
                          if k in secs}
            rec.bytes_h2d = int(sink["bytes"].get("h2d", 0))
            _record_candidate(self.label, rec, par)

        if par <= 1:
            for i in range(len(records)):
                _one(i)
        else:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                    max_workers=par,
                    thread_name_prefix=f"h2o3tpu-train-{self.label}") as ex:
                list(ex.map(_one, range(len(records))))

        wall = time.perf_counter() - t0
        busy = sum(r.wall_s for r in records)
        entry = dict(
            label=self.label, parallelism=par,
            requested_parallelism=self.parallelism,
            n_jobs=len(records),
            done=sum(r.status == "done" for r in records),
            failed=sum(r.status == "failed" for r in records),
            cancelled=sum(r.status == "cancelled" for r in records),
            skipped=sum(r.status == "skipped" for r in records),
            retried=sum(r.retries for r in records),
            wall_s=round(wall, 4), busy_s=round(busy, 4),
            occupancy=round(busy / max(wall * par, 1e-9), 4),
        )
        with _LOCK:
            _TOTALS["pools"] += 1
            _TOTALS["submitted"] += len(records)
            _TOTALS["completed"] += entry["done"]
            _TOTALS["failed"] += entry["failed"]
            _TOTALS["cancelled"] += entry["cancelled"]
            _TOTALS["skipped"] += entry["skipped"]
            _TOTALS["busy_s"] += busy
            _TOTALS["wall_s"] += wall
            _LAST_POOL.clear()
            _LAST_POOL.update(entry)
        reg = _registry()
        reg["pools"].inc()
        reg["submitted"].inc(len(records))
        reg["completed"].inc(entry["done"])
        reg["failed"].inc(entry["failed"])
        reg["cancelled"].inc(entry["cancelled"])
        reg["skipped"].inc(entry["skipped"])
        reg["busy_s"].inc(busy)
        reg["wall_s"].inc(wall)
        return records


def _record_candidate(label: str, rec: JobRecord, parallelism: int) -> None:
    entry = dict(label=label, name=rec.name, status=rec.status,
                 wall_s=round(rec.wall_s, 4), parallelism=parallelism,
                 phases=rec.phases, bytes_h2d=rec.bytes_h2d)
    if rec.retries:
        entry["retries"] = rec.retries
    if rec.error:
        entry["error"] = rec.error
    with _LOCK:
        _CANDIDATES.append(entry)


def snapshot() -> Dict:
    """The GET /3/Training/metrics body (cache section joined in by the
    REST handler from models/dataset_cache.snapshot())."""
    with _LOCK:
        totals = dict(_TOTALS)
        cv = dict(_CV)
        cands = list(_CANDIDATES)
        last = dict(_LAST_POOL) if _LAST_POOL else None
    busy, wall = totals.pop("busy_s"), totals.pop("wall_s")
    totals["busy_s"] = round(busy, 4)
    totals["wall_s"] = round(wall, 4)
    return dict(totals=totals, cv=cv, candidates=cands, last_pool=last,
                retry=_retry.snapshot(), faults=_faults.snapshot(),
                active=totals["submitted"] > 0)


def reset() -> None:
    with _LOCK:
        _TOTALS.update(pools=0, submitted=0, completed=0, failed=0,
                       cancelled=0, skipped=0, retried=0,
                       watchdog_cancelled=0, resumed=0, resumed_mid_fit=0,
                       busy_s=0.0, wall_s=0.0)
        _CV.update(reuse_folds=0, rebin_folds=0)
        _CANDIDATES.clear()
        _LAST_POOL.clear()
