"""Cloud/mesh management — the TPU-native replacement for H2O's clouding layer.

Reference parity: `h2o-core/src/main/java/water/H2O.java` (node bootstrap),
`water/Paxos.java` + `water/HeartBeatThread.java` (cloud membership). In the
reference a "cloud" is a set of JVM peers discovered by gossip; here a cloud
is a `jax.sharding.Mesh` over the devices JAX already knows about —
`jax.distributed.initialize()` plays the role of Paxos (one process per TPU
host ≡ one H2O node), and membership is fixed at init, matching H2O's
"cloud locks at first job" semantics (`water/Paxos.java`).

The data-parallel axis is named ``"hosts"`` everywhere: rows of a Frame are
sharded over it, and every MRTask-style reduction lowers to an XLA collective
(`lax.psum`) over it instead of H2O's binary RPC tree (`water/MRTask.java`).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

ROWS_AXIS = "hosts"  # the one inter-node axis H2O has: row/data parallelism

_lock = threading.Lock()
_cloud: Optional["Cloud"] = None
# the (coordinator_address, num_processes, process_id) jax.distributed was
# initialized with — re-initializing the distributed runtime crashes, so a
# repeat init() with the same topology is answered idempotently and a
# CONFLICTING topology is a loud error instead of a crash mid-bootstrap
_dist_topology: Optional[tuple] = None


@dataclass
class Cloud:
    """A locked set of devices arranged in a 1-D data-parallel mesh.

    Mirrors `water.H2O.CLOUD` (static cloud singleton). `size` ≡
    `H2O.CLOUD.size()`; `self_idx` ≡ `H2O.SELF.index()`.
    """

    mesh: Mesh
    name: str = "h2o-tpu"

    @property
    def size(self) -> int:
        return self.mesh.devices.size

    @property
    def self_idx(self) -> int:
        return jax.process_index()

    @property
    def devices(self):
        return list(self.mesh.devices.flat)

    def row_sharding(self) -> NamedSharding:
        """Sharding for per-row (leading-axis) data — H2O's chunk layout."""
        return NamedSharding(self.mesh, P(ROWS_AXIS))

    def replicated(self) -> NamedSharding:
        """Sharding for model state: replicated on every node (like DKV
        cached values on every H2O node)."""
        return NamedSharding(self.mesh, P())


def init(
    devices: Optional[Sequence[jax.Device]] = None,
    name: str = "h2o-tpu",
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> Cloud:
    """Form the cloud. Single-process: mesh over local devices. Multi-host:
    pass coordinator_address/num_processes/process_id (wraps
    `jax.distributed.initialize`, replacing `water/init/NetworkInit.java`).

    Re-init is IDEMPOTENT for the distributed runtime: a second call with
    the same coordinator topology returns the live cloud instead of
    re-invoking `jax.distributed.initialize` (which crashes); a second call
    with a CONFLICTING topology raises a clear error naming both. Device
    re-selection (the single-process `devices=` form) still rebuilds the
    mesh — that is how tests move between 1- and 8-device clouds.
    """
    global _cloud, _dist_topology
    with _lock:
        if coordinator_address is not None and num_processes and num_processes > 1:
            topo = (coordinator_address, int(num_processes),
                    None if process_id is None else int(process_id))
            if _dist_topology is not None:
                if topo != _dist_topology:
                    raise RuntimeError(
                        "cloud already initialized with coordinator "
                        f"topology {_dist_topology}; re-init with {topo} "
                        "conflicts — shut the process down to re-cloud "
                        "(membership is fixed at init, water/Paxos.java "
                        "'cloud locks' semantics)")
                # same topology: the distributed runtime is already up —
                # answer with the live cloud (or rebuild the mesh below if
                # reset() dropped it)
                if _cloud is not None:
                    return _cloud
            else:
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes,
                    process_id=process_id,
                )
                _dist_topology = topo
        if devices is None:
            devices = jax.devices()
        mesh = Mesh(np.asarray(devices), (ROWS_AXIS,))
        _cloud = Cloud(mesh=mesh, name=name)
        _lane_cache_topology(_cloud)
        return _cloud


def cloud() -> Cloud:
    """The current cloud, forming a local one lazily (like `H2O.main` being
    auto-started by the Python client, `h2o-py/h2o/backend/server.py`)."""
    global _cloud
    if _cloud is None:
        init()
    return _cloud


def reset() -> None:
    global _cloud
    with _lock:
        _cloud = None


def shard_call(fn, cloud: "Cloud", in_specs, out_specs, check_vma=True):
    """t5x-style fall-through-to-jit wrapper (SNIPPETS.md [1], `t5x
    partitioning.pjit`): on a multi-device cloud, wrap `fn` in `shard_map`
    over the 1-D ``hosts`` mesh; on a 1-device cloud return `fn` UNCHANGED
    so the caller's plain `jit` runs the IDENTICAL function body — the
    forced-CPU test lane exercises the same sharded code path (blocked
    histogram reduction included) without a mesh, and a parity pin between
    the two lanes compares one implementation against itself.

    `check_vma=False` is required for bodies whose replicated outputs come
    from an `all_gather` + explicit fold (the deterministic histogram
    merge) rather than a `psum` — shard_map cannot statically infer the
    replication there, but the fold IS replicated by construction."""
    if cloud.size > 1:
        return shard_map(fn, mesh=cloud.mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=check_vma)
    return fn


def collective_fence(x) -> None:
    """Serialize multi-device collective programs on the CPU backend.

    XLA:CPU executes async-dispatched executables CONCURRENTLY on one shared
    thunk pool. Two in-flight collective programs can starve each other: one
    holds pool threads at its all-reduce rendezvous while the other's thunks
    occupy the rest, so the final participant never runs and the runtime
    aborts after its 40 s rendezvous timeout (observed as 7/8 participants
    on the 8-virtual-device test cloud of a 1-core host). Blocking on the
    previous program's output before dispatching the next collective keeps
    at most one collective executable in flight. TPU streams already
    serialize executions, so this is a no-op there.

    The blocked time is booked to the ``collective`` phase bucket
    (runtime/phases): on a CPU mesh it is the wait for collective-program
    completion, so runtime/phases decomposes a sharded fit's wall into
    {h2d, compute, collective, ...} instead of hiding the merge cost in
    compute."""
    import time as _time

    import jax

    c = _cloud
    if c is not None and c.size > 1 and jax.default_backend() == "cpu":
        t0 = _time.perf_counter()
        from ..runtime import supervisor as _sup

        deadline = _sup.fence_deadline_s()
        if deadline > 0:
            # deadline'd fence (ISSUE 20): a peer rank dying mid-collective
            # leaves this block waiting on the rendezvous forever — the
            # supervisor aborts it with CollectiveTimeout instead, marks
            # the suspect ranks down, and the caller resumes elsewhere
            _sup.deadline_block(x, deadline, tag="collective_fence")
        else:
            jax.block_until_ready(x)
        try:
            from ..runtime import phases as _phases

            _phases.add("collective", _time.perf_counter() - t0)
        except Exception:
            pass


_training_lock = threading.RLock()


def training_guard():
    """Context manager serializing whole training jobs across threads on
    multi-device CPU meshes and on MULTI-PROCESS clouds of any backend.

    `collective_fence` keeps at most one collective executable in flight
    *within* a training loop, but two REST-spawned jobs (grid + AutoML, or
    two concurrent model builds) interleave dispatches from separate
    threads:

    * on a multi-device XLA:CPU mesh that recreates the thunk-pool
      rendezvous deadlock the fence exists to avoid;
    * on a multi-HOST cloud (TPU pod over ICI/DCN included) collective
      launch order must be identical on every rank. This lock serializes
      jobs WITHIN each process; it cannot order jobs ACROSS ranks — that
      is the SPMD contract: every rank runs the same driver script, so
      jobs are submitted in the same program order everywhere (the
      reference demands the same: every node must see the same job
      submissions). Submitting jobs to different ranks from independent
      sources concurrently is unsupported and would deadlock with or
      without this lock; docs/distributed.md spells this out.

    Single-process single-backend TPU (streams serialize, no cross-rank
    ordering to break) returns a no-op context so concurrent jobs still
    overlap host-side work."""
    import contextlib

    if must_serialize_training():
        return _training_lock
    return contextlib.nullcontext()


def must_serialize_training() -> bool:
    """True when `training_guard()` would hand out the real lock — i.e.
    concurrent training jobs are unsafe on this cloud (multi-device CPU
    thunk-pool rendezvous, or multi-process collective launch order). The
    train-pool scheduler (runtime/trainpool.py) checks this and degrades
    to sequential in-thread execution instead of taking the lock from
    worker threads — an RLock already held by the submitting thread (the
    REST grid handler wraps the whole sweep in training_guard) would
    deadlock its own workers."""
    import jax

    c = _cloud
    return bool(c is not None and c.size > 1 and (
        jax.default_backend() == "cpu" or jax.process_count() > 1))


def pad_to_multiple(n: int, k: int) -> int:
    """Rows are padded so each mesh shard is equal-sized (XLA needs static,
    uniform shards; H2O chunks could be ragged — ours cannot)."""
    return ((n + k - 1) // k) * k


# -- per-lane collective skew profiling + straggler detection (ISSUE 13) ------
#
# A slow lane in a sharded fit (the classic data-parallel-boosting
# straggler) was invisible: `collective_fence` books only the DRIVER'S
# total wait. The instrument here records, per collective fence, WHEN each
# lane arrived at the rendezvous: `lane_mark(x, axis, tag)` inserts an
# `io_callback` into the sharded program (ordered before the all_gather by
# an optimization_barrier data dependency), so each lane stamps a host
# timestamp the moment its local partial is ready. The fence's per-lane
# wait is each lane's arrival lag behind the FIRST arriver — the time the
# collective spent waiting on that lane. All bookkeeping is host-side
# dicts; nothing blocks device work, and the instrument is only attached
# to the per-scoring-interval programs (the event-loss fence), NEVER the
# per-level histogram hot path.
#
# The same callback is the injection point for the `mesh.lane_delay`
# fault (runtime/faults, latency-only): arming it with lane=N sleeps N's
# arrival callback, delaying that lane's rendezvous entry for real — the
# detector below must then flag exactly lane N (pinned in
# tests/test_tree_sharded.py and exercised by dryrun_multichip).
#
# Straggler detection: a lane whose per-fence wait persistently (>=
# H2O3_STRAGGLER_FENCES consecutive fences) exceeds
# max(median_wait * H2O3_STRAGGLER_FACTOR, H2O3_STRAGGLER_MIN_MS) fires
# `h2o3_stragglers_total{lane}`, a Timeline event and a zero-duration
# trace span — once per streak, re-armed when the lane recovers.

import functools as _functools
import time as _time
from collections import deque as _deque

_LANE_LOCK = threading.Lock()
_LANE_SEQ = 0                      # monotone fence counter
_LANE_OPEN: dict = {}              # tag -> {lane: t_arrive}
_LANE_RECORDS: "_deque" = _deque(maxlen=256)
_LANE_LAST: dict = {}              # lane -> wait_ms of the most recent fence
_LANE_STREAK: dict = {}            # lane -> consecutive flagged fences
_LANE_FIRED: dict = {}             # lane -> total straggler firings
_LANE_REG: dict = {}
_F32_ZERO = np.float32(0.0)
# Topology cached at init() so watchdog threads can map a lane to its
# owning RANK without ever touching jax (a hung backend blocks any jax
# call).
_LANE_PROC: dict = {}              # lane -> owning process index
_LANE_SELF: int = 0                # this process's index
_LANE_EXPECT: int = 0              # lanes whose callbacks run IN this process
_LANE_LAST_TS: float = 0.0         # wall time of the last fence flush


def _lane_cache_topology(c: "Cloud") -> None:
    """Cache {lane: process_index} for the new cloud (called under _lock
    from init). On a pod only the LOCAL lanes' io_callbacks ever run in
    this process, so the fence-flush threshold is the local lane count."""
    global _LANE_SELF, _LANE_EXPECT
    self_idx = int(jax.process_index())
    topo = {i: int(getattr(d, "process_index", 0))
            for i, d in enumerate(c.mesh.devices.flat)}
    with _LANE_LOCK:
        _LANE_PROC.clear()
        _LANE_PROC.update(topo)
        _LANE_SELF = self_idx
        _LANE_EXPECT = sum(1 for pr in topo.values() if pr == self_idx)


def lane_timing_enabled() -> bool:
    """Per-lane timing is on by default for mesh-sharded programs;
    H2O3_LANE_TIMING=0 is the escape hatch. Evaluated at TRACE time — the
    cached sharded programs bake the choice in for their lifetime."""
    return os.environ.get("H2O3_LANE_TIMING", "1").lower() not in (
        "0", "false", "no")


def _lane_registry() -> dict:
    """Memoized central-registry families (the usual memoization stance:
    recording a fence must not take the registry registration lock)."""
    if not _LANE_REG:
        from ..runtime import metrics_registry as _reg

        _LANE_REG["skew"] = _reg.histogram(
            "h2o3_collective_skew_ms",
            "per-fence collective skew (ms): slowest lane's arrival lag "
            "behind the first arriver, per instrumented fence tag",
            labelnames=("tag",))
        _LANE_REG["lane_wait"] = _reg.histogram(
            "h2o3_collective_lane_wait_ms",
            "per-lane collective wait (ms): how long each fence waited on "
            "this lane (arrival lag behind the first arriver)",
            labelnames=("lane",))
        _LANE_REG["fences"] = _reg.counter(
            "h2o3_collective_fences",
            "instrumented collective fences recorded")
        _LANE_REG["stragglers"] = _reg.counter(
            "h2o3_stragglers",
            "straggler detections: fences streaks where one lane's wait "
            "persistently exceeded the median by H2O3_STRAGGLER_FACTOR",
            labelnames=("lane",))
    return _LANE_REG


def _lane_arrive_cb(tag: str, lane) -> np.float32:
    """io_callback target: runs ON the lane's execution thread the moment
    its local partial is ready. Stamps the arrival; flushes the fence
    record when every lane of the cloud has reported (or when a lane
    reports twice — a new fence started before a peer's callback landed)."""
    lane = int(lane)
    from ..runtime import faults as _faults

    try:
        _faults.check("mesh.lane_delay", lane=lane)
    except Exception:
        pass   # latency-only point; an injected error class is a misconfig
    if _faults.active():
        # rank death at fence N (pod chaos lane): a hard exit from inside
        # the arrival callback is exactly a process dying mid-collective —
        # peers are left at the rendezvous, which the supervisor's fence
        # deadline must abort. os._exit: no atexit/finalizers, like a kill.
        try:
            _faults.check("mesh.rank_kill", detail=f"lane{lane}", lane=lane)
        except Exception:
            os._exit(43)
    t = _time.perf_counter()
    actions = None
    with _LANE_LOCK:
        open_ = _LANE_OPEN.setdefault(tag, {})
        if lane in open_:
            actions = _flush_locked(tag)
            _LANE_OPEN[tag] = open_ = {}
        open_[lane] = t
        c = _cloud
        # flush when every lane THIS process will ever hear from has
        # reported: all lanes single-process, the local lanes on a pod
        # (remote lanes' callbacks run on their own ranks — waiting for
        # them here would leave every fence open forever)
        expect = _LANE_EXPECT or (c.size if c is not None else 0)
        if c is not None and len(open_) >= expect:
            acts2 = _flush_locked(tag)
            actions = (actions or []) + acts2 if acts2 else actions
    if actions:
        _run_lane_actions(actions)
    return _F32_ZERO


def _flush_locked(tag: str):
    """Fold one fence's arrivals into a record (+ detector update). Caller
    holds _LANE_LOCK; returns deferred registry/timeline actions so the
    lock never nests into other subsystems' locks."""
    global _LANE_SEQ, _LANE_LAST_TS
    arrivals = _LANE_OPEN.pop(tag, None)
    if not arrivals:
        return None
    _LANE_LAST_TS = _time.time()
    if len(arrivals) < 2:
        _LANE_LAST.clear()
        _LANE_LAST.update({lane: 0.0 for lane in arrivals})
        if not (len(arrivals) == 1 and _LANE_EXPECT == 1
                and len(_LANE_PROC) > 1):
            # incomplete fence (a lane re-reported before its local peers
            # landed): nothing comparable to record
            return None
        # 1-local-lane pod rank: this IS the complete local fence. There is
        # no within-rank skew to measure (peer lanes' callbacks run on
        # their own ranks), but the record itself is the pod observable:
        # the fences counter and skew series on every rank's scrape prove
        # that rank's collectives are moving — the fleet aggregator's
        # per-rank liveness and the watchdog's hang evidence both read
        # them — so record the fence with zero wait.
        lane0 = next(iter(arrivals))
        _LANE_SEQ += 1
        _LANE_RECORDS.append(dict(
            seq=_LANE_SEQ, ts=_time.time(), tag=tag,
            waits_ms={str(lane0): 0.0}, skew_ms=0.0))
        return [("fence", tag, 0.0, {lane0: 0.0})]
    tmin = min(arrivals.values())
    waits = {lane: (t - tmin) * 1e3 for lane, t in arrivals.items()}
    skew = max(waits.values())
    _LANE_SEQ += 1
    rec = dict(seq=_LANE_SEQ, ts=_time.time(), tag=tag,
               waits_ms={str(lv): round(w, 3) for lv, w in sorted(waits.items())},
               skew_ms=round(skew, 3))
    _LANE_RECORDS.append(rec)
    _LANE_LAST.clear()
    _LANE_LAST.update(waits)
    # straggler detection on this fence
    from ..runtime import env_float, env_int

    factor = env_float("H2O3_STRAGGLER_FACTOR", 4.0)
    floor_ms = env_float("H2O3_STRAGGLER_MIN_MS", 25.0)
    persist = env_int("H2O3_STRAGGLER_FENCES", 3)
    srt = sorted(waits.values())
    # LOWER median: the threshold must come from a typical healthy lane.
    # The upper middle would, on a 2-lane mesh, be the straggler's own
    # wait (threshold = 4x itself — the detector could never fire), and
    # on any even mesh where half the lanes are slow it would inflate
    # the threshold by the very skew being detected.
    median = srt[(len(srt) - 1) // 2]
    threshold = max(median * factor, floor_ms)
    actions = [("fence", tag, skew, dict(waits))]
    for lane, w in waits.items():
        if w > threshold:
            _LANE_STREAK[lane] = _LANE_STREAK.get(lane, 0) + 1
            if _LANE_STREAK[lane] == persist:
                _LANE_FIRED[lane] = _LANE_FIRED.get(lane, 0) + 1
                actions.append(("straggler", tag, lane,
                                dict(wait_ms=round(w, 1),
                                     median_ms=round(median, 1),
                                     factor=factor, fences=persist)))
        else:
            _LANE_STREAK[lane] = 0
    return actions


def _run_lane_actions(actions) -> None:
    try:
        reg = _lane_registry()
    except Exception:
        return
    for act in actions:
        if act[0] == "fence":
            _, tag, skew, waits = act
            reg["fences"].inc()
            reg["skew"].observe(skew, tag)
            for lane, w in waits.items():
                reg["lane_wait"].observe(w, str(lane))
        else:
            _, tag, lane, info = act
            reg["stragglers"].inc(1, str(lane))
            try:
                from ..runtime import tracing as _tracing
                from ..runtime.timeline import Timeline

                Timeline.record(
                    "straggler",
                    f"lane {lane} waited {info['wait_ms']}ms at '{tag}' "
                    f"fences (median {info['median_ms']}ms, "
                    f"factor {info['factor']})", lane=lane, **info)
                _tracing.record_span(f"straggler:lane{lane}", 0.0,
                                     kind="collective", lane=lane,
                                     tag=tag, **info)
            except Exception:
                pass


def lane_mark(x, axis_name: str, tag: str):
    """Attach the per-lane arrival stamp to `x` inside a sharded program:
    an io_callback carrying this lane's index, ordered BEFORE the
    downstream collective via an optimization_barrier data dependency
    (pure_callback would be DCE'd — its result is unused by the math).
    Identity on the values; returns `x` barrier-tied to the stamp."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback

    lane = jax.lax.axis_index(axis_name)
    t = io_callback(_functools.partial(_lane_arrive_cb, tag),
                    jax.ShapeDtypeStruct((), jnp.float32), lane,
                    ordered=False)
    x, _ = jax.lax.optimization_barrier((x, t))
    return x


def lane_seq() -> int:
    with _LANE_LOCK:
        return _LANE_SEQ


def lane_last_waits() -> dict:
    """{lane: wait_ms} naming the suspect lane of a hung collective —
    host-side dicts only (safe from a watchdog thread while the
    backend hangs). A fence currently OPEN (some lanes arrived, the
    collective still waiting on the rest) takes priority: its partial
    arrivals are reported, so the lanes MISSING from the dict are exactly
    the ones the fence is hung on. With no open fence, the most recent
    COMPLETED fence's waits."""
    with _LANE_LOCK:
        for open_ in _LANE_OPEN.values():
            if open_:
                tmin = min(open_.values())
                return {int(lv): round((t - tmin) * 1e3, 3)
                        for lv, t in sorted(open_.items())}
        return {int(lv): round(w, 3) for lv, w in _LANE_LAST.items()}


def lane_ranks() -> dict:
    """{lane: owning process index}, cached at init() — host dict only,
    safe from watchdog threads while the backend hangs."""
    with _LANE_LOCK:
        return dict(_LANE_PROC)


def lane_hang_report() -> dict:
    """The supervisor watchdog's hung-collective attribution: which
    lanes arrived at the currently-open fence, which are missing, and the
    RANKS owning the missing lanes (cached topology — never a jax call).

    On a pod each process only hears its own lanes, so the report is
    rank-local evidence: a missing LOCAL lane names this rank (its shard
    never reached the rendezvous); all local lanes arrived at the last
    fence but the program is hung → the suspects are the REMOTE ranks.
    Empty dict when no mesh topology was cached (no sharded fit ran)."""
    with _LANE_LOCK:
        topo = dict(_LANE_PROC)
        if not topo:
            return {}
        self_rank = _LANE_SELF
        local = sorted(lv for lv, pr in topo.items() if pr == self_rank)
        remote_ranks = sorted({pr for pr in topo.values() if pr != self_rank})
        out = dict(self_rank=self_rank, local_lanes=local,
                   n_ranks=len(set(topo.values())))
        if _LANE_LAST_TS:
            out["last_fence_age_s"] = round(_time.time() - _LANE_LAST_TS, 1)
        for tag, open_ in _LANE_OPEN.items():
            if open_:
                tmin = min(open_.values())
                missing = [lv for lv in local if lv not in open_]
                out.update(
                    open_fence=tag,
                    arrived={int(lv): round((t - tmin) * 1e3, 3)
                             for lv, t in sorted(open_.items())},
                    missing_local_lanes=missing,
                    suspect_ranks=([self_rank] if missing else remote_ranks))
                return out
        # no open fence: every local lane made its last rendezvous — if the
        # run is hung on a collective, the lanes never heard from are remote
        out.update(suspect_ranks=remote_ranks if remote_ranks else [])
        return out


def lane_records(since_seq: int = 0) -> list:
    with _LANE_LOCK:
        return [dict(r) for r in _LANE_RECORDS if r["seq"] > since_seq]


def lane_summary(since_seq: int = 0) -> dict:
    """Fold the fences recorded after `since_seq` into one summary (the
    per-fit skew embed: record_fit_plan tree fold, fit trace events): fence count, skew p50/max, and the worst lane."""
    recs = lane_records(since_seq)
    if not recs:
        return dict(fences=0)
    skews = sorted(r["skew_ms"] for r in recs)
    per_lane: dict = {}
    for r in recs:
        for lv, w in r["waits_ms"].items():
            per_lane.setdefault(lv, []).append(w)
    worst = max(per_lane, key=lambda lv: max(per_lane[lv]))
    return dict(
        fences=len(recs),
        skew_p50_ms=round(skews[len(skews) // 2], 3),
        skew_max_ms=round(skews[-1], 3),
        worst_lane=int(worst),
        per_lane_max_ms={lv: round(max(ws), 3)
                         for lv, ws in sorted(per_lane.items())},
    )


def lane_stats() -> dict:
    """The full lane-timing snapshot (the /3/Profiler `tree`-adjacent
    fold + dryrun assertions): enabled flag, totals, last fence, per-lane
    straggler streaks and firing counts, recent records tail."""
    with _LANE_LOCK:
        return dict(
            enabled=lane_timing_enabled(),
            fences=_LANE_SEQ,
            last={str(lv): round(w, 3) for lv, w in _LANE_LAST.items()},
            streaks={str(lv): n for lv, n in _LANE_STREAK.items() if n},
            stragglers={str(lv): n for lv, n in _LANE_FIRED.items()},
            records=[dict(r) for r in list(_LANE_RECORDS)[-8:]],
        )


def lane_reset() -> None:
    """Drop lane-timing state (tests). Registry families are monotone and
    stay — only the host-side rings/streaks reset."""
    global _LANE_SEQ, _LANE_LAST_TS
    with _LANE_LOCK:
        _LANE_SEQ = 0
        _LANE_LAST_TS = 0.0
        _LANE_OPEN.clear()
        _LANE_RECORDS.clear()
        _LANE_LAST.clear()
        _LANE_STREAK.clear()
        _LANE_FIRED.clear()
