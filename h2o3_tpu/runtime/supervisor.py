"""Elastic training supervisor — mid-fit checkpoints, hung-collective
abort, degrade-and-resume (docs/robustness.md "Recovery matrix").

The reference platform's HeartBeatThread + Paxos recovery keep a cloud
working when a node misbehaves; PR 10/15 made a dead or wedged rank
*visible* (`parallel/mesh.lane_hang_report`, `h2o3_fleet_peer_up`) but a
fit still hung forever on the open collective and lost every tree of
progress with it. This module closes that gap with three cooperating
pieces:

* **Fit checkpoints** — `models/shared_tree` snapshots its loop state
  (forest-so-far, live f32 margins/OOB accumulators, gain partial sum,
  scoring history, early-stop cursor) every ``H2O3_CKPT_TREES`` trees,
  and `models/estimator_engine` snapshots its ``while_loop`` carry at
  the QoS ``segment_stops`` boundaries. Snapshots are one ``.npz`` blob
  written through the persist SPI with the ``.part``+rename pattern and
  stamped with a **run fingerprint** (frame shape + params + seed + the
  shard plan S): a torn write is never restorable (the zip central
  directory and per-array CRCs fail the full-read validation) and a
  checkpoint from different data/params is ignored, exactly like the
  sweep records of PR 5. Restored margins are the LIVE f32 arrays, not a
  forest fast-forward — incremental per-tree adds round differently than
  `_margin_ffwd_jit`'s refold, and bit-identity to the undisturbed fit
  is the whole point.

* **Failure detection + abort** — `deadline_block` wraps
  ``jax.block_until_ready`` in a watcher so a fence whose peer died
  raises `CollectiveTimeout` within ``H2O3_FENCE_DEADLINE_S`` instead of
  waiting on the rendezvous forever; on breach the suspect ranks from
  `lane_hang_report`'s cached topology are marked DOWN in the fleet
  registry (their ``h2o3_fleet_peer_up`` series flips to 0), a Timeline
  event names them, and ``h2o3_supervisor_aborts`` / the detection-
  latency histogram record it. An optional background watcher
  (`start()`, launcher-armed on pods) fires the same detection for
  host-side hangs the fence wrapper cannot see.

* **Elastic resume** — the aborted/killed fit reloads the newest VALID
  checkpoint and continues. Per-rank shards are saved in the pod
  canonical row layout (parallel/distdata), so rank-ordered
  concatenation of the shard files IS the global padded array: a fit
  that lost ranks resumes on one host (``H2O3_TREE_SHARD=1`` degrade)
  bit-identical, because the shard plan S pinned in the checkpoint keeps
  the deterministic reduction grid unchanged. `CollectiveTimeout`
  subclasses ``TimeoutError`` so the trainpool's transient classifier
  retries the candidate — which resumes mid-fit instead of retraining
  from tree 0 (``totals.resumed_mid_fit`` in /3/Training/metrics).

Fault points: ``supervisor.ckpt_corrupt`` (truncates the serialized blob
before the atomic rename — restore must reject it), ``supervisor.fit_abort``
(raises at a chunk boundary — the in-process kill-and-resume pin), and
`parallel/mesh`'s ``mesh.rank_kill`` (hard-exits a rank at fence N — the
pod chaos drill of tests/test_supervisor.py). ``H2O3_CKPT=0`` is the escape hatch:
checkpointing fully off, bit-identical to pre-supervisor behavior.

State surfaces at ``GET /3/Supervisor`` (rest/server.py) and the
``h2o3_supervisor_*`` registry families.
"""

from __future__ import annotations

import io
import json
import os
import re
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from . import metrics_registry as _reg

__all__ = [
    "CollectiveTimeout", "ckpt_enabled", "ckpt_dir", "ckpt_every_trees",
    "fence_deadline_s", "run_fingerprint", "save_fit_checkpoint",
    "load_fit_checkpoint", "latest_fit_checkpoint", "deadline_block",
    "mark_ranks_down", "fit_started", "fit_finished", "pulse",
    "note_checkpoint", "note_mid_fit_resume", "note_abort", "snapshot",
    "start", "stop", "reset",
]


class CollectiveTimeout(TimeoutError):
    """A collective fence exceeded its deadline — a peer rank is dead or
    wedged. Subclasses ``TimeoutError`` (an ``OSError``) so the shared
    retry classifier treats the abort as TRANSIENT: the trainpool retries
    the candidate, which resumes from its newest fit checkpoint."""


# -- config ------------------------------------------------------------------

def ckpt_enabled() -> bool:
    """``H2O3_CKPT=0`` is the escape hatch: no snapshots, no restores,
    bit-identical to pre-supervisor behavior."""
    return os.environ.get("H2O3_CKPT", "1").strip() != "0"


def ckpt_dir() -> Optional[str]:
    """Checkpoint directory (``H2O3_CKPT_DIR``). Unset ⇒ mid-fit
    checkpointing is off — there is nowhere durable to put snapshots."""
    d = os.environ.get("H2O3_CKPT_DIR", "").strip()
    return d or None


def ckpt_every_trees() -> int:
    """Snapshot cadence for tree fits (``H2O3_CKPT_TREES``, default 25 —
    one checkpoint per default scoring chunk)."""
    try:
        return max(int(os.environ.get("H2O3_CKPT_TREES", "25")), 1)
    except ValueError:
        return 25


def fence_deadline_s() -> float:
    """Per-fence collective deadline (``H2O3_FENCE_DEADLINE_S``; 0 = no
    deadline — the pre-supervisor wait-forever behavior)."""
    try:
        return float(os.environ.get("H2O3_FENCE_DEADLINE_S", "0") or 0.0)
    except ValueError:
        return 0.0


# -- metrics -----------------------------------------------------------------

_REG: Dict = {}


def _registry() -> Dict:
    if not _REG:
        _REG["aborts"] = _reg.counter(
            "h2o3_supervisor_aborts",
            "hung-collective aborts: fences that exceeded the deadline and "
            "raised CollectiveTimeout instead of waiting forever")
        _REG["resumes"] = _reg.counter(
            "h2o3_supervisor_resumes",
            "mid-fit checkpoint restores (a fit continued past tree/"
            "iteration 0 from a prior snapshot)")
        _REG["ckpt_saves"] = _reg.counter(
            "h2o3_supervisor_ckpt_saves",
            "fit checkpoints atomically committed (.part+rename)")
        _REG["ckpt_rejects"] = _reg.counter(
            "h2o3_supervisor_ckpt_rejects",
            "checkpoint files rejected at restore (torn zip / CRC mismatch "
            "/ wrong run fingerprint / incomplete rank set)")
        _REG["marked_down"] = _reg.counter(
            "h2o3_supervisor_marked_down",
            "ranks marked down in the fleet registry by failure detection")
        _REG["detect_ms"] = _reg.histogram(
            "h2o3_supervisor_detect_ms",
            "failure detection latency (ms): deadline breach wall from "
            "fence dispatch to abort")
        _reg.bind_rest_field("supervisor", "totals.aborts",
                             "h2o3_supervisor_aborts")
        _reg.bind_rest_field("supervisor", "totals.resumes",
                             "h2o3_supervisor_resumes")
        _reg.bind_rest_field("supervisor", "totals.ckpt_saves",
                             "h2o3_supervisor_ckpt_saves")
        _reg.bind_rest_field("supervisor", "totals.ckpt_rejects",
                             "h2o3_supervisor_ckpt_rejects")
        _reg.bind_rest_field("supervisor", "totals.marked_down",
                             "h2o3_supervisor_marked_down")
    return _REG


# -- supervisor state machine ------------------------------------------------

_LOCK = threading.Lock()
_STATE: Dict = dict(state="idle", fit=None, heartbeat=None,
                    last_abort=None, last_resume=None, last_ckpt=None)


def fit_started(tag: str, fingerprint: str = "", total: int = 0) -> None:
    """idle → watching: a supervised fit entered its loop."""
    with _LOCK:
        _STATE["state"] = "watching"
        _STATE["fit"] = dict(tag=tag, fingerprint=fingerprint,
                             total=int(total), started=time.time())
        _STATE["heartbeat"] = None


def fit_finished(tag: str) -> None:
    """watching → idle (no-op when another fit already took over)."""
    with _LOCK:
        fit = _STATE.get("fit")
        if fit is not None and fit.get("tag") == tag:
            _STATE["state"] = "idle"
            _STATE["fit"] = None


def pulse(tag: str, step: int = 0) -> None:
    """Progress heartbeat from inside a supervised loop (chunk/segment/
    stream-block boundaries). The background watcher reads its age."""
    with _LOCK:
        _STATE["heartbeat"] = dict(tag=tag, step=int(step), ts=time.time())


def note_checkpoint(path: str, step: int, wall_s: float = 0.0) -> None:
    _registry()["ckpt_saves"].inc()
    with _LOCK:
        _STATE["last_ckpt"] = dict(path=path, step=int(step),
                                   wall_s=round(float(wall_s), 4),
                                   ts=time.time())


def note_mid_fit_resume(tag: str, step: int, restored: int = 0) -> None:
    """A fit restored a mid-fit snapshot and continued past step 0. Bumps
    the supervisor counter AND the trainpool's ``resumed_mid_fit`` total
    (the /3/Training/metrics face of the same event)."""
    _registry()["resumes"].inc()
    with _LOCK:
        _STATE["last_resume"] = dict(tag=tag, step=int(step),
                                     restored=int(restored), ts=time.time())
    try:
        from . import trainpool
        trainpool.bump_total("resumed_mid_fit")
    except Exception:
        pass
    try:
        from .timeline import Timeline
        Timeline.record("supervisor_resume", tag,
                        step=int(step), restored=int(restored))
    except Exception:
        pass
    try:
        from . import tracing
        tracing.event("supervisor_resume", tag=tag, step=int(step))
    except Exception:
        pass


def note_abort(tag: str, latency_s: float, suspects: List[int]) -> Dict:
    """Record one hung-collective abort: counters, detection-latency
    histogram, Timeline, state machine. Returns the abort record."""
    reg = _registry()
    reg["aborts"].inc()
    reg["detect_ms"].observe(float(latency_s) * 1e3)
    rec = dict(tag=tag, latency_s=round(float(latency_s), 3),
               suspect_ranks=[int(r) for r in suspects], ts=time.time())
    with _LOCK:
        _STATE["state"] = "aborted"
        _STATE["last_abort"] = rec
    try:
        from .timeline import Timeline
        Timeline.record("supervisor_abort", tag,
                        latency_s=rec["latency_s"],
                        suspect_ranks=rec["suspect_ranks"])
    except Exception:
        pass
    try:
        from . import tracing
        tracing.event("supervisor_abort", tag=tag,
                      latency_s=rec["latency_s"],
                      suspects=",".join(map(str, rec["suspect_ranks"])))
    except Exception:
        pass
    return rec


def mark_ranks_down(ranks: List[int], reason: str = "") -> None:
    """Flip the suspect ranks' ``h2o3_fleet_peer_up`` series to 0 (the
    launcher self-registers ranks as ``rank{N}``) and emit a Timeline
    event — failure detection must reach the fleet scrape immediately,
    not at the next failed scrape."""
    if not ranks:
        return
    reg = _registry()
    try:
        from . import fleet
        for r in ranks:
            fleet.mark_down(f"rank{int(r)}", reason or "supervisor")
            reg["marked_down"].inc()
    except Exception:
        pass


def snapshot() -> Dict:
    """The ``GET /3/Supervisor`` document: state machine + last abort/
    resume/checkpoint + counters + resolved config."""
    reg = _registry()
    with _LOCK:
        st = {k: (dict(v) if isinstance(v, dict) else v)
              for k, v in _STATE.items()}
    st["totals"] = dict(
        aborts=reg["aborts"].value(),
        resumes=reg["resumes"].value(),
        ckpt_saves=reg["ckpt_saves"].value(),
        ckpt_rejects=reg["ckpt_rejects"].value(),
        marked_down=reg["marked_down"].value(),
    )
    st["detect_ms"] = reg["detect_ms"].summary()
    st["config"] = dict(
        ckpt_enabled=ckpt_enabled(), ckpt_dir=ckpt_dir(),
        ckpt_trees=ckpt_every_trees(),
        fence_deadline_s=fence_deadline_s(),
        watcher=_WATCHER is not None,
    )
    return st


def reset() -> None:
    """Back to idle, watcher stopped (tests). Registry counters are
    monotone and stay."""
    stop()
    with _LOCK:
        _STATE.update(state="idle", fit=None, heartbeat=None,
                      last_abort=None, last_resume=None, last_ckpt=None)


# -- run fingerprint ---------------------------------------------------------

def run_fingerprint(**fields) -> str:
    """Stable digest of everything that must match for a checkpoint to be
    restorable: frame identity (global rows + column names + response),
    the param subset that shapes the loop, the seed, and the shard plan S
    (the deterministic reduction grid). Deliberately NOT a content hash —
    it must be computable identically on a 2-rank pod and its 1-host
    degraded resume, where no process holds all the bytes."""
    import hashlib

    def _san(v):
        if isinstance(v, (np.integer,)):
            return int(v)
        if isinstance(v, (np.floating,)):
            return float(v)
        if isinstance(v, (list, tuple)):
            return [_san(x) for x in v]
        if isinstance(v, dict):
            return {str(k): _san(x) for k, x in sorted(v.items())}
        return v

    blob = json.dumps(_san(fields), sort_keys=True,
                      separators=(",", ":"), default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


# -- fit checkpoint store ----------------------------------------------------

_META_KEY = "__h2o3_meta__"
_FNAME_RE = re.compile(
    r"^fitckpt_(?P<tag>[A-Za-z0-9]+)_(?P<fp>[0-9a-f]+)"
    r"_s(?P<step>\d+)_r(?P<rank>\d+)of(?P<nproc>\d+)\.npz$")


def _ckpt_name(tag: str, fingerprint: str, step: int, rank: int,
               nproc: int) -> str:
    return (f"fitckpt_{tag}_{fingerprint[:12]}_s{step:08d}"
            f"_r{rank}of{nproc}.npz")


def save_fit_checkpoint(directory: str, tag: str, fingerprint: str,
                        step: int, arrays: Dict[str, np.ndarray],
                        meta: Optional[Dict] = None, rank: int = 0,
                        nproc: int = 1, keep: int = 2) -> str:
    """Atomically commit one snapshot through the persist SPI: serialize
    to an in-memory npz, write ``<name>.part``, rename into place. The
    ``supervisor.ckpt_corrupt`` fault truncates the blob BEFORE the
    rename — the committed file is then torn exactly like a mid-write
    crash, and restore must reject it via the full-read validation."""
    from . import faults, persist

    t0 = time.perf_counter()
    meta = dict(meta or {})
    meta.update(tag=tag, fingerprint=fingerprint, step=int(step),
                rank=int(rank), nproc=int(nproc), ts=time.time())
    payload = {k: np.asarray(v) for k, v in arrays.items()}
    payload[_META_KEY] = np.frombuffer(
        json.dumps(meta, default=float).encode(), dtype=np.uint8).copy()
    buf = io.BytesIO()
    np.savez(buf, **payload)
    blob = buf.getvalue()
    try:
        faults.check("supervisor.ckpt_corrupt", detail=f"{tag}:s{step}")
    except Exception:
        blob = blob[: max(len(blob) // 2, 1)]
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, _ckpt_name(tag, fingerprint, step,
                                              rank, nproc))
    part = path + ".part"
    be = persist.for_uri(part)
    with be.open(part, "wb") as f:
        f.write(blob)
    os.replace(part, path)
    note_checkpoint(path, step, time.perf_counter() - t0)
    _gc_old(directory, tag, fingerprint, rank, nproc, keep)
    return path


def _gc_old(directory: str, tag: str, fingerprint: str, rank: int,
            nproc: int, keep: int) -> None:
    """Keep the newest `keep` snapshots for this (tag, fp, rank) — older
    ones only waste disk once a newer valid one exists."""
    try:
        mine = []
        for f in os.listdir(directory):
            m = _FNAME_RE.match(f)
            if (m and m.group("tag") == tag
                    and m.group("fp") == fingerprint[:12]
                    and int(m.group("rank")) == rank
                    and int(m.group("nproc")) == nproc):
                mine.append((int(m.group("step")), f))
        for _, f in sorted(mine)[:-keep] if keep > 0 else []:
            os.unlink(os.path.join(directory, f))
    except OSError:
        pass


def load_fit_checkpoint(path: str) -> (Dict, Dict):
    """Load + VALIDATE one snapshot: every array is fully materialized
    (forcing the zip CRC check over all bytes) and the embedded meta must
    parse. Raises on any damage — callers treat any exception as
    'not restorable'."""
    with np.load(path) as z:
        arrays = {k: np.array(z[k]) for k in z.files if k != _META_KEY}
        meta = json.loads(bytes(z[_META_KEY].tobytes()).decode())
    return arrays, meta


def latest_fit_checkpoint(directory: str, tag: str,
                          fingerprint: str) -> Optional[Dict]:
    """Newest restorable snapshot for (tag, fingerprint): the highest
    step whose COMPLETE rank set loads and validates. Returns
    ``dict(step, nproc, shards=[arrays per rank 0..nproc-1], meta)`` or
    None. Torn files, wrong fingerprints, and incomplete rank sets are
    counted into ``ckpt_rejects`` and skipped — never restored."""
    reg = _registry()
    try:
        names = os.listdir(directory)
    except OSError:
        return None
    groups: Dict[tuple, Dict[int, str]] = {}
    for f in names:
        m = _FNAME_RE.match(f)
        if (not m or m.group("tag") != tag
                or m.group("fp") != fingerprint[:12]):
            continue
        key = (int(m.group("step")), int(m.group("nproc")))
        groups.setdefault(key, {})[int(m.group("rank"))] = f
    for (step, nproc), ranks in sorted(groups.items(), reverse=True):
        if set(ranks) != set(range(nproc)):
            reg["ckpt_rejects"].inc()
            continue
        shards, metas, ok = [], [], True
        for r in range(nproc):
            try:
                arrays, meta = load_fit_checkpoint(
                    os.path.join(directory, ranks[r]))
            except Exception:
                ok = False
                break
            if (meta.get("fingerprint") != fingerprint
                    or int(meta.get("step", -1)) != step):
                ok = False
                break
            shards.append(arrays)
            metas.append(meta)
        if not ok:
            reg["ckpt_rejects"].inc()
            continue
        return dict(step=step, nproc=nproc, shards=shards, meta=metas[0])
    return None


# -- deadline'd collective fence ---------------------------------------------

def deadline_block(x, timeout_s: Optional[float] = None,
                   tag: str = "collective", _blocker=None):
    """``jax.block_until_ready`` with a deadline. The block runs on a
    daemon worker; if it misses the deadline the caller raises
    `CollectiveTimeout` (the abort the surviving ranks need — the wedged
    dispatch itself cannot be cancelled, but the DRIVER regains control,
    marks the suspects down, and moves to resume). `_blocker` is the
    injectable wait for in-process tests."""
    timeout_s = fence_deadline_s() if timeout_s is None else timeout_s
    if _blocker is None:
        import jax

        def _blocker():
            jax.block_until_ready(x)
    if not timeout_s or timeout_s <= 0:
        _blocker()
        return x
    done = threading.Event()
    err: List[BaseException] = []

    def _run():
        try:
            _blocker()
        except BaseException as e:  # noqa: BLE001 — re-raised on the caller
            err.append(e)
        finally:
            done.set()

    t0 = time.perf_counter()
    threading.Thread(target=_run, daemon=True,
                     name="h2o3-fence-deadline").start()
    if not done.wait(timeout_s):
        latency = time.perf_counter() - t0
        suspects: List[int] = []
        try:
            from ..parallel import mesh
            suspects = list(mesh.lane_hang_report().get("suspect_ranks")
                            or [])
        except Exception:
            pass
        mark_ranks_down(suspects, reason="hung_collective")
        note_abort(tag, latency, suspects)
        raise CollectiveTimeout(
            f"collective fence '{tag}' exceeded its {timeout_s:.1f}s "
            f"deadline (waited {latency:.1f}s; suspect ranks: "
            f"{suspects or 'unknown'})")
    if err:
        raise err[0]
    return x


# -- background watcher ------------------------------------------------------

_WATCHER: Optional[threading.Thread] = None
_WATCH_STOP = threading.Event()


def start(poll_s: float = 2.0) -> bool:
    """Start the background failure watcher (launcher-armed on pods when
    a fence deadline is configured). It polls `lane_hang_report`: an open
    fence older than the deadline means a peer died mid-collective — the
    suspects are marked down and the abort recorded even if the driver
    thread is still stuck (detection must not depend on the victim)."""
    global _WATCHER
    with _LOCK:
        if _WATCHER is not None:
            return False
        _WATCH_STOP.clear()
        _WATCHER = threading.Thread(target=_watch_loop, args=(poll_s,),
                                    daemon=True, name="h2o3-supervisor")
        _WATCHER.start()
        return True


def stop() -> None:
    global _WATCHER
    with _LOCK:
        w = _WATCHER
        _WATCHER = None
    if w is not None:
        _WATCH_STOP.set()
        w.join(timeout=5.0)


def _watch_loop(poll_s: float) -> None:
    fired_tag = None
    while not _WATCH_STOP.wait(poll_s):
        deadline = fence_deadline_s()
        if deadline <= 0:
            continue
        try:
            from ..parallel import mesh
            rep = mesh.lane_hang_report()
        except Exception:
            continue
        open_tag = rep.get("open_fence")
        age = rep.get("last_fence_age_s")
        if (open_tag and age is not None and age > deadline
                and open_tag != fired_tag):
            # one detection per open fence: the wedged fence stays open, so
            # without the tag latch every poll would re-count the same hang
            fired_tag = open_tag
            suspects = list(rep.get("suspect_ranks") or [])
            mark_ranks_down(suspects, reason="heartbeat_stall")
            note_abort("watcher", float(age), suspects)
        elif not open_tag:
            fired_tag = None
