"""Device-resident iteration engine for the non-tree estimators (ISSUE 15).

The tree path got fused device-resident kernels (PR 7), deterministic mesh
sharding (PR 9) and streaming (PR 11); GLM, K-Means, PCA/GLRM and
DeepLearning stayed seed-shaped: every fit re-extracted and re-uploaded its
float matrix and iterated in a host Python loop with a blocking device sync
per iteration. This module is the shared spine that routes the same
treatment to them:

- **One matrix, one upload** — `host_matrix` / `device_matrix` /
  `design_matrix` resolve the standardized float design through the
  dataset cache's new ``std`` layer (keyed by frame fingerprint + x +
  standardization/impute/expansion params + pad/shard grid), so every CV
  fold and sweep candidate sharing a frame reuses ONE extraction and ONE
  device artifact instead of paying `fit_transform` + H2D per fit.
- **Shard plan** — `shard_plan()` is the one mode decision (mirroring
  `shared_tree._shard_plan`): a multi-device single-process cloud runs row
  reductions as S canonical ordered blocks merged by
  `ops.histogram.ordered_axis_fold` ("mesh"); ``H2O3_EST_SHARD=1`` forces
  the identical blocked structure on one device ("blocks") so an N-device
  fit is bit-identical to the 1-device forced-shard lane; ``=0`` is the
  escape hatch. Multi-process clouds and ``H2O3_EST_LEGACY=1`` keep the
  pre-engine paths.
- **Observability** — per-fit plans (`record_fit`: algo, path, iterations,
  converged-on-device, matrix cache hit/miss, n_shards) in a bounded ring
  surfaced at /3/Profiler's ``est`` fold, `h2o3_est_dispatch{algo,path}` /
  `h2o3_est_iterations{algo}` registry families, and the fused iteration
  wall booked into the ``est_iter`` phase bucket (`iter_phase`).

The estimators' fused whole-iteration programs themselves (GLM IRLS as a
`lax.while_loop`, K-Means Lloyd, PCA power iteration, GLRM alternating
solves, DL's `lax.scan` epochs) live in their own modules; this engine
holds what they share.
"""

from __future__ import annotations

import math
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Optional, Tuple

import numpy as np


def legacy() -> bool:
    """``H2O3_EST_LEGACY=1`` restores the host per-iteration estimator
    paths as the parity comparator (for GLM lambda search that is
    the host IRLS loop — the pre-device-program shape)."""
    return os.environ.get("H2O3_EST_LEGACY", "").lower() in ("1", "true",
                                                            "yes")


def shard_blocks() -> int:
    return max(int(os.environ.get("H2O3_EST_SHARD_BLOCKS", "8") or 8), 1)


def shard_plan(ndev: int, multiproc: bool) -> Tuple[str, int]:
    """(shard_mode, n_shards) for one estimator fit — the ONE place the
    decision is made (mirrors `shared_tree._shard_plan`).

    "mesh": multi-device cloud — S ordered blocks spread over the lanes,
    merged by `ordered_axis_fold`. "blocks": 1 device,
    ``H2O3_EST_SHARD=1`` — the same S-block structure forced on one chip
    (the bit-identity comparator lane). "off": plain full-row reductions
    (1 device default — bit-exact with the pre-engine math). The legacy
    comparator always reports "off". Multi-process POD clouds (ISSUE 18)
    report "mesh" like any multi-device cloud — the caller decides whether
    its fit supports the pod lane (GLM does; estimators that keep the
    pre-engine multi-process paths gate on their own `engine_on`)."""
    env = os.environ.get("H2O3_EST_SHARD", "").strip()
    if legacy() or env == "0":
        return "off", 0
    if multiproc:
        base = shard_blocks()
        return "mesh", base * ndev // math.gcd(base, ndev)
    base = shard_blocks()
    if ndev > 1:
        return "mesh", base * ndev // math.gcd(base, ndev)
    if env == "1":
        return "blocks", base
    return "off", 0


def pad_rows(n: int, n_shards: int) -> int:
    """Rows padded to the canonical block grid (zero-filled, zero-weight
    rows — exact no-ops in every weighted reduction)."""
    if n_shards <= 0:
        return n
    from ..parallel.mesh import pad_to_multiple

    return pad_to_multiple(n, n_shards)


def local_plan(cloud, shard_mode: str, n_shards: int):
    """(local_blocks, axis_name) for one fused program under the shard
    plan — the ONE derivation of 'how many ordered blocks does THIS
    lane/device compute, and over which mesh axis do partials gather'
    (mesh: n_shards spread over the lanes; blocks: all on one device;
    off: 0 = plain full-row reductions)."""
    from ..parallel.mesh import ROWS_AXIS

    local_blocks = (n_shards // cloud.size if shard_mode == "mesh"
                    else n_shards)
    axis = (ROWS_AXIS if shard_mode == "mesh" and cloud.size > 1 else None)
    return local_blocks, axis


def block_slices(nrows: int, local_blocks: int):
    """The canonical per-block row slices of one lane's rows — every
    estimator's blocked partials must cut the same grid or two fits
    sharing S would not be bit-comparable."""
    rows = nrows // local_blocks
    return [slice(i * rows, (i + 1) * rows) for i in range(local_blocks)]


def fold_blocks(parts, axis_name: Optional[str], tag: Optional[str] = None):
    """Deterministic ordered merge of per-block partials — the PR 9
    blocked-fold contract, re-exported so estimator programs and the tree
    path can never drift apart."""
    from ..ops.histogram import ordered_axis_fold

    return ordered_axis_fold(parts, axis_name, timing_tag=tag)


# -- cached matrices through the dataset cache's std layer --------------------

def cache_enabled() -> bool:
    from . import dataset_cache

    return dataset_cache.enabled() and not legacy()


def _expansion_key(frame, x, use_all: bool) -> bool:
    """use_all_factor_levels only changes the design when a categorical
    column exists — normalize it out of the key for all-numeric frames so
    GLM (use_all=False) and K-Means (use_all=True) share one artifact."""
    if not use_all:
        return False
    return any(frame.vec(c).type == "enum" for c in x)


@contextmanager
def _design_span():
    """The fit's ``fit.design`` span, as a decorator of the three matrix
    resolvers: from the frame's columns to the matrix the fit iterates on
    (the cache look-up with its frame fingerprint; on a miss the DataInfo
    statistics, packing, H2D and the expand program's dispatch). Attrs:
    ``cache`` (hit/miss/off, set by `_resolve`) and ``bytes_h2d`` (what
    `phases.accounted_h2d` counted meanwhile, process-wide).
    `device_matrix` resolves its host layer inside its own span: the two
    layers are one span."""
    from ..runtime import phases as _phases
    from ..runtime import tracing as _tracing

    outer = _tracing.current()
    if outer is not None and outer.name == "fit.design":
        yield
        return
    h2d0 = _phases.snapshot().get("bytes_h2d", 0)
    with _tracing.span("fit.design", kind="fit") as sp:
        try:
            yield
        finally:
            sp.annotate(
                bytes_h2d=_phases.snapshot().get("bytes_h2d", 0) - h2d0)


def _resolve(frame, x, skey: tuple, build):
    """One artifact through the dataset cache's std layer, or built afresh
    when the cache is off; the open ``fit.design`` span is told which."""
    from ..runtime import tracing as _tracing

    sp = _tracing.current()
    if not cache_enabled():
        sp.annotate(cache="off")
        return build()[0]
    from . import dataset_cache

    built = []

    def counted():
        built.append(True)
        return build()

    out = dataset_cache.std_artifact(frame, x, skey, counted)
    sp.annotate(cache="miss" if built else "hit")
    return out


@_design_span()
def host_matrix(frame, x, *, standardize: bool, use_all: bool = False,
                impute: bool = True):
    """(DataInfo, standardized float32 host matrix) for (frame, x) —
    cached. The host artifact backs K-Means init draws and is the parent
    of `device_matrix`."""
    from .model_base import DataInfo

    ua = _expansion_key(frame, x, use_all)

    def build():
        dinfo = DataInfo(frame, x, standardize=standardize,
                         use_all_factor_levels=ua, impute_missing=impute)
        X = dinfo.fit_transform(frame)
        return (dinfo, X), int(X.nbytes), "host"

    return _resolve(frame, x, ("host", bool(standardize), ua, bool(impute)),
                    build)


@_design_span()
def device_matrix(frame, x, *, standardize: bool, use_all: bool = False,
                  impute: bool = True, n_shards: int = 0, n_devices: int = 1):
    """(DataInfo, device design matrix) — the cached host matrix uploaded
    ONCE (padded to the block grid, row-sharded over the mesh when
    n_devices > 1). Consumers that iterate on the plain standardized
    matrix (K-Means, PCA, GLRM's quadratic path) share this artifact; the
    numbers are bitwise the `fit_transform` values the legacy paths use,
    so "off"-mode fused fits stay bit-comparable."""
    ua = _expansion_key(frame, x, use_all)
    npad = pad_rows(frame.nrow, n_shards)
    # resolve the host layer OUTSIDE the device layer's build: std_artifact
    # holds the cache entry's (non-reentrant) lock around the builder, and
    # both layers live on the same entry
    dinfo, X = host_matrix(frame, x, standardize=standardize, use_all=ua,
                           impute=impute)

    def build():
        Xp = X
        if npad != X.shape[0]:
            Xp = np.concatenate(
                [X, np.zeros((npad - X.shape[0], X.shape[1]), X.dtype)])
        from ..runtime import phases as _phases

        def _put():
            import jax
            import jax.numpy as jnp

            if n_devices > 1:
                from ..parallel import mesh as cloudlib

                return jax.device_put(jnp.asarray(Xp),
                                      cloudlib.cloud().row_sharding())
            return jnp.asarray(Xp)

        Xd = _phases.accounted_h2d(_put, int(Xp.nbytes))
        return (dinfo, Xd), int(Xp.nbytes), "device"

    return _resolve(frame, x, ("dev", bool(standardize), ua, bool(impute),
                               int(npad), int(n_devices)), build)


@_design_span()
def design_matrix(frame, x, *, standardize: bool, use_all: bool = False,
                  add_intercept: bool = False, n_shards: int = 0,
                  n_devices: int = 1):
    """(DataInfo, device design matrix) via `DataInfo.device_design` — the
    compact-upload + on-device-expansion path GLM and DeepLearning already
    run (small-range integer columns travel at 1-2 bytes/value, the dense
    one-hot never crosses the link), now cached so a sweep expands and
    uploads once. Bitwise the same artifact those estimators built per-fit
    before. On a mesh (``n_devices > 1``, one process) the statistics are
    fitted from the compact columns exactly as on one device and the packs
    go up row-sharded: the dense host design is never built, so a frame
    whose design no single chip (and no host buffer) holds still fits."""
    from .model_base import DataInfo

    ua = _expansion_key(frame, x, use_all)
    npad = pad_rows(frame.nrow, n_shards)

    def build():
        dinfo = DataInfo(frame, x, standardize=standardize,
                         use_all_factor_levels=ua, impute_missing=True)
        # the two lanes differ in where the rows land, not in how the
        # statistics are fitted
        if n_devices > 1:
            from ..parallel import mesh as cloudlib

            layout = dict(cloud=cloudlib.cloud(), quota=npad)
        else:
            layout = dict(row_bucket=n_shards or 0)
        Xd = dinfo.device_design(frame, fit=True,
                                 add_intercept=add_intercept, **layout)
        nbytes = int(np.prod(Xd.shape)) * Xd.dtype.itemsize
        return (dinfo, Xd), nbytes, "device"

    return _resolve(frame, x, ("design", bool(standardize), ua,
                               bool(add_intercept), int(npad),
                               int(n_devices)), build)


# -- per-cloud fused-program cache --------------------------------------------

_PROG_LOCK = threading.Lock()


def cached_program(cloud, key: tuple, build):
    """Get-or-build one fused estimator program, cached on the cloud (like
    `shared_tree._sharded_event_loss_fn`) so sweep candidates share traces
    and a mesh rebuild drops the stale executables with the old cloud."""
    with _PROG_LOCK:
        cache = cloud.__dict__.setdefault("_est_fns_cache", {})
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = build()
        return fn


# -- observability ------------------------------------------------------------

_PLAN_LOCK = threading.Lock()
_PLANS: "deque" = deque(maxlen=16)
_REG: dict = {}


def _registry() -> dict:
    """Memoized registry families (the usual stance: recording a fit must
    not take the registry registration lock)."""
    if not _REG:
        from ..runtime import metrics_registry as _reg

        _REG["dispatch"] = _reg.counter(
            "h2o3_est_dispatch",
            "estimator-engine fit dispatches by algo and resolved path "
            "(fused/fused_blocks/fused_mesh/legacy/host)",
            labelnames=("algo", "path"))
        _REG["iterations"] = _reg.counter(
            "h2o3_est_iterations",
            "estimator iterations executed inside fused device programs "
            "(whole-fit loops — the host observed only the final state)",
            labelnames=("algo",))
    return _REG


def record_fit(algo: str, path: str, *, iterations: Optional[int] = None,
               converged: Optional[bool] = None,
               matrix_cache: Optional[str] = None, n_shards: int = 0,
               n_devices: int = 1, wall_s: Optional[float] = None,
               **extra) -> dict:
    """Record one estimator fit's plan: how it dispatched (fused vs
    legacy, shard mode), how many device iterations it ran, whether the
    on-device convergence test fired, and whether the standardized matrix
    came out of the cache. Ring + counters; the ring is the /3/Profiler
    ``est`` fold."""
    plan = dict(algo=algo, ts=time.time(), path=path,
                n_shards=int(n_shards), n_devices=int(n_devices))
    if iterations is not None:
        plan["iterations"] = int(iterations)
    if converged is not None:
        plan["converged"] = bool(converged)
    if matrix_cache is not None:
        plan["matrix_cache"] = matrix_cache
    if wall_s is not None:
        plan["wall_s"] = round(float(wall_s), 4)
    plan.update(extra)
    with _PLAN_LOCK:
        _PLANS.append(plan)
    try:
        reg = _registry()
        reg["dispatch"].inc(1, algo, path)
        if iterations:
            reg["iterations"].inc(int(iterations), algo)
    except Exception:
        pass
    try:
        from ..runtime import tracing as _tracing

        _tracing.event("est_fit", algo=algo, path=path,
                       iterations=iterations, n_shards=n_shards)
    except Exception:
        pass
    return plan


def matrix_cache_state(before: dict) -> str:
    """"hit"/"miss" verdict for the std layer between two
    `dataset_cache.snapshot()` reads around a fit's matrix resolution."""
    from . import dataset_cache

    after = dataset_cache.snapshot()
    if after.get("std_misses", 0) > before.get("std_misses", 0):
        return "miss"
    if after.get("std_hits", 0) > before.get("std_hits", 0):
        return "hit"
    return "off"


def est_stats() -> dict:
    """Per-fit plans + cumulative dispatch/iteration counters (the
    /3/Profiler ``est`` fold). Pure counter read — never fits anything."""
    with _PLAN_LOCK:
        plans = list(_PLANS)
    out = dict(plans=plans, dispatch={}, iterations={})
    try:
        reg = _registry()
        out["dispatch"] = {"/".join(lv): c.value()
                           for lv, c in reg["dispatch"].children().items()}
        out["iterations"] = {lv[0]: c.value()
                             for lv, c in reg["iterations"].children().items()}
    except Exception:
        pass
    return out


def reset_plans() -> None:
    """Drop the plan ring (tests). Registry counters are monotone and stay."""
    with _PLAN_LOCK:
        _PLANS.clear()


# -- QoS dispatch segmentation (ISSUE 19) -------------------------------------

def max_iters_per_dispatch() -> int:
    """Cap on ``while_loop`` iterations per device dispatch.

    Under the multi-tenant QoS gate a fused estimator fit becomes a
    RESUMABLE sequence of bounded device programs: each segment runs at
    most this many iterations (the loop cond gains ``it < stop_at``), the
    carry round-trips on device between segments, and the call site visits
    ``qos.yield_point("est_segment")`` between dispatches so serving never
    waits behind an unbounded fused loop. 0 = unbounded (one fused
    dispatch — the default whenever QoS is off). ``stop_at = max_iter``
    makes segmentation the identity: same trip count, same body, same
    bits (pinned)."""
    import os

    try:
        cap = int(os.environ.get("H2O3_QOS_EST_ITERS_PER_DISPATCH", "0"))
    except ValueError:
        cap = 0
    if cap > 0:
        return cap
    from ..runtime import qos

    return 32 if qos.enabled() else 0


def segment_stops(max_iter: int):
    """The ``stop_at`` schedule for one fused fit under the dispatch cap:
    ``[cap, 2·cap, …, max_iter]``, or ``[max_iter]`` when uncapped (the
    single-dispatch identity path)."""
    max_iter = int(max_iter)
    cap = max_iters_per_dispatch()
    if cap <= 0 or cap >= max_iter:
        return [max_iter]
    return list(range(cap, max_iter, cap)) + [max_iter]


# -- mid-fit carry snapshots (ISSUE 20) ---------------------------------------
# A fused estimator fit is a sequence of bounded device programs under the
# QoS dispatch cap (segment_stops above); each boundary is also a natural
# checkpoint: the while_loop carry IS the whole fit state. Snapshotting it
# through the supervisor store makes a killed kmeans/GLM fit resume at the
# last completed segment instead of iteration 0 — and because the carry
# round-trips the exact f32 values, the resumed fit is bit-identical to an
# undisturbed one (the remaining segments run the same body on the same
# carry). Disabled (fingerprint None) unless H2O3_CKPT_DIR is set.

def segment_fingerprint(algo: str, **fields):
    """Run fingerprint for one fused fit's carry snapshots, or None when
    fit checkpointing is off — the single gate the call sites branch on."""
    from ..runtime import supervisor as _sup

    if not (_sup.ckpt_enabled() and _sup.ckpt_dir()):
        return None
    return _sup.run_fingerprint(algo=algo, **fields)


def _carry_host(a):
    """One carry leaf to host bits. Replicated process-spanning arrays
    (the only multi-host carry shape — β/centroids are replicated) read
    their local copy; everything else is directly materializable."""
    import jax
    import jax.numpy as jnp

    a = jnp.asarray(a)
    if getattr(a, "is_fully_addressable", True):
        return np.asarray(a)
    return np.asarray(a.addressable_data(0))


def segment_carry_save(algo: str, fingerprint, stop: int, carry) -> None:
    """Snapshot the fused loop's carry tuple at a completed segment
    boundary (``stop`` iterations done). No-op when fingerprint is None."""
    if fingerprint is None:
        return
    import jax

    from ..runtime import supervisor as _sup

    arrays = {f"c{i}": _carry_host(c) for i, c in enumerate(carry)}
    _sup.save_fit_checkpoint(
        _sup.ckpt_dir(), f"est{algo}", fingerprint, int(stop), arrays,
        meta=dict(ncarry=len(carry)),
        rank=jax.process_index(), nproc=jax.process_count())


def segment_carry_restore(algo: str, fingerprint):
    """Newest valid carry snapshot for this fit → ``(stop, carry_tuple)``
    or None. The carry is replicated, so any rank's shard reconstructs it;
    multi-process clouds take a consensus vote first (a rank-divergent
    restore would deadlock the segment collectives)."""
    if fingerprint is None:
        return None
    import jax.numpy as jnp

    from ..parallel import distdata
    from ..runtime import supervisor as _sup

    rec = _sup.latest_fit_checkpoint(_sup.ckpt_dir(), f"est{algo}",
                                     fingerprint)
    ok = rec is not None
    if distdata.multiprocess():
        ok = distdata.global_all(bool(ok))
    if not ok:
        return None
    sh = rec["shards"][0]
    n = int(rec["meta"].get("ncarry", len(sh)))
    carry = tuple(jnp.asarray(sh[f"c{i}"]) for i in range(n))
    _sup.note_mid_fit_resume(f"est{algo}", int(rec["step"]),
                             restored=int(rec["step"]))
    return int(rec["step"]), carry


@contextmanager
def iter_phase():
    """The fit's ``fit.iterate`` span: the dispatch of a fused iteration
    loop up to the read of its final state. ONE context manager for every
    engine estimator (GLM, K-Means, PCA, GLRM); it yields the span, so
    a call site may annotate ``iterations`` / ``segments``. The span's
    wall is also booked into the ``est_iter`` phase bucket (compile/trace
    time the first call triggers is subtracted — it is already accounted
    by the monitoring listener)."""
    from ..runtime import phases as _phases
    from ..runtime import tracing as _tracing

    comp0 = _phases.totals(_phases.COMPILE_KEYS)
    with _tracing.span("fit.iterate", kind="fit") as sp:
        try:
            yield sp
        finally:
            el = (time.perf_counter() - sp.t0
                  - (_phases.totals(_phases.COMPILE_KEYS) - comp0))
            _phases.add("est_iter", max(el, 0.0))
