"""Dataset-artifact cache — bin/pack/transfer a training frame ONCE per sweep.

Every tree fit runs the same prep pipeline over its training frame:
`frame_to_matrix` (Frame → float64 matrix), `build_bins` (quantize to bin
codes), sub-byte bit-packing and the H2D upload of the code matrix. A grid
sweep or AutoML run repeats that per candidate even though every candidate
shares ONE (frame, x) pair — exactly the waste XGBoost's `gpu_hist` avoids
by quantizing once and reusing the compressed binned matrix across all
boosting work ("XGBoost: Scalable GPU Accelerated Learning", PAPERS.md).

This module is the sweep-level analog: a fingerprinted multi-layer cache

- **matrix**: key(frame, x) → (X float64, is_categorical, domains)
- **bins**: + (nbins, histogram_type[, seed for Random]) → `BinnedMatrix`
- **device**: + (npad rows, pack mode, shard layout) → the device-resident
  packed code matrix, so repeat candidates skip the pack + upload
  entirely. On a single-process multi-device cloud the artifact is the
  row-sharded jax.Array itself (per-shard placement reused across the
  sweep, ISSUE 12). Multi-process POD fits cache their global row-sharded
  array too (ISSUE 18): the canonical row exchange runs eagerly inside the
  fit, so the cached builder is collective-free (a per-rank hit/miss
  divergence can never strand a rank in a collective) and each rank's
  entry accounts only its local shards' bytes.
- **std**: + a caller-supplied standardization key (standardize /
  use_all_factor_levels / impute / intercept / pad grid, see
  `models/estimator_engine.py`) → the standardized float design matrix
  the non-tree estimators iterate on — the fitted `DataInfo` plus either
  the host float32 matrix or the device-resident (possibly row-sharded)
  design array (ISSUE 15). GLM, K-Means, PCA, GLRM and DeepLearning —
  and every CV fold and sweep candidate sharing a frame — reuse ONE
  upload instead of re-extracting and re-uploading per fit.
- **targets**: + the response, weights and offset columns (each by name
  and Vec/buffer identity) and a caller-supplied key (problem, class
  count, distribution, estimator mode, custom objective, class balancing,
  padded rows, device layout, see `models/shared_tree.py`) → the tree
  fit's padded device response and weights, the padded device offset, the
  initial margin f0 and the class-balancing priors. A sweep's candidates
  then skip the host build of those vectors and their upload. Only
  single-process fits whose codes the **device** layer holds use it: the
  multi-process builds run collectives, which no cache builder may.

Fingerprint: frame identity (id + DKV key + a weakref guard), row count,
the frame's in-place mutation counter (`Frame._touch` bumps it), the x
column list, and each column's Vec/buffer identity — replacing a column or
mutating the frame invalidates, while Rapids-style functional ops produce
new frames (new ids) naturally.

Eviction: LRU over entries with both an entry cap
(``H2O3_DATASET_CACHE_ENTRIES``, default 4) and a byte budget
(``H2O3_DATASET_CACHE_MB``, default 1024, host+device bytes). Dead frames
drop their entries via weakref callback. ``H2O3_DATASET_CACHE=0``
disables caching entirely.

Stats (hits/misses per layer — matrix, bins, device, blocks, std, targets —
and evictions) feed ``GET /3/Training/metrics``.
"""

from __future__ import annotations

import os
import threading
import weakref
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..runtime import memory_ledger as _memory

_LOCK = threading.RLock()
_ENTRIES: "OrderedDict[tuple, _Entry]" = OrderedDict()
_STATS = dict(matrix_hits=0, matrix_misses=0, bins_hits=0, bins_misses=0,
              device_hits=0, device_misses=0, blocks_hits=0,
              blocks_misses=0, std_hits=0, std_misses=0, targets_hits=0,
              targets_misses=0, evictions=0)


def enabled() -> bool:
    return os.environ.get("H2O3_DATASET_CACHE", "1") not in ("0", "false",
                                                            "no")


def _caps() -> Tuple[int, int]:
    """(max entries, max bytes) — read per call so tests can env-tune."""
    ents = int(os.environ.get("H2O3_DATASET_CACHE_ENTRIES", 4))
    mb = float(os.environ.get("H2O3_DATASET_CACHE_MB", 1024))
    return max(ents, 1), int(mb * 1e6)


class _Artifact:
    """One cached artifact whose size the cache cannot read off a single
    array: a standardized design, the fitted DataInfo-equivalent
    `aux` plus the matrix itself (host np.ndarray or a device jax.Array), or
    a tree fit's targets. `space` says which side of the link most of the
    bytes live on, for the ledger's host/device split."""

    __slots__ = ("value", "_nbytes", "space")

    def __init__(self, value, nbytes: int, space: str = "host"):
        self.value = value
        self._nbytes = int(nbytes)
        self.space = space

    def nbytes(self) -> int:
        return self._nbytes


class _Entry:
    __slots__ = ("frame_ref", "key", "matrix", "bins", "device", "blocks",
                 "std", "targets", "lock", "owner_base", "__weakref__")

    def __init__(self, frame, key):
        self.frame_ref = weakref.ref(frame, lambda _: _drop(key))
        self.key = key
        self.matrix = None                      # (X, is_cat, doms)
        self.bins: Dict[tuple, object] = {}     # bkey -> BinnedMatrix
        self.device: Dict[tuple, object] = {}   # (bkey, npad) -> jax array
        self.blocks: Dict[tuple, object] = {}   # (bkey, npad, ...) -> BlockStore
        self.std: Dict[tuple, _Artifact] = {}  # skey -> _Artifact
        self.targets: Dict[tuple, _Artifact] = {}  # tkey -> _Artifact
        self.lock = threading.Lock()            # serializes builds per entry
        self.owner_base = ""                    # memory-ledger owner prefix

    def nbytes(self) -> int:
        total = 0
        if self.matrix is not None:
            total += int(self.matrix[0].nbytes)
        for bm in self.bins.values():
            total += int(bm.codes.nbytes)
        for arr in self.device.values():
            total += _arr_nbytes(arr)
        for st in self.blocks.values():
            total += int(st.nbytes_total())
        for art in (*self.std.values(), *self.targets.values()):
            total += art.nbytes()
        return total


_LAYERS = ("matrix", "bins", "device", "blocks", "std", "targets")


def _arr_nbytes(arr) -> int:
    """Per-PROCESS resident bytes of a cached device artifact: a pod fit's
    global row-sharded array holds only this rank's shards locally, and the
    per-rank ledger/caps must see that 1/N footprint (ISSUE 18)."""
    try:
        if getattr(arr, "is_fully_addressable", True) is False:
            return sum(int(s.data.nbytes) for s in arr.addressable_shards)
    except Exception:
        pass
    return int(np.prod(arr.shape)) * arr.dtype.itemsize


def _register_ledger(e: "_Entry", frame) -> None:
    """Memory-ledger owners for one cache entry: `dataset_cache:<fp>:<layer>`
    per layer, byte callbacks through a weakref (the ledger must never pin
    an evicted entry alive), referent = the owning frame."""
    from ..runtime import memory_ledger as ml

    e.owner_base = f"dataset_cache:{ml.fingerprint(e.key)}"
    wr = weakref.ref(e)

    def _layer_fn(layer):
        def _bytes():
            ent = wr()
            if ent is None:
                return (0, 0)
            return ml.measure(getattr(ent, layer))
        return _bytes

    for layer in _LAYERS:
        ml.register(f"{e.owner_base}:{layer}", kind="dataset_cache",
                    bytes_fn=_layer_fn(layer), referent=frame,
                    type_name=layer)


def _release_entry(e: "_Entry", trigger: str) -> None:
    """Unregister an entry's ledger owners + emit ONE eviction event with
    the bytes actually freed and why (cap/pressure/weakref/clear) — cache
    thrash becomes visible in /3/Timeline and /3/Trace instead of silent."""
    if not e.owner_base:
        return
    from ..runtime import memory_ledger as ml

    try:
        freed = e.nbytes()
    except Exception:
        freed = 0
    ml.record_event("evict", e.owner_base, freed, trigger=trigger,
                    kind="dataset_cache",
                    space="device" if e.device else "host")
    for layer in _LAYERS:
        ml.unregister(f"{e.owner_base}:{layer}")
    # close block stores so their spill FILES go with the entry — a
    # dropped entry that left files behind would (correctly) surface as a
    # `<owner>:spill` leak, but the cache releasing an entry is the
    # orderly path, not the leak
    for st in list(e.blocks.values()):
        try:
            st.close()
        except Exception:
            pass


def _drop(key) -> None:
    with _LOCK:
        e = _ENTRIES.pop(key, None)
    if e is not None:
        try:
            _release_entry(e, "weakref")
        except Exception:
            # interpreter teardown: a frame dying at exit fires this
            # weakref callback after module globals are gone — nothing
            # left to account to
            pass


def _column_guard(frame, name: str) -> tuple:
    """A column by name and Vec/buffer identity: replacing the column, or
    its buffer, changes the guard."""
    v = frame.vec(name)
    return (name, id(v),
            id(v.data) if getattr(v, "data", None) is not None else 0)


def _frame_key(frame, x: Tuple[str, ...]) -> tuple:
    cols = tuple(_column_guard(frame, n) for n in x)
    return (id(frame), frame.key, int(frame.nrow),
            int(getattr(frame, "_version", 0)), x, cols)


def _entry_for(frame, x: Tuple[str, ...]) -> "_Entry":
    key = _frame_key(frame, x)
    with _LOCK:
        e = _ENTRIES.get(key)
        if e is not None and e.frame_ref() is frame:
            _ENTRIES.move_to_end(key)
            return e
        e = _ENTRIES[key] = _Entry(frame, key)
        _register_ledger(e, frame)
        _evict_locked(keep=key)
        return e


def _pop_entry_locked(key, trigger: str) -> None:
    e = _ENTRIES.pop(key, None)
    if e is None:
        return
    _STATS["evictions"] += 1
    _release_entry(e, trigger)


def _evict_locked(keep=None) -> None:
    """LRU-evict entries other than `keep` until both caps are met, then
    keep shedding while the memory ledger reports pressure above
    `H2O3_MEM_EVICT_PRESSURE` (the byte-side twin of admission shedding)."""
    # Iterate snapshots: _LOCK is reentrant, so a frame's weakref death
    # callback (_drop) triggered by GC mid-iteration in THIS thread can pop
    # from _ENTRIES even while we hold the lock.
    max_entries, max_bytes = _caps()
    victims = [k for k in list(_ENTRIES) if k != keep]
    while victims and len(_ENTRIES) > max_entries:
        _pop_entry_locked(victims.pop(0), "cap")
    while victims and sum(e.nbytes() for e in list(_ENTRIES.values())) > max_bytes:
        _pop_entry_locked(victims.pop(0), "cap")
    from ..runtime import qos as _qos

    # ONE pressure snapshot decides — qos.pressure_view(), the same view
    # serving admission reads, so shed-serving can never be true here
    # while evict-training-artifacts is false (pressure is RSS/HBM-budget
    # dominated — it cannot drop mid-loop just because entries were
    # unregistered, so re-reading per victim would only burn a full
    # accounting pass under _LOCK per pop): past the threshold, DEVICE
    # blocks shed FIRST (ISSUE 14 — a shed block keeps its host copy and
    # costs only a re-upload, the cheapest byte to give back), then HOST
    # blocks spill to disk (round 19 — the spilled copy is kept, so a
    # re-shed is free and only a restore pays a read), then every LRU
    # victim entry, oldest first — training artifacts always go before
    # serving sheds (the eviction threshold sits below the serving one)
    if (victims or any(e.blocks for e in list(_ENTRIES.values()))) \
            and _qos.pressure_view().evict_cache:
        for e in list(_ENTRIES.values()):
            for st in list(e.blocks.values()):
                st.shed(trigger="pressure")
        for e in list(_ENTRIES.values()):
            for st in list(e.blocks.values()):
                try:
                    st.shed_host(trigger="pressure")
                except Exception:
                    pass
        while victims:
            _pop_entry_locked(victims.pop(0), "pressure")


def _bins_key(nbins: int, histogram_type: str, seed) -> tuple:
    ht = "UniformAdaptive" if histogram_type in ("AUTO", None) \
        else str(histogram_type)
    # only Random binning draws from the seed; other types share across seeds
    return (int(nbins), ht, int(seed) if ht == "Random" else None)


def matrix(frame, x, builder: Callable[[], tuple]):
    """(X, is_categorical, domains) for (frame, x) — cached."""
    e = _entry_for(frame, tuple(x))
    with e.lock:
        if e.matrix is not None:
            with _LOCK:
                _STATS["matrix_hits"] += 1
            return e.matrix
        with _LOCK:
            _STATS["matrix_misses"] += 1
        built = builder()
        # publish under _LOCK: nbytes()/snapshot() iterate entry dicts
        # holding only _LOCK, so mutations must not race them (lock order
        # is always entry.lock → _LOCK, never reversed)
        with _LOCK:
            e.matrix = built
        _memory.record_event("alloc", f"{e.owner_base}:matrix",
                             int(built[0].nbytes), trigger="miss",
                             kind="dataset_cache")
    with _LOCK:
        _evict_locked(keep=e.key)
    return e.matrix


def bins(frame, x, nbins: int, histogram_type: str, seed,
         builder: Callable[[], object]):
    """`BinnedMatrix` for (frame, x, nbins, histogram_type) — cached."""
    e = _entry_for(frame, tuple(x))
    bkey = _bins_key(nbins, histogram_type, seed)
    with e.lock:
        bm = e.bins.get(bkey)
        if bm is not None:
            with _LOCK:
                _STATS["bins_hits"] += 1
            return bm
        with _LOCK:
            _STATS["bins_misses"] += 1
        bm = builder()
        with _LOCK:   # see matrix(): publish vs nbytes()/snapshot() races
            e.bins[bkey] = bm
        _memory.record_event("alloc", f"{e.owner_base}:bins",
                             int(bm.codes.nbytes), trigger="miss",
                             kind="dataset_cache")
    with _LOCK:
        _evict_locked(keep=e.key)
    return bm


def device_codes(frame, x, nbins: int, histogram_type: str, seed, npad: int,
                 builder: Callable[[], object], pack_bits: int = 0,
                 n_devices: int = 1):
    """Device-resident (padded) code matrix — cached so repeat candidates
    skip the pack + H2D upload. With `pack_bits` > 0 the cached artifact
    is the `ops.packing` packed word matrix (2-4× smaller resident HBM,
    ISSUE 7); with `n_devices` > 1 it is the ROW-SHARDED jax.Array over
    the 1-D hosts mesh (ISSUE 12) — each shard resident on its chip,
    padded to the mesh multiple by the caller. The packing mode and the
    shard layout are part of the key, so packed vs full-width consumers
    (e.g. a legacy-flag comparator run) and 1-device vs N-shard consumers
    never share an entry. `builder` does the pack/upload/placement and
    its own byte accounting on a miss."""
    e = _entry_for(frame, tuple(x))
    dkey = (_bins_key(nbins, histogram_type, seed), int(npad),
            int(pack_bits), int(n_devices))
    with e.lock:
        arr = e.device.get(dkey)
        if arr is not None:
            with _LOCK:
                _STATS["device_hits"] += 1
            return arr
        with _LOCK:
            _STATS["device_misses"] += 1
        arr = builder()
        with _LOCK:   # see matrix(): publish vs nbytes()/snapshot() races
            e.device[dkey] = arr
        _memory.record_event(
            "alloc", f"{e.owner_base}:device", _arr_nbytes(arr),
            trigger="miss", kind="dataset_cache", space="device")
    with _LOCK:
        _evict_locked(keep=e.key)
    return arr


def blocked_codes(frame, x, nbins: int, histogram_type: str, seed, npad: int,
                  builder: Callable[[], object], pack_bits: int = 0,
                  n_blocks: int = 0):
    """Row-BLOCKED packed code artifact (a `models.block_store.BlockStore`)
    — the out-of-core materialization of `device_codes` (ISSUE 14): packed
    sub-byte blocks live on host, a bounded LRU resident set lives on
    device, and the whole store is accounted through this entry's
    ``dataset_cache:<fp>:blocks`` ledger layer (the store itself does not
    register a second owner). Cached per (bins key, npad, pack mode, block
    grid) so a sweep's candidates share ONE blocked pack; the block grid
    aligns with the PR 9 shard layout, so a later sharded consumer shares
    block boundaries. `builder` packs the blocks on a miss."""
    e = _entry_for(frame, tuple(x))
    dkey = (_bins_key(nbins, histogram_type, seed), int(npad),
            int(pack_bits), int(n_blocks))
    with e.lock:
        st = e.blocks.get(dkey)
        if st is not None:
            with _LOCK:
                _STATS["blocks_hits"] += 1
            return st
        with _LOCK:
            _STATS["blocks_misses"] += 1
        st = builder()
        with _LOCK:   # see matrix(): publish vs nbytes()/snapshot() races
            e.blocks[dkey] = st
        _memory.record_event("alloc", f"{e.owner_base}:blocks",
                             int(st.host_bytes()), trigger="miss",
                             kind="dataset_cache")
    with _LOCK:
        _evict_locked(keep=e.key)
    return st


def _artifact(layer: str, frame, x, key: tuple,
              builder: Callable[[], tuple]):
    """Look-up of an `_Artifact` layer (`std`, `targets`) — cached.
    `builder` returns ``(value, nbytes, space)`` on a miss."""
    e = _entry_for(frame, tuple(x))
    arts = getattr(e, layer)
    with e.lock:
        art = arts.get(key)
        if art is not None:
            with _LOCK:
                _STATS[f"{layer}_hits"] += 1
            return art.value
        with _LOCK:
            _STATS[f"{layer}_misses"] += 1
        art = _Artifact(*builder())
        with _LOCK:   # see matrix(): publish vs nbytes()/snapshot() races
            arts[key] = art
        _memory.record_event("alloc", f"{e.owner_base}:{layer}",
                             art.nbytes(), trigger="miss",
                             kind="dataset_cache", space=art.space)
    with _LOCK:
        _evict_locked(keep=e.key)
    return art.value


def std_artifact(frame, x, skey: tuple, builder: Callable[[], tuple]):
    """Standardized-design artifact for (frame, x, skey) — cached.
    `skey` carries the standardization/impute/expansion parameters
    (composed by `models/estimator_engine.py` — the ONE place the key
    layout lives); `builder` returns ``(value, nbytes, space)`` on a miss,
    where `value` is whatever the engine wants back (typically a
    ``(DataInfo, matrix)`` pair) and `space` is ``"host"`` or ``"device"``
    for the ledger's split. Every estimator fit and CV fold sharing the
    (frame, x, params) triple then reuses one extraction + one upload."""
    return _artifact("std", frame, x, tuple(skey), builder)


def targets(frame, x, columns, tkey: tuple, builder: Callable[[], object]):
    """A tree fit's targets for (frame, x) — cached. `columns` names the
    response, weights and offset columns (None where a fit has none); each
    joins the key by name and Vec/buffer identity. `tkey` carries
    everything else the build reads (composed by `models/shared_tree.py`).
    `builder` returns the artifact on a miss: device arrays and small host
    arrays, measured here once for the ledger."""
    key = (tuple(None if c is None else _column_guard(frame, c)
                 for c in columns), tuple(tkey))

    def sized():
        value = builder()
        host, dev = _memory.measure(value)
        return value, host + dev, "device" if dev >= host else "host"

    return _artifact("targets", frame, x, key, sized)


def snapshot() -> Dict:
    with _LOCK:
        stats = dict(_STATS)
        entries = len(_ENTRIES)
        nbytes = sum(e.nbytes() for e in list(_ENTRIES.values()))
    stats.update(entries=entries, bytes=int(nbytes), enabled=enabled())
    return stats


def clear() -> None:
    """Drop every entry (tests / explicit memory release)."""
    with _LOCK:
        doomed = list(_ENTRIES.values())
        _ENTRIES.clear()
    for e in doomed:
        _release_entry(e, "clear")


def reset_stats() -> None:
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0
