"""BlockStore — the row-blocked packed code matrix of the out-of-core path.

"Fits in HBM" stops being the dataset ceiling (ROADMAP item 2, following
"Out-of-Core GPU Gradient Boosting", arXiv 2005.09148): the sub-byte packed
bin-code matrix lives on HOST as equal row-blocks, and only a bounded
RESIDENT SET of blocks lives on device at any moment. The streamed tree
driver (`models/tree_stream.py`) walks blocks in canonical order — block
boundaries are the PR 9 deterministic-reduction block grid, so a streamed
histogram pass folds the same per-block partials in the same order as the
in-core ``shard_mode="blocks"`` fit and stays BIT-IDENTICAL to it.

Accounting and shedding:

- the store is a **memory-ledger owner** (``block_store:<id>`` standalone,
  or folded into its ``dataset_cache:<fp>:blocks`` layer when the dataset
  cache holds it): host block bytes and resident device bytes are
  attributed like every other subsystem's.
- the resident set is LRU-bounded by a byte budget
  (``H2O3_STREAM_BUDGET_MB``, default: half the device capacity the ledger
  sees) and **sheds device blocks first** when
  ``memory_ledger.pressure()`` crosses ``H2O3_MEM_EVICT_PRESSURE`` — the
  `_evict_locked`-style response, except a shed block costs only a future
  re-upload (the host copy remains), so it is always the cheapest byte to
  give back. Every eviction lands in the Timeline/trace as a ``memory``
  event (owner, bytes, trigger), mirroring the dataset-cache events.
- uploads are double-buffer friendly: ``prefetch(b+1)`` dispatches the
  next block's H2D while the caller's kernel consumes block ``b`` (the
  `_score_event_async` dispatch-before-block pattern); transfer seconds
  land in the new ``h2d_stream`` phase bucket and upload/evict/reuse
  counters + streamed bytes feed the Prometheus scrape and the per-fit
  tree fold at ``/3/Profiler``.

The disk tier (round 19) adds the third level of the LRU: host blocks
that overflow ``H2O3_STREAM_HOST_BUDGET_MB`` (ledger-derived default:
half the host budget; ``H2O3_TREE_OOC_DISK=0`` disables the tier) SPILL
through the persist layer as atomic ``.part``+rename files and stream
back through ``Persist.open_resuming`` — a torn or injected
``persist.read`` failure resumes at the current offset under the shared
retry policy instead of failing the fit. ``prefetch`` goes asynchronous
once blocks live on disk, so the disk→host read of block ``b+1``
overlaps block ``b``'s H2D and compute. Restored bytes are byte-identical
to what was packed, so a spilled fit sharing the block grid stays
BIT-IDENTICAL to in-core. Spill files are ledger-visible as
``<owner>:spill`` owners in the new ``disk`` space — a store dropped
without ``close()`` leaves files behind and surfaces as a leak.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
import weakref
from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from ..ops import packing
from ..runtime import env_float
from ..runtime import memory_ledger as _ml
from ..runtime import persist as _persist
from ..runtime import phases as _phases

_TOTALS_LOCK = threading.Lock()
# process-lifetime stream totals — the bench/loadgen record embed next to
# the memory embeds (`streamed_bytes`, `resident_block_peak`)
_TOTALS = {"streamed_bytes": 0, "resident_block_peak": 0,
           "spilled_bytes": 0, "restored_bytes": 0,
           "resident_host_peak": 0}

_REG: Dict = {}


def _registry() -> Dict:
    """Memoized registry families (the usual lazy-memoization stance)."""
    if not _REG:
        from ..runtime import metrics_registry as reg

        _REG["blocks"] = reg.counter(
            "h2o3_tree_stream_blocks",
            "out-of-core code blocks by lifecycle event "
            "(uploaded/evicted/reused)",
            labelnames=("event",))
        _REG["bytes"] = reg.counter(
            "h2o3_tree_stream_bytes",
            "bytes streamed host->device by the out-of-core tree path")
        _REG["resident_peak"] = reg.gauge(
            "h2o3_tree_stream_resident_peak_bytes",
            "high watermark of device-resident out-of-core block bytes")
        _REG["spill_blocks"] = reg.counter(
            "h2o3_tree_spill_blocks",
            "disk-tier block events (spilled: host->disk write or host "
            "drop with a disk copy kept; restored: disk->host read)",
            labelnames=("event",))
        _REG["spill_bytes"] = reg.counter(
            "h2o3_tree_spill_bytes",
            "disk-tier bytes by direction (spill: written host->disk; "
            "restore: read disk->host)",
            labelnames=("direction",))
        _REG["spill_host_peak"] = reg.gauge(
            "h2o3_tree_spill_resident_host_peak_bytes",
            "high watermark of host-resident out-of-core block bytes "
            "while the disk tier is active")
    return _REG


def stream_budget_bytes() -> int:
    """The resident-set byte budget of the out-of-core path:
    ``H2O3_STREAM_BUDGET_MB`` when set, else half the device capacity the
    memory ledger sees (``memory_stats()`` limit on real chips;
    ``H2O3_DEVICE_BUDGET_MB`` / host budget on census backends) — the
    other half stays free for margins, histograms and the forest pack."""
    mb = env_float("H2O3_STREAM_BUDGET_MB", 0.0)
    if mb > 0:
        return int(mb * 1e6)
    return max(_ml.device_capacity_bytes() // 2, 1)


def stream_host_budget_bytes() -> int:
    """The HOST-resident byte budget of the disk spill tier:
    ``H2O3_STREAM_HOST_BUDGET_MB`` when set, else half the ledger's host
    budget (``H2O3_MEM_BUDGET_MB`` / MemTotal) — packed blocks past it
    spill to disk through the persist layer. 0 (or
    ``H2O3_TREE_OOC_DISK=0``) disables the tier: every block stays
    host-resident, the pre-round-19 behavior."""
    if os.environ.get("H2O3_TREE_OOC_DISK", "") == "0":
        return 0
    mb = env_float("H2O3_STREAM_HOST_BUDGET_MB", 0.0)
    if mb > 0:
        return int(mb * 1e6)
    return max(_ml._host_budget_bytes() // 2, 1)


def process_totals() -> Dict:
    """Cumulative stream totals for record embeds (0s when never used)."""
    with _TOTALS_LOCK:
        return dict(_TOTALS)


def _account_totals(nbytes: int = 0, resident: int = 0) -> None:
    with _TOTALS_LOCK:
        _TOTALS["streamed_bytes"] += int(nbytes)
        if resident > _TOTALS["resident_block_peak"]:
            _TOTALS["resident_block_peak"] = int(resident)


def _account_spill_totals(spilled: int = 0, restored: int = 0,
                          host_peak: int = 0) -> None:
    with _TOTALS_LOCK:
        _TOTALS["spilled_bytes"] += int(spilled)
        _TOTALS["restored_bytes"] += int(restored)
        if host_peak > _TOTALS["resident_host_peak"]:
            _TOTALS["resident_host_peak"] = int(host_peak)


class BlockStore:
    """Packed row-blocks across three LRU tiers: a bounded device resident
    set, a bounded host set, and persist-backed spill files on disk."""

    _IDS = iter(range(1 << 62))

    def __init__(self, host_blocks: List[np.ndarray], block_rows: int,
                 pack_bits: int, owner: str = "",
                 budget_bytes: Optional[int] = None,
                 host_budget_bytes: Optional[int] = None,
                 register: bool = True):
        self.host_blocks = list(host_blocks)
        self.n_blocks = len(self.host_blocks)
        self.block_rows = int(block_rows)
        self.pack_bits = int(pack_bits)
        self.owner = owner or f"block_store:{next(self._IDS)}"
        # resolved ONCE: the default consults the memory ledger's device
        # probe (an O(live-arrays) census walk on CPU backends) — far too
        # heavy for the per-miss hot path in get()
        self._budget = (int(budget_bytes) if budget_bytes is not None
                        else stream_budget_bytes())
        # host-tier budget (0 disables the disk tier); sizes and dtypes
        # are pinned up front because a spilled slot holds None
        self._host_budget = (int(host_budget_bytes)
                             if host_budget_bytes is not None
                             else stream_host_budget_bytes())
        self._block_nbytes = [int(hb.nbytes) for hb in self.host_blocks]
        self._block_meta = [(hb.shape, hb.dtype) for hb in self.host_blocks]
        self._lock = threading.Lock()
        self._resident: "OrderedDict[int, object]" = OrderedDict()
        self._resident_bytes = 0
        self._window_peak = 0
        # host LRU: block id -> None for host-resident blocks, in LRU order
        self._host_lru: "OrderedDict[int, None]" = OrderedDict()
        for b in range(self.n_blocks):
            self._host_lru[b] = None
        self._host_bytes_resident = sum(self._block_nbytes)
        self._host_window_peak = self._host_bytes_resident
        self._on_disk: set = set()          # blocks with a spill file
        self._spill_dir: Optional[str] = None
        self._spill_registered = False
        self._pending: set = set()          # async prefetches in flight
        self._pool = None
        # serializes the restore slow path (evict-then-read-then-insert)
        # so concurrent prefetch + compute restores cannot both claim the
        # same headroom and push the watermark over the host budget
        self._restore_lock = threading.Lock()
        self.counters = dict(uploaded=0, evicted=0, reused=0,
                             bytes_streamed=0, spilled=0, restored=0,
                             bytes_spilled=0, bytes_restored=0)
        self.resident_peak_bytes = 0
        self.host_resident_peak_bytes = self._host_bytes_resident
        self._registered = False
        if register:
            # standalone owner (cache-disabled fits): the referent is the
            # store itself, so a dropped store retires its owner
            wr = weakref.ref(self)

            def _bytes():
                st = wr()
                if st is None:
                    return (0, 0)
                return st.host_bytes(), st.resident_bytes()

            _ml.register(self.owner, kind="block_store", bytes_fn=_bytes,
                         referent=self, type_name="blocks")
            self._registered = True
        if self._host_budget > 0:
            self._enforce_host_budget(keep=())

    # -- construction ------------------------------------------------------

    @classmethod
    def from_codes(cls, codes: np.ndarray, n_blocks: int, pack_bits: int,
                   **kw) -> "BlockStore":
        """Blocked (and sub-byte packed) store from a padded full-width
        code matrix. Each block is packed independently via
        `ops.packing.pack_host_range` — O(block) transients, the
        streaming-ingest contract — and, with ``pack_bits=0`` (nbins too
        wide to pack), blocks are contiguous row copies."""
        n = codes.shape[0]
        if n % n_blocks:
            raise ValueError(f"{n} rows do not divide into {n_blocks} blocks")
        rows = n // n_blocks
        if pack_bits and rows % packing.GROUP_ROWS[pack_bits]:
            raise ValueError(
                f"block rows {rows} not aligned to the {pack_bits}-bit "
                "pack group")
        blocks = []
        for b in range(n_blocks):
            if pack_bits:
                blocks.append(packing.pack_host_range(
                    codes, pack_bits, b * rows, (b + 1) * rows))
            else:
                blocks.append(np.ascontiguousarray(codes[b * rows:
                                                         (b + 1) * rows]))
        return cls(blocks, rows, pack_bits, **kw)

    # -- sizes -------------------------------------------------------------

    def host_bytes(self) -> int:
        """HOST-RESIDENT block bytes (spilled slots hold None and do not
        count — their bytes live in `disk_bytes()`)."""
        with self._lock:
            return self._host_bytes_resident

    def resident_bytes(self) -> int:
        with self._lock:
            return self._resident_bytes

    def disk_bytes(self) -> int:
        """Bytes held by spill files (kept even after a restore — the
        'spilled copies kept' rule makes a later host shed free)."""
        with self._lock:
            return sum(self._block_nbytes[b] for b in self._on_disk)

    def nbytes_total(self) -> int:
        return self.host_bytes() + self.resident_bytes()

    def budget_bytes(self) -> int:
        """Resident budget, floored at two blocks so the double buffer
        (consume b, prefetch b+1) always fits."""
        floor = 2 * max(self._block_nbytes, default=0)
        return max(self._budget, floor)

    def host_budget_bytes(self) -> int:
        """Host-tier budget (0: disk tier disabled), floored at two
        blocks so the disk double buffer (restore b+1 while b computes)
        always fits."""
        if self._host_budget <= 0:
            return 0
        floor = 2 * max(self._block_nbytes, default=0)
        return max(self._host_budget, floor)

    def peak_window_start(self) -> None:
        """Reset the per-window resident peaks — a fit sharing a cached
        store marks its own window so `peak_window_bytes()` reports THIS
        fit's watermark, not the store-lifetime one."""
        with self._lock:
            self._window_peak = self._resident_bytes
            self._host_window_peak = self._host_bytes_resident

    def peak_window_bytes(self) -> int:
        with self._lock:
            return self._window_peak

    def host_peak_window_bytes(self) -> int:
        with self._lock:
            return self._host_window_peak

    # -- disk tier ---------------------------------------------------------

    def _spill_dir_path(self) -> str:
        """Lazily-created per-store spill directory; also registers the
        ``<owner>:spill`` ledger owner whose bytes come from the
        FILESYSTEM (not the store object), so a store dropped without
        ``close()`` leaves a dead owner that still reports disk bytes —
        the leak detector's cue."""
        if self._spill_dir is None:
            base = os.environ.get("H2O3_SPILL_DIR") or tempfile.gettempdir()
            safe = self.owner.replace(":", "_").replace("/", "_")
            # rank-unique (ISSUE 18): pod ranks on different hosts can
            # share H2O3_SPILL_DIR (NFS) and pids collide across hosts
            try:
                import jax

                rank = int(jax.process_index())
            except Exception:
                rank = 0
            d = os.path.join(base,
                             f"h2o3_spill_r{rank}_{os.getpid()}_{safe}")
            os.makedirs(d, exist_ok=True)
            self._spill_dir = d
        if not self._spill_registered:
            self._spill_registered = True
            d = self._spill_dir

            def _disk():
                try:
                    with os.scandir(d) as it:
                        return (0, 0, sum(e.stat().st_size for e in it
                                          if e.is_file()))
                except OSError:
                    return (0, 0, 0)

            _ml.register(f"{self.owner}:spill", kind="block_store",
                         bytes_fn=_disk, referent=self, type_name="spill")
        return self._spill_dir

    def _spill_path(self, b: int) -> str:
        return os.path.join(self._spill_dir_path(), f"block{b}.bin")

    def _write_spill(self, b: int, hb: np.ndarray) -> None:
        """host→disk through the persist layer: write ``.part``, fsync,
        atomic rename — the registry publish pattern, so a crash mid-spill
        never leaves a torn file where a restore would read it."""
        path = self._spill_path(b)
        part = path + ".part"
        be = _persist.for_uri(path)
        t0 = time.perf_counter()
        fh = be.open(part, "wb")
        try:
            fh.write(hb.tobytes() if not hb.flags.c_contiguous
                     else memoryview(hb).cast("B"))
            fh.flush()
            try:
                os.fsync(fh.fileno())
            except (OSError, AttributeError):
                pass
        finally:
            fh.close()
        os.replace(part, path)
        _phases.add("disk_stream", time.perf_counter() - t0, hb.nbytes)

    def _read_spill(self, b: int) -> np.ndarray:
        """disk→host via the persist layer's resuming reader: a torn or
        fault-injected read resumes at the current offset under the
        shared retry policy instead of failing the fit."""
        path = self._spill_path(b)
        expected = self._block_nbytes[b]
        shape, dtype = self._block_meta[b]
        be = _persist.for_uri(path)
        t0 = time.perf_counter()
        buf = bytearray()
        with be.open_resuming(path) as src:
            while len(buf) < expected:
                chunk = src.read(min(1 << 20, expected - len(buf)))
                if not chunk:
                    break
                buf += chunk
        if len(buf) != expected:
            raise IOError(f"spill file {path} truncated: "
                          f"{len(buf)} of {expected} bytes")
        _phases.add("disk_stream", time.perf_counter() - t0, expected)
        return np.frombuffer(bytes(buf), dtype=dtype).reshape(shape)

    def _pick_spill_victim_locked(self, keep) -> Optional[int]:
        for b in self._host_lru:
            if b not in keep:
                return b
        return None

    def _enforce_host_budget(self, keep=(), trigger: str = "host_cap",
                             headroom: int = 0) -> int:
        """Spill LRU host blocks (except `keep`) until host-resident bytes
        plus `headroom` (bytes an imminent restore is about to insert) fit
        the host budget. File writes run OUTSIDE the lock; a block already
        on disk just drops its host copy (spilled copies kept)."""
        budget = self.host_budget_bytes()
        if budget <= 0:
            return 0
        spilled = 0
        while True:
            with self._lock:
                if self._host_bytes_resident + headroom <= budget:
                    return spilled
                b = self._pick_spill_victim_locked(keep)
                if b is None:
                    return spilled
                hb = self.host_blocks[b]
                on_disk = b in self._on_disk
            if hb is None:
                # raced with another spiller; bookkeeping already done
                continue
            if not on_disk:
                self._write_spill(b, hb)
            nbytes = self._block_nbytes[b]
            with self._lock:
                if self.host_blocks[b] is None:
                    continue
                self.host_blocks[b] = None
                self._host_lru.pop(b, None)
                self._host_bytes_resident -= nbytes
                self._on_disk.add(b)
                self.counters["spilled"] += 1
                self.counters["bytes_spilled"] += nbytes
            spilled += 1
            try:
                reg = _registry()
                reg["spill_blocks"].inc(1, "spilled")
                reg["spill_bytes"].inc(nbytes, "spill")
            except Exception:
                pass
            _account_spill_totals(spilled=nbytes)
            _ml.record_event("spill", f"{self.owner}:block{b}", nbytes,
                             trigger=trigger, space="disk",
                             kind="block_store")

    def shed_host(self, keep=(), trigger: str = "pressure") -> int:
        """Spill ALL host blocks except `keep` — the second stage of the
        pressure response (device blocks shed first via `shed`; host
        blocks spill after, and blocks already on disk just drop their
        host copy). No-op when the disk tier is disabled."""
        if self._host_budget <= 0:
            return 0
        spilled = 0
        while True:
            with self._lock:
                b = self._pick_spill_victim_locked(keep)
                if b is None:
                    return spilled
                hb = self.host_blocks[b]
                on_disk = b in self._on_disk
            if hb is None:
                continue
            if not on_disk:
                self._write_spill(b, hb)
            nbytes = self._block_nbytes[b]
            with self._lock:
                if self.host_blocks[b] is None:
                    continue
                self.host_blocks[b] = None
                self._host_lru.pop(b, None)
                self._host_bytes_resident -= nbytes
                self._on_disk.add(b)
                self.counters["spilled"] += 1
                self.counters["bytes_spilled"] += nbytes
            spilled += 1
            try:
                reg = _registry()
                reg["spill_blocks"].inc(1, "spilled")
                reg["spill_bytes"].inc(nbytes, "spill")
            except Exception:
                pass
            _account_spill_totals(spilled=nbytes)
            _ml.record_event("spill", f"{self.owner}:block{b}", nbytes,
                             trigger=trigger, space="disk",
                             kind="block_store")

    def fetch_host(self, b: int) -> np.ndarray:
        """Host array of block `b`, restoring from its spill file when the
        host copy was shed. Touches the host LRU and enforces the host
        budget (so a restore can spill a colder block in turn). This is
        the ONE host read path — the streamed driver's GOSS gathers and
        device uploads all come through here,
        which is what makes restored bytes bit-identical by construction."""
        b = int(b)
        with self._lock:
            hb = self.host_blocks[b]
            if hb is not None:
                self._host_lru.move_to_end(b) if b in self._host_lru \
                    else self._host_lru.setdefault(b, None)
                return hb
        nbytes = self._block_nbytes[b]
        with self._restore_lock:
            with self._lock:
                cur = self.host_blocks[b]
                if cur is not None:
                    # a concurrent restore (prefetch) won; keep the winner
                    return cur
            # make room FIRST — the watermark must never exceed the
            # budget, even transiently. Keep the block being restored and
            # its successor (the disk double buffer); a colder block pays
            # the spill
            self._enforce_host_budget(keep={b, (b + 1) % self.n_blocks},
                                      headroom=nbytes)
            arr = self._read_spill(b)
            with self._lock:
                self.host_blocks[b] = arr
                self._host_lru[b] = None
                self._host_lru.move_to_end(b)
                self._host_bytes_resident += nbytes
                self.counters["restored"] += 1
                self.counters["bytes_restored"] += nbytes
                if self._host_bytes_resident > self.host_resident_peak_bytes:
                    self.host_resident_peak_bytes = self._host_bytes_resident
                if self._host_bytes_resident > self._host_window_peak:
                    self._host_window_peak = self._host_bytes_resident
                host_peak = self._host_bytes_resident
        try:
            reg = _registry()
            reg["spill_blocks"].inc(1, "restored")
            reg["spill_bytes"].inc(nbytes, "restore")
            reg["spill_host_peak"].set(
                max(self.host_resident_peak_bytes,
                    reg["spill_host_peak"].value() or 0))
        except Exception:
            pass
        _account_spill_totals(restored=nbytes, host_peak=host_peak)
        _ml.record_event("restore", f"{self.owner}:block{b}", nbytes,
                         trigger="stream", space="disk", kind="block_store")
        return arr

    # -- resident-set management -------------------------------------------

    def _evict_locked(self, b: int, trigger: str) -> None:
        arr = self._resident.pop(b, None)
        if arr is None:
            return
        nbytes = self._block_nbytes[b]
        self._resident_bytes -= nbytes
        self.counters["evicted"] += 1
        try:
            _registry()["blocks"].inc(1, "evicted")
        except Exception:
            pass
        _ml.record_event("evict", f"{self.owner}:block{b}", nbytes,
                         trigger=trigger, space="device", kind="block_store")

    def shed(self, keep=(), trigger: str = "pressure") -> int:
        """Drop device blocks (LRU first) except `keep` — the
        pressure-shedding hook. Host copies remain; cost is a future
        re-upload, so device blocks are always the first bytes returned
        when `memory_ledger.pressure()` crosses the eviction threshold."""
        dropped = 0
        with self._lock:
            for b in [b for b in list(self._resident) if b not in keep]:
                self._evict_locked(b, trigger)
                dropped += 1
        return dropped

    def _upload(self, b: int):
        import jax

        hb = self.fetch_host(b)

        def _put():
            return jax.device_put(hb)

        t0 = time.perf_counter()
        arr = _put()
        if _phases.ENABLED:
            # accounted transfer: a tiny D2H is the only reliable barrier
            # over the host↔device link (see phases.accounted_h2d)
            try:
                np.asarray(arr.ravel()[:1])
            except Exception:
                jax.block_until_ready(arr)
            _phases.add("h2d_stream", time.perf_counter() - t0, hb.nbytes)
        else:
            _phases.add("h2d_stream", 0.0, hb.nbytes)
        return arr

    def get(self, b: int):
        """Device array of block `b`: LRU hit, or evict-then-upload."""
        with self._lock:
            arr = self._resident.get(b)
            if arr is not None:
                self._resident.move_to_end(b)
                self.counters["reused"] += 1
                try:
                    _registry()["blocks"].inc(1, "reused")
                except Exception:
                    pass
                return arr
        # pressure shed BEFORE growing the resident set: past the ledger's
        # eviction threshold only the double buffer stays resident
        try:
            if _ml.pressure() >= _ml.evict_threshold():
                self.shed(keep={b, (b + 1) % self.n_blocks},
                          trigger="pressure")
        except Exception:
            pass
        hb_bytes = self._block_nbytes[b]
        with self._lock:
            arr = self._resident.get(b)
            if arr is not None:
                self._resident.move_to_end(b)
                self.counters["reused"] += 1
                return arr
            budget = self.budget_bytes()
            while self._resident and self._resident_bytes + hb_bytes > budget:
                self._evict_locked(next(iter(self._resident)), "cap")
        arr = self._upload(b)
        with self._lock:
            cur = self._resident.get(b)
            if cur is not None:
                # lost a concurrent-miss race (a shared cached store can
                # be streamed by several sweep candidates): the transfer
                # happened and is counted, but the resident entry — and
                # its bytes — stay singular; our duplicate array is
                # dropped to the GC
                self._resident.move_to_end(b)
                self.counters["uploaded"] += 1
                self.counters["bytes_streamed"] += hb_bytes
                peak = self._resident_bytes
                arr = cur
            else:
                self._resident[b] = arr
                self._resident_bytes += hb_bytes
                self.counters["uploaded"] += 1
                self.counters["bytes_streamed"] += hb_bytes
                if self._resident_bytes > self.resident_peak_bytes:
                    self.resident_peak_bytes = self._resident_bytes
                if self._resident_bytes > self._window_peak:
                    self._window_peak = self._resident_bytes
                peak = self._resident_bytes
        try:
            reg = _registry()
            reg["blocks"].inc(1, "uploaded")
            reg["bytes"].inc(hb_bytes)
            reg["resident_peak"].set(
                max(self.resident_peak_bytes,
                    reg["resident_peak"].value() or 0))
        except Exception:
            pass
        _account_totals(hb_bytes, peak)
        return arr

    def _prefetch_pool(self):
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            with self._lock:
                if self._pool is None:
                    self._pool = ThreadPoolExecutor(
                        max_workers=1,
                        thread_name_prefix="h2o3-spill-prefetch")
        return self._pool

    def prefetch(self, b: int) -> None:
        """Dispatch block `b`'s H2D now so the upload overlaps the
        caller's compute on the previous block (double buffering). The
        device_put is async on real backends; `get(b)` then finds it
        resident. Once blocks live on disk the whole fetch moves to a
        single background worker — a synchronous prefetch would serialize
        the disk read with the caller's compute, which is the one cost
        the three-tier pipeline exists to hide; max_workers=1 keeps it a
        strict double buffer (one restore+upload in flight)."""
        b = int(b)
        with self._lock:
            disk_active = bool(self._on_disk)
            if disk_active:
                if b in self._pending:
                    return
                self._pending.add(b)
        if not disk_active:
            try:
                self.get(b)
            except Exception:
                pass   # advisory; the blocking get reports real failures
            return

        def _run():
            try:
                self.get(b)
            except Exception:
                pass
            finally:
                with self._lock:
                    self._pending.discard(b)

        try:
            self._prefetch_pool().submit(_run)
        except Exception:
            with self._lock:
                self._pending.discard(b)

    def account_external_bytes(self, nbytes: int) -> None:
        """Fold an out-of-band H2D (e.g. a GOSS compact-sample upload)
        into the stream byte counters so `streamed_bytes` reflects every
        byte the out-of-core path actually moved."""
        with self._lock:
            self.counters["bytes_streamed"] += int(nbytes)
        try:
            _registry()["bytes"].inc(int(nbytes))
        except Exception:
            pass
        _account_totals(int(nbytes))

    # -- stats / lifecycle -------------------------------------------------

    def stats(self) -> Dict:
        with self._lock:
            out = dict(self.counters)
        out.update(n_blocks=self.n_blocks, block_rows=self.block_rows,
                   pack_bits=self.pack_bits,
                   host_bytes=self.host_bytes(),
                   resident_bytes=self.resident_bytes(),
                   disk_bytes=self.disk_bytes(),
                   resident_peak_bytes=self.resident_peak_bytes,
                   host_resident_peak_bytes=self.host_resident_peak_bytes,
                   budget_bytes=self.budget_bytes(),
                   host_budget_bytes=self.host_budget_bytes())
        return out

    def close(self) -> None:
        self.shed(trigger="clear")
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)
        # remove spill files BEFORE retiring the :spill owner — its bytes
        # come from the filesystem, so files left behind would read as a
        # leak (which is exactly what an unclosed store should read as)
        with self._lock:
            on_disk = list(self._on_disk)
            self._on_disk.clear()
            sd = self._spill_dir
        freed = 0
        for b in on_disk:
            try:
                p = os.path.join(sd, f"block{b}.bin") if sd else None
                if p and os.path.exists(p):
                    freed += self._block_nbytes[b]
                    os.remove(p)
            except OSError:
                pass
        if sd:
            try:
                os.rmdir(sd)
            except OSError:
                pass
        if self._spill_registered:
            _ml.unregister(f"{self.owner}:spill",
                           event="free" if freed else None, nbytes=freed,
                           trigger="close", space="disk")
            self._spill_registered = False
        if self._registered:
            _ml.unregister(self.owner)
            self._registered = False
