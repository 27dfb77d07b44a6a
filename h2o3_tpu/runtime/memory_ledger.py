"""Memory ledger — unified host+device byte accounting for every subsystem.

The PR 6 spine made *time* observable (spans, latency histograms, retrace
counters); this module is its byte-side twin. Before it, every byte-holding
subsystem kept a private, incompatible count (``DKV._nbytes``,
``dataset_cache._Entry.nbytes``, ``ScorerCache.stats``) and nothing reported
live HBM occupancy, watermarks or leaks — the exact blind spot that gates
out-of-core training (stream blocks against an HBM budget, arXiv 2005.09148)
and sustained-SLO serving (ROADMAP items 3 and 4).

Design — an *accountant*, not an allocator:

- every byte-holding subsystem **registers owners** (``dkv:<key>``,
  ``dataset_cache:<fp>:<layer>``, ``scorer:<model_key>:<kind>``,
  ``ingest:<what>``) with byte callbacks and an optional weakref *referent*
  whose death marks the owner dead. Callbacks must never strongly pin the
  accounted object — they dereference weakrefs and report 0 once it died.
- ``refresh()`` walks the owners (rate-limited, callbacks run lock-free,
  one shared ``measure()`` dedup set per pass so a buffer reachable from
  two owners is attributed once), reconciles attributed device bytes
  against what the runtime actually holds (``device.memory_stats()`` where
  available, live-buffer census fallback on CPU — the unattributed delta
  is reported as ``owner_kind="unaccounted"``), tracks high watermarks and
  the top owners at the peak, and feeds the
  ``h2o3_memory_bytes{owner_kind,space}`` gauges.
- the **leak detector**: a dead owner whose callbacks still report bytes
  (the referent died but something else pins its buffers), or a FAILED/
  CANCELLED Job whose dest key is still in the DKV (``job_end``). Leaks
  surface as ``h2o3_memory_leaked_bytes`` + timeline events and *clear*
  when the bytes are finally released.
- the **pressure API**: ``pressure()`` ∈ [0,1] against
  ``H2O3_MEM_BUDGET_MB`` (host; default: /proc/meminfo MemTotal) and the
  device capacity (``memory_stats()['bytes_limit']`` or
  ``H2O3_DEVICE_BUDGET_MB``). Serving admission control sheds at
  ``H2O3_SERVING_SHED_PRESSURE`` and ``dataset_cache._evict_locked``
  evicts LRU entries past ``H2O3_MEM_EVICT_PRESSURE``; threshold
  crossings are traced.

Read surfaces: ``GET /3/Memory`` (JSON breakdown; ``?schema=1`` →
MemoryV3), the normal ``/3/Metrics`` Prometheus scrape (a registry collect
hook refreshes the gauges at scrape time), and the ``/3/Profiler`` fold.
Alloc/evict/free/leak events land in the Timeline ring and annotate the
open tracing span (docs/observability.md "Memory accounting").
"""

from __future__ import annotations

import os
import sys
import threading
import time
import weakref
import zlib
from typing import Callable, Dict, List, Optional, Tuple

from . import env_float, env_int

__all__ = ["register", "unregister", "unregister_prefix", "record_event",
           "measure", "refresh", "snapshot", "totals", "pressure", "peak",
           "owners", "dkv_stats", "job_end", "ingest_buffer",
           "evict_threshold", "device_capacity_bytes", "clear"]

# how stale a cached refresh may be before a read recomputes (scrape-time
# collect hooks and the admission-path pressure() both ride this)
_REFRESH_S = env_float("H2O3_MEM_REFRESH_S", 0.5)
# pressure above this emits a threshold-crossing event (and below, a
# recovery event) — the observability signal, not an action threshold
_PRESS_THRESHOLD = env_float("H2O3_MEM_PRESSURE_THRESHOLD", 0.85)
# owners listed in a snapshot (the rest aggregate into by_kind totals)
_SNAPSHOT_OWNERS = env_int("H2O3_MEM_SNAPSHOT_OWNERS", 256)

_STR_SAMPLE = 256          # sampled string-column estimate (DKV._nbytes rule)
_MEASURE_DEPTH = 4         # object-graph walk bound
_LOCK_TYPE = type(threading.Lock())


class _Owner:
    __slots__ = ("owner", "kind", "type_name", "bytes_fn", "ref", "dead",
                 "leaked", "t_register", "last_host", "last_device",
                 "last_disk", "__weakref__")

    def __init__(self, owner: str, kind: str, type_name: str,
                 bytes_fn: Callable[[], Tuple[int, int]]):
        self.owner = owner
        self.kind = kind
        self.type_name = type_name
        self.bytes_fn = bytes_fn
        self.ref: Optional[weakref.ref] = None
        self.dead = False          # referent died (weakref callback fired)
        self.leaked = False        # leak event already emitted
        self.t_register = time.time()
        self.last_host = 0
        self.last_device = 0
        self.last_disk = 0


_REG_LOCK = threading.Lock()       # guards _OWNERS / _JOB_LEAKS only
_OWNERS: Dict[str, _Owner] = {}
_JOB_LEAKS: Dict[str, Dict] = {}   # dest key -> {status, t_end, bytes}

_REFRESH_LOCK = threading.Lock()   # one refresh pass at a time
_STATE_LOCK = threading.Lock()     # guards the cached-result REFERENCE
# the cached refresh result: REBOUND atomically, never mutated in place —
# readers got handed this dict lock-free, so a clear()+update() swap would
# expose them to a transient KeyError mid-pass
_STATE: Dict = dict(
    t=0.0, by_kind={}, totals=dict(host_bytes=0, device_bytes=0,
                                   disk_bytes=0, leaked_bytes=0,
                                   unaccounted_device_bytes=0,
                                   owner_count=0),
    owners=[], leaks=[], device={}, pressure={}, )
_HWM = dict(host=0, device=0, disk=0, total=0)
_PEAK_TOP: List[Dict] = []
_PRESS_HIGH = [False]

_TLS = threading.local()           # .seen — per-refresh measure dedup set


# -- registry families ---------------------------------------------------------

_REG: Dict = {}


def _registry() -> Dict:
    """Memoized registry families + REST bindings + the scrape-time collect
    hook (same lazy-memoization stance as every other subsystem)."""
    if not _REG:
        from . import metrics_registry as reg

        _REG["bytes"] = reg.gauge(
            "h2o3_memory_bytes",
            "ledger-attributed bytes per owner kind and memory space "
            "(owner_kind=unaccounted is the device-census remainder)",
            labelnames=("owner_kind", "space"))
        _REG["hwm"] = reg.gauge(
            "h2o3_memory_high_watermark_bytes",
            "high watermark of ledger-attributed bytes per space",
            labelnames=("space",))
        _REG["leaked"] = reg.gauge(
            "h2o3_memory_leaked_bytes",
            "bytes held by dead owners (referent died, buffers persist) "
            "plus DKV keys not freed after a failed job")
        _REG["owners"] = reg.gauge(
            "h2o3_memory_owners", "registered ledger owners")
        _REG["pressure"] = reg.gauge(
            "h2o3_memory_pressure",
            "memory pressure in [0,1]: max of host bytes vs "
            "H2O3_MEM_BUDGET_MB and device bytes vs device capacity")
        _REG["events"] = reg.counter(
            "h2o3_memory_events",
            "memory lifecycle events (alloc/evict/free/leak/leak_cleared/"
            "pressure_high/pressure_normal)",
            labelnames=("event", "owner_kind"))
        for f, m in (("host_bytes", "h2o3_memory_bytes"),
                     ("device_bytes", "h2o3_memory_bytes"),
                     ("disk_bytes", "h2o3_memory_bytes"),
                     ("unaccounted_device_bytes", "h2o3_memory_bytes"),
                     ("leaked_bytes", "h2o3_memory_leaked_bytes"),
                     ("owner_count", "h2o3_memory_owners")):
            reg.bind_rest_field("memory", f"totals.{f}", m)
        # scrape-time pull: GET /3/Metrics and the /3/Profiler fold see
        # gauges no staler than the refresh rate limit
        reg.register_collect_hook(lambda: refresh())
    return _REG


# -- budgets / probes ----------------------------------------------------------

def _host_budget_bytes() -> int:
    mb = env_float("H2O3_MEM_BUDGET_MB", 0.0)
    if mb > 0:
        return int(mb * 1e6)
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 16 << 30


def _rss_bytes() -> int:
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        try:
            import resource

            return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            return 0


def evict_threshold() -> float:
    """Pressure above which byte caches (dataset_cache) shed LRU entries."""
    return env_float("H2O3_MEM_EVICT_PRESSURE", 0.9)


def device_capacity_bytes() -> int:
    """Device byte capacity as the ledger sees it: the ``memory_stats()``
    limit on an accelerator (one that reports none is an error, see
    `_probe_device`), the census cap (``H2O3_DEVICE_BUDGET_MB`` / host
    budget) on the CPU backend. The out-of-core streaming
    layer derives its resident budget from this — one authoritative
    number instead of a guessed HBM cap (ISSUE 14)."""
    cap = int(_probe_device().get("capacity_bytes", 0))
    return cap or _host_budget_bytes()


def _probe_device() -> Dict:
    """What the runtime actually holds on-device: per-device
    ``memory_stats()`` where the backend reports them (TPU/GPU), else a
    live-buffer census (sum of live jax.Array nbytes — the CPU fallback).
    Never *imports* jax: if the platform isn't loaded there are no device
    buffers to probe."""
    jx = sys.modules.get("jax")
    if jx is None:
        return dict(probe="unavailable", in_use_bytes=0, capacity_bytes=0,
                    devices=[])
    devices = []
    in_use = limit = 0
    for d in jx.local_devices():     # only addressable devices answer
        stats = d.memory_stats()
        if stats and "bytes_in_use" in stats:
            devices.append(dict(id=str(d.id), platform=d.platform,
                                bytes_in_use=int(stats["bytes_in_use"]),
                                bytes_limit=int(stats.get("bytes_limit",
                                                          0))))
            in_use += int(stats["bytes_in_use"])
            limit += int(stats.get("bytes_limit", 0))
        elif d.platform != "cpu":
            # an accelerator that cannot say what it holds is a fault, not
            # a reason to budget its HBM by the host's RAM
            raise RuntimeError(
                f"{d.platform} device {d.id} reports no memory_stats(): "
                "the ledger cannot budget device memory")
    if devices:
        return dict(probe="memory_stats", in_use_bytes=in_use,
                    capacity_bytes=limit, devices=devices)
    # census (the CPU backend reports no memory_stats)
    census = n = 0
    try:
        for a in jx.live_arrays():
            try:
                census += int(a.nbytes)
                n += 1
            except Exception:
                pass
    except Exception:
        return dict(probe="unavailable", in_use_bytes=0, capacity_bytes=0,
                    devices=[])
    cap_mb = env_float("H2O3_DEVICE_BUDGET_MB", 0.0)
    cap = int(cap_mb * 1e6) if cap_mb > 0 else _host_budget_bytes()
    return dict(probe="census", in_use_bytes=census, capacity_bytes=cap,
                live_buffers=n, devices=[])


# -- the one deep sizer --------------------------------------------------------

def measure(value) -> Tuple[int, int]:
    """(host_bytes, device_bytes) of one object graph — the ONE sizing rule
    DKV, the scorer cache and the job-leak check share. numpy buffers are
    host; jax Arrays are device (``.nbytes`` without materializing — a
    device array must never pay a D2H to be counted); string columns use
    the sampled estimate; nested Frames/Vecs/BinnedMatrix/model ``__dict__``
    graphs are walked to a bounded depth with a cycle/shared-buffer guard.
    Inside a ledger refresh pass the guard set is shared across owners, so
    a buffer reachable from two owners is attributed to the first."""
    seen = getattr(_TLS, "seen", None)
    if seen is None:
        seen = set()
    acc = [0, 0]
    _measure_into(value, acc, seen, 0)
    return acc[0], acc[1]


def _measure_into(x, acc, seen, depth) -> None:
    if x is None or isinstance(x, (bool, int, float, complex)):
        return
    if isinstance(x, (str, bytes, bytearray)):
        acc[0] += len(x)
        return
    i = id(x)
    if i in seen:
        return
    seen.add(i)
    import numpy as np

    if isinstance(x, np.ndarray):
        if x.dtype == object:
            # sampled estimate — a per-element loop would make a scrape
            # O(total string cells)
            import itertools

            flat = x.ravel()
            sample = list(itertools.islice(
                (s for s in flat if s is not None), _STR_SAMPLE))
            avg = (sum(len(str(s)) for s in sample) / len(sample)
                   if sample else 0.0)
            acc[0] += int(avg * flat.size)
        else:
            acc[0] += int(x.nbytes)
        return
    jx = sys.modules.get("jax")
    if jx is not None and isinstance(x, jx.Array):
        try:
            if getattr(x, "is_fully_addressable", True):
                acc[1] += int(x.nbytes)
            else:
                # process-spanning global array (pod training): count only
                # THIS rank's resident shards — the per-rank ledger must
                # show the 1/N local footprint, not the global bytes
                acc[1] += sum(int(s.data.nbytes)
                              for s in x.addressable_shards)
        except Exception:
            pass
        return
    if depth >= _MEASURE_DEPTH:
        return
    if isinstance(x, dict):
        for v in x.values():
            _measure_into(v, acc, seen, depth + 1)
        return
    if isinstance(x, (list, tuple, set, frozenset)):
        for v in x:
            _measure_into(v, acc, seen, depth + 1)
        return
    if isinstance(x, (type, threading.Thread, _LOCK_TYPE,
                      weakref.ref)) or callable(x):
        return
    vecs = getattr(x, "_vecs", None)
    if isinstance(vecs, dict):                 # Frame
        for v in vecs.values():
            _measure_into(v, acc, seen, depth + 1)
        return
    d = getattr(x, "__dict__", None)
    if isinstance(d, dict):                    # models, BinnedMatrix, ...
        for v in d.values():
            _measure_into(v, acc, seen, depth + 1)
        return
    slots = getattr(type(x), "__slots__", None)
    if slots:                                  # Vec and friends
        for s in slots:
            if s == "__weakref__":
                continue
            try:
                _measure_into(getattr(x, s, None), acc, seen, depth + 1)
            except Exception:
                pass


# -- owner lifecycle -----------------------------------------------------------

def register(owner: str, kind: Optional[str] = None, *,
             bytes_fn: Optional[Callable[[], Tuple[int, int]]] = None,
             host_fn: Optional[Callable[[], int]] = None,
             device_fn: Optional[Callable[[], int]] = None,
             referent=None, type_name: str = "") -> str:
    """Register (or replace) a byte owner. `bytes_fn` returns
    (host, device) or (host, device, disk) — the optional third element
    accounts persist-backed spill files (the block store's disk tier);
    or pass `host_fn`/`device_fn` separately. `referent`
    is the object whose death marks the owner dead (weakref-backed —
    never pinned); callbacks must not strongly hold the referent either,
    or the ledger itself becomes the leak it exists to find."""
    if kind is None:
        kind = owner.split(":", 1)[0]
    if bytes_fn is None:
        hf, df = host_fn, device_fn
        bytes_fn = lambda: (int(hf() if hf else 0),   # noqa: E731
                            int(df() if df else 0))
    o = _Owner(owner, kind, type_name, bytes_fn)
    if referent is not None:
        try:
            o.ref = weakref.ref(referent, lambda _r, _o=weakref.ref(o):
                                _mark_dead(_o))
        except TypeError:
            o.ref = None
    with _REG_LOCK:
        _OWNERS[owner] = o
    _registry()
    return owner


def _mark_dead(owner_ref) -> None:
    o = owner_ref()
    if o is None:
        return
    with _REG_LOCK:
        if _OWNERS.get(o.owner) is o:
            o.dead = True


def unregister(owner: str, *, event: Optional[str] = None,
               nbytes: Optional[int] = None, trigger: str = "",
               space: str = "host") -> bool:
    """Remove an owner; optionally emit a lifecycle event sized by
    `nbytes` (defaults to the owner's last-refreshed bytes)."""
    with _REG_LOCK:
        o = _OWNERS.pop(owner, None)
    if o is None:
        return False
    if event:
        if nbytes is None:
            nbytes = o.last_host + o.last_device + o.last_disk
        record_event(event, owner, nbytes, trigger=trigger, space=space,
                     kind=o.kind)
    return True


def unregister_prefix(prefix: str) -> int:
    with _REG_LOCK:
        doomed = [k for k in _OWNERS if k.startswith(prefix)]
        for k in doomed:
            _OWNERS.pop(k, None)
    return len(doomed)


def owners(prefix: str = "") -> List[Dict]:
    """Registered owners (id, kind, last-refreshed bytes, dead flag)."""
    with _REG_LOCK:
        items = [o for k, o in _OWNERS.items() if k.startswith(prefix)]
    return [dict(owner=o.owner, kind=o.kind, type=o.type_name,
                 host_bytes=o.last_host, device_bytes=o.last_device,
                 disk_bytes=o.last_disk, dead=o.dead) for o in items]


def record_event(event: str, owner: str, nbytes: int = 0, *,
                 trigger: str = "", space: str = "host",
                 kind: Optional[str] = None) -> None:
    """One memory lifecycle event → registry counter + Timeline ring +
    an annotation on the open tracing span (so an eviction that happens
    inside a request/candidate shows up in its trace)."""
    if kind is None:
        kind = owner.split(":", 1)[0]
    _registry()["events"].inc(1, event, kind)
    try:
        from .timeline import Timeline

        Timeline.record("memory", f"{event} {owner}", owner=owner,
                        bytes=int(nbytes), trigger=trigger, space=space)
    except Exception:
        pass
    try:
        from . import tracing

        tracing.event(f"memory_{event}", owner=owner, bytes=int(nbytes),
                      trigger=trigger)
    except Exception:
        pass


def job_end(dest_key: str, status: str) -> None:
    """Job-lifecycle leak check: a FAILED/CANCELLED job whose dest key is
    still in the DKV is a leak candidate (the partial model should have
    been deleted — docs/robustness.md); it surfaces in the leak report
    until the key is freed."""
    if status not in ("FAILED", "CANCELLED"):
        with _REG_LOCK:
            _JOB_LEAKS.pop(dest_key, None)
        return
    from .dkv import DKV, _owner_kind

    v = DKV.get(dest_key)
    if v is None or _owner_kind(v) == "dkv":
        # nothing there, or only bookkeeping (the Job itself stays for
        # status polling) — no byte-owner left behind
        return
    with _REG_LOCK:
        known = dest_key in _JOB_LEAKS
        if not known:
            _JOB_LEAKS[dest_key] = dict(status=status, t_end=time.time(),
                                        bytes=0)
    if not known:
        record_event("leak", f"dkv:{dest_key}", 0,
                     trigger=f"job_{status.lower()}", kind="dkv")


# -- ingest transient buffers --------------------------------------------------

_INGEST_LOCK = threading.Lock()
_INGEST_BYTES = [0]
_INGEST_REGISTERED = [False]


class ingest_buffer:
    """``with ingest_buffer(len(data)):`` — account a parse payload while
    it is being tokenized (the `ingest:<what>` owner of the taxonomy)."""

    def __init__(self, nbytes: int, what: str = "tokenize"):
        self.nbytes = int(nbytes)
        self.what = what

    def __enter__(self):
        with _INGEST_LOCK:
            _INGEST_BYTES[0] += self.nbytes
            if not _INGEST_REGISTERED[0]:
                _INGEST_REGISTERED[0] = True
                register("ingest:tokenize", kind="ingest",
                         host_fn=lambda: _INGEST_BYTES[0],
                         type_name="bytes")
        record_event("alloc", f"ingest:{self.what}", self.nbytes,
                     trigger="parse", kind="ingest")
        return self

    def __exit__(self, *exc):
        with _INGEST_LOCK:
            _INGEST_BYTES[0] = max(_INGEST_BYTES[0] - self.nbytes, 0)
        return False


# -- refresh: the accounting pass ----------------------------------------------

def refresh(force: bool = False) -> Dict:
    """Recompute the ledger: per-owner bytes (one shared measure() dedup
    set), leak scan, device reconciliation, watermarks, pressure, gauges.
    Rate-limited (`H2O3_MEM_REFRESH_S`) unless `force`; concurrent callers
    get the cached result instead of a second pass. Callbacks run without
    any ledger lock held, so a callback may take its subsystem's lock
    (DKV, dataset_cache) without ordering hazards."""
    now = time.time()
    with _STATE_LOCK:
        if not force and now - _STATE["t"] < _REFRESH_S:
            return _STATE
    if not _REFRESH_LOCK.acquire(blocking=False):
        with _STATE_LOCK:
            return _STATE
    try:
        return _refresh_locked(now)
    finally:
        _REFRESH_LOCK.release()


def _refresh_locked(now: float) -> Dict:
    reg = _registry()
    with _REG_LOCK:
        owner_objs = list(_OWNERS.values())
        job_leaks = dict(_JOB_LEAKS)
    _TLS.seen = set()
    try:
        by_kind: Dict[str, List[int]] = {}
        rows: List[Dict] = []
        leaks: List[Dict] = []
        retire: List[_Owner] = []
        host_total = dev_total = disk_total = leaked = 0
        # job leaks FIRST: the leaked value usually also has a live `dkv:`
        # owner (the key never left the store), and the shared dedup set
        # attributes each buffer to whichever view measures it first — an
        # operator reading the leak report needs its size, so the leak
        # entry wins and the aliasing owner reports ~0 for the pass
        from .dkv import DKV

        for dest, info in job_leaks.items():
            v = DKV.get(dest)
            if v is None:
                with _REG_LOCK:
                    _JOB_LEAKS.pop(dest, None)
                record_event("leak_cleared", f"dkv:{dest}", info["bytes"],
                             kind="dkv")
                continue
            h, d = measure(v)
            b = h + d
            info["bytes"] = b
            with _REG_LOCK:
                if dest in _JOB_LEAKS:
                    _JOB_LEAKS[dest]["bytes"] = b
            leaked += b
            host_total += h
            dev_total += d
            agg = by_kind.setdefault("leaked", [0, 0, 0, 0])
            agg[0] += h
            agg[1] += d
            agg[3] += 1
            rows.append(dict(owner=f"dkv:{dest}", kind="leaked",
                             host_bytes=h, device_bytes=d, disk_bytes=0,
                             dead=False))
            leaks.append(dict(owner=f"dkv:{dest}", kind="dkv", bytes=b,
                              reason=f"job_{info['status'].lower()}"))
        for o in owner_objs:
            try:
                vals = o.bytes_fn()
                h, d = int(vals[0]), int(vals[1])
                k = int(vals[2]) if len(vals) > 2 else 0
            except Exception:
                h = d = k = 0
            o.last_host, o.last_device, o.last_disk = h, d, k
            if o.dead:
                if h + d + k <= 0:
                    if o.leaked:
                        record_event("leak_cleared", o.owner, 0,
                                     kind=o.kind)
                    retire.append(o)
                    continue
                leaked += h + d + k
                leaks.append(dict(owner=o.owner, kind=o.kind,
                                  bytes=h + d + k, reason="referent_dead"))
                if not o.leaked:
                    o.leaked = True
                    record_event("leak", o.owner, h + d + k,
                                 trigger="referent_dead", kind=o.kind,
                                 space="disk" if (k and not d and not h)
                                 else ("device" if d else "host"))
            host_total += h
            dev_total += d
            disk_total += k
            agg = by_kind.setdefault(o.kind, [0, 0, 0, 0])
            agg[0] += h
            agg[1] += d
            agg[2] += k
            agg[3] += 1
            rows.append(dict(owner=o.owner, kind=o.kind,
                             host_bytes=h, device_bytes=d, disk_bytes=k,
                             dead=o.dead))
    finally:
        _TLS.seen = None
    with _REG_LOCK:
        for o in retire:
            if _OWNERS.get(o.owner) is o:
                _OWNERS.pop(o.owner, None)
        n_owners = len(_OWNERS)

    device = _probe_device()
    unaccounted = max(int(device.get("in_use_bytes", 0)) - dev_total, 0) \
        if device.get("probe") != "unavailable" else 0

    # pressure: host bytes vs budget, device bytes vs capacity
    host_budget = _host_budget_bytes()
    rss = _rss_bytes()
    host_press = max(rss, host_total) / max(host_budget, 1)
    dev_cap = int(device.get("capacity_bytes", 0))
    dev_used = max(int(device.get("in_use_bytes", 0)), dev_total)
    dev_press = dev_used / dev_cap if dev_cap > 0 else 0.0
    press = min(max(host_press, dev_press, 0.0), 1.0)
    if press >= _PRESS_THRESHOLD and not _PRESS_HIGH[0]:
        _PRESS_HIGH[0] = True
        record_event("pressure_high", "ledger", 0,
                     trigger=f"{press:.3f}", kind="ledger")
    elif press < _PRESS_THRESHOLD and _PRESS_HIGH[0]:
        _PRESS_HIGH[0] = False
        record_event("pressure_normal", "ledger", 0,
                     trigger=f"{press:.3f}", kind="ledger")

    # watermarks + top owners at the combined peak
    total = host_total + dev_total + disk_total
    _HWM["host"] = max(_HWM["host"], host_total)
    _HWM["device"] = max(_HWM["device"], dev_total)
    _HWM["disk"] = max(_HWM["disk"], disk_total)
    if total > _HWM["total"]:
        _HWM["total"] = total
        top = sorted(rows, key=lambda r: -(r["host_bytes"]
                                           + r["device_bytes"]
                                           + r.get("disk_bytes", 0)))[:3]
        _PEAK_TOP[:] = [dict(owner=r["owner"], kind=r["kind"],
                             bytes=r["host_bytes"] + r["device_bytes"]
                             + r.get("disk_bytes", 0))
                        for r in top]

    # gauges (zero kinds that vanished so stale series don't lie)
    seen_labels = set()
    for kind, (h, d, k, _n) in by_kind.items():
        reg["bytes"].set(h, kind, "host")
        reg["bytes"].set(d, kind, "device")
        seen_labels.add((kind, "host"))
        seen_labels.add((kind, "device"))
        if k:
            reg["bytes"].set(k, kind, "disk")
            seen_labels.add((kind, "disk"))
    reg["bytes"].set(unaccounted, "unaccounted", "device")
    seen_labels.add(("unaccounted", "device"))
    for lv in reg["bytes"].children():
        if lv not in seen_labels and lv != ("_overflow", "_overflow"):
            reg["bytes"].set(0, *lv)
    reg["hwm"].set(_HWM["host"], "host")
    reg["hwm"].set(_HWM["device"], "device")
    reg["hwm"].set(_HWM["disk"], "disk")
    reg["leaked"].set(leaked)
    reg["owners"].set(n_owners)
    reg["pressure"].set(round(press, 4))

    rows.sort(key=lambda r: -(r["host_bytes"] + r["device_bytes"]
                              + r.get("disk_bytes", 0)))
    state = dict(
        t=now,
        totals=dict(host_bytes=host_total, device_bytes=dev_total,
                    disk_bytes=disk_total, leaked_bytes=leaked,
                    unaccounted_device_bytes=unaccounted,
                    owner_count=n_owners),
        by_kind={k: dict(host_bytes=v[0], device_bytes=v[1],
                         disk_bytes=v[2], owners=v[3])
                 for k, v in sorted(by_kind.items())},
        owners=rows[:_SNAPSHOT_OWNERS],
        leaks=leaks,
        device=device,
        pressure=dict(value=round(press, 4),
                      host=round(min(host_press, 1.0), 4),
                      device=round(min(dev_press, 1.0), 4),
                      threshold=_PRESS_THRESHOLD,
                      host_budget_bytes=host_budget,
                      device_capacity_bytes=dev_cap,
                      rss_bytes=rss),
    )
    global _STATE
    with _STATE_LOCK:
        _STATE = state
    return state


# -- read side -----------------------------------------------------------------

def totals() -> Dict:
    return dict(refresh()["totals"])


def pressure() -> float:
    """The [0,1] pressure signal admission control and cache eviction
    consult — a cached read between refresh intervals."""
    return float(refresh()["pressure"].get("value", 0.0))


def peak() -> Dict:
    """High watermarks + the top-3 owners captured at the combined peak."""
    refresh()
    return dict(host_bytes=_HWM["host"], device_bytes=_HWM["device"],
                disk_bytes=_HWM["disk"], total_bytes=_HWM["total"],
                top_owners=list(_PEAK_TOP))


def snapshot(force: bool = True) -> Dict:
    """The GET /3/Memory document: owners, by-kind totals, watermarks,
    pressure, device probe + reconciliation, leaks. `force=False` serves
    the rate-limited cached pass (the /3/Profiler fold) instead of paying
    a fresh accounting walk per read."""
    st = refresh(force=force)
    out = {k: v for k, v in st.items() if k != "t"}
    out["watermarks"] = peak()
    return out


def dkv_stats() -> Dict:
    """The DKV's store-level accounting, derived from the ledger's
    `dkv:`-prefixed owners — `DKV.stats()` delegates here so the two
    surfaces can never disagree."""
    refresh(force=True)
    with _REG_LOCK:
        items = [o for k, o in _OWNERS.items() if k.startswith("dkv:")]
    by_kind: Dict[str, Dict] = {}
    total = 0
    for o in items:
        b = o.last_host + o.last_device
        d = by_kind.setdefault(o.type_name or "object",
                               {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += b
        total += b
    return {"entries": len(items), "total_bytes": total, "by_kind": by_kind}


def fingerprint(key) -> str:
    """Short stable digest for owner ids built from unhashable-ish keys."""
    return "%08x" % (zlib.crc32(repr(key).encode()) & 0xFFFFFFFF)


def clear() -> None:
    """Forget every owner, leak and watermark (tests)."""
    global _STATE
    with _REG_LOCK:
        _OWNERS.clear()
        _JOB_LEAKS.clear()
    with _STATE_LOCK:
        _STATE = dict(_STATE, t=0.0)   # rebind: readers hold the old dict
    _HWM.update(host=0, device=0, disk=0, total=0)
    _PEAK_TOP.clear()
    _PRESS_HIGH[0] = False
