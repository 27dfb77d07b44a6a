"""Process-local → global array plumbing for multi-host training.

Reference parity: `water/fvec/Vec`'s home-node chunk layout + `MRTask`'s
implicit "compute where the data lives". In the TPU framework a multi-host
cloud trains on ONE global `jax.Array` per column whose shards live where
each process parsed them: `jax.make_array_from_process_local_data` is the
DKV-home-node placement, and host-side reductions that the reference ran as
MRTask reduces (global means, min/max, weighted sums) run here as
`multihost_utils.process_allgather` collectives.

Row balancing: byte-range ingest gives every process a *similar but not
equal* row count, while a global row-sharded array needs equal per-device
shards. Every process therefore pads its local block to the agreed
per-process quota with ZERO-WEIGHT rows (w=0 ⇒ no gradient, no histogram,
no Gram contribution — the same trick the single-process path uses for its
pad tail). Algorithms must mask by `w`, which they already do.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np


def multiprocess() -> bool:
    import jax

    return jax.process_count() > 1


def allgather_bytes(payload: bytes) -> "list[bytes]":
    """Variable-length byte blobs from every process, in rank order."""
    import jax

    if jax.process_count() == 1:
        return [payload]
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    lens = np.asarray(multihost_utils.process_allgather(
        jnp.asarray([len(payload)], jnp.int32))).reshape(-1)
    maxlen = int(max(lens.max(), 1))
    buf = np.zeros(maxlen, np.uint8)
    buf[: len(payload)] = np.frombuffer(payload, np.uint8)
    out = np.asarray(multihost_utils.process_allgather(jnp.asarray(buf)))
    out = out.reshape(len(lens), maxlen)
    return [out[r, : lens[r]].tobytes() for r in range(len(lens))]


def allgather_host(arr: np.ndarray) -> np.ndarray:
    """(nproc, *arr.shape) stack of every process's host array. f64 arrays
    travel as raw bytes: with x64 disabled a device gather would silently
    truncate them to f32, rounding exactly the quantities (global sums,
    min/max of timestamp-scale columns) this transport exists to keep
    exact."""
    import jax

    a = np.asarray(arr)
    if jax.process_count() == 1:
        return a[None]
    if a.dtype == np.float64:
        blobs = allgather_bytes(np.ascontiguousarray(a).tobytes())
        return np.stack([np.frombuffer(b, np.float64).reshape(a.shape)
                         for b in blobs])
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    out = multihost_utils.process_allgather(jnp.asarray(a))
    return np.asarray(out)


def global_sum(arr: np.ndarray) -> np.ndarray:
    return allgather_host(np.asarray(arr)).sum(axis=0)


def allgather_rows(local: np.ndarray) -> np.ndarray:
    """Rank-order concatenation of every process's local rows — the GLOBAL
    row order (ingest shards are contiguous byte ranges assigned in rank
    order, `frame/distributed_parse.py`). Ranks may hold different row
    counts; byte transport keeps dtypes exact."""
    a = np.ascontiguousarray(local)
    if not multiprocess():
        return a
    blobs = allgather_bytes(a.tobytes())
    trail = a.shape[1:]
    return np.concatenate([
        np.frombuffer(b, a.dtype).reshape((-1,) + trail) for b in blobs])


def allgather_rows_padded(local: np.ndarray, quota: int,
                          counts: np.ndarray) -> np.ndarray:
    """Global row-order concat with ONE fixed-size collective: each rank
    pads its rows to `quota` (loop-invariant), gathers (nproc, quota, ...),
    and trims per the known per-rank `counts`. Use for per-round gathers
    where `allgather_rows`'s variable-length byte transport would pay two
    collectives per call. Float64 inputs are rejected (the device gather
    would truncate them — use allgather_rows for exact f64)."""
    a = np.ascontiguousarray(local)
    if a.dtype == np.float64:
        raise TypeError("allgather_rows_padded is f32/int transport; "
                        "use allgather_rows for exact f64")
    if not multiprocess():
        return a
    pad = quota - a.shape[0]
    if pad > 0:
        a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
    out = allgather_host(a)                      # (nproc, quota, ...)
    return np.concatenate([out[r, : int(counts[r])]
                           for r in range(len(counts))])


def row_counts(n_local: int) -> np.ndarray:
    """Per-rank local row counts in rank order (one-time collective);
    pair with `allgather_rows_padded`."""
    return allgather_host(np.asarray([n_local], np.int64)).reshape(-1)


def row_offset(n_local: int) -> int:
    """This process's first-row index in the global row order."""
    import jax

    if not multiprocess():
        return 0
    return int(row_counts(n_local)[: jax.process_index()].sum())


def global_any(flag: bool) -> bool:
    """True iff ANY process votes True (one host collective; single-process
    = identity). The canonical transport for control-flow consensus —
    every rank MUST take the same branch or subsequent collectives
    deadlock (clock votes, early-stop votes)."""
    if not multiprocess():
        return bool(flag)
    votes = allgather_host(
        np.asarray([1.0 if flag else 0.0], np.float32)).reshape(-1)
    return bool(votes.max() >= 0.5)


def global_all(flag: bool) -> bool:
    """True iff EVERY process votes True (one host collective)."""
    if not multiprocess():
        return bool(flag)
    votes = allgather_host(
        np.asarray([1.0 if flag else 0.0], np.float32)).reshape(-1)
    return bool(votes.min() >= 0.5)


def global_minmax(local_min: np.ndarray, local_max: np.ndarray):
    """Per-column global (min, max) from per-process locals (NaN-safe: a
    process with no finite values contributes ±inf)."""
    mins = allgather_host(np.asarray(local_min, np.float64))
    maxs = allgather_host(np.asarray(local_max, np.float64))
    return np.min(mins, axis=0), np.max(maxs, axis=0)


def local_quota(n_local: int, row_multiple: int = 8) -> int:
    """The per-process padded row count every process agrees on: the max
    local count, rounded up so each local device shard stays aligned."""
    import jax

    from . import mesh as cloudlib

    counts = allgather_host(np.asarray([n_local], np.int32)).reshape(-1)
    ldev = max(len(jax.local_devices()), 1)
    return cloudlib.pad_to_multiple(int(counts.max()),
                                    max(ldev * row_multiple, row_multiple))


def global_row_array(local: np.ndarray, quota: int, cloud, fill=0):
    """Pad this process's rows to `quota` and assemble the global row-sharded
    jax.Array (nproc·quota global rows, shards resident where parsed)."""
    import jax

    pad = quota - local.shape[0]
    if pad:
        fill_block = np.full((pad,) + local.shape[1:], fill, local.dtype)
        local = np.concatenate([local, fill_block])
    if not multiprocess():
        # straight from the host buffer: each device is sent its own rows
        # (through jnp.asarray the whole array would land on device 0 first)
        return jax.device_put(local, cloud.row_sharding())
    return jax.make_array_from_process_local_data(
        cloud.row_sharding(), local)


def replicated_array(host_value, cloud):
    """Host value (identical on every process) → replicated global array."""
    import jax
    import jax.numpy as jnp

    arr = np.asarray(host_value)
    if not multiprocess():
        return jax.device_put(jnp.asarray(arr), cloud.replicated())
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    return multihost_utils.host_local_array_to_global_array(
        arr, cloud.mesh, P())


def sharded_full(shape, value, dtype, cloud):
    """Create a row-sharded constant directly on the devices (no host
    transfer — works across processes where device_put of host data can't)."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda: jnp.full(shape, value, dtype),
                   out_shardings=cloud.row_sharding())()


def global_order_stats(values: np.ndarray, ranks: Sequence[int],
                       iters: int = 4, nb: int = 512) -> np.ndarray:
    """Exact-to-f32-ulp global order statistics x_(k) (0-based, global sort
    order) of a column whose rows are scattered across processes — the
    `hex/quantile/Quantile.java` iterative-histogram-refinement design as a
    host collective.

    Each iteration histograms the local shard into `nb` uniform bins per
    tracked rank interval, `global_sum`s the counts (exact integers ⇒ the
    refinement path is DETERMINISTIC and independent of the process count),
    and shrinks each interval to the bin containing its rank. After `iters`
    rounds the interval width is range·nb^-iters (~1e-11 of range), below
    f32 ulp for f32-sourced data; the midpoint is returned.

    `values` must be this process's finite values (NaNs pre-dropped).
    """
    v = np.sort(np.asarray(values, np.float64))
    ranks = np.asarray(ranks, np.int64)
    lo0, hi0 = ((v[0], v[-1]) if v.size else (np.inf, -np.inf))
    glo, ghi = global_minmax(np.asarray([lo0]), np.asarray([hi0]))
    glo, ghi = float(glo[0]), float(ghi[0])
    if not np.isfinite(glo):
        return np.full(len(ranks), np.nan)
    if ghi <= glo:
        return np.full(len(ranks), glo)
    M = len(ranks)
    lo = np.full(M, glo)
    hi = np.full(M, ghi)
    below = np.zeros(M, np.int64)       # global count of values < lo[m]
    for _ in range(iters):
        # counts[m, b] = #local values in bin b of interval m (right-closed
        # last bin, matching np.histogram)
        edges = lo[:, None] + (hi - lo)[:, None] * (
            np.arange(nb + 1)[None, :] / nb)
        idx = np.searchsorted(v, edges)           # (M, nb+1)
        idx[:, -1] = np.searchsorted(v, edges[:, -1], side="right")
        counts = np.diff(idx, axis=1).astype(np.float64)
        gc = global_sum(counts)                   # exact: integer-valued
        cum = below[:, None] + np.cumsum(gc, axis=1)   # (M, nb)
        # bin containing rank k: first bin with cum > k
        b = (cum <= ranks[:, None]).sum(axis=1)
        b = np.minimum(b, nb - 1)
        prev = np.where(b > 0, np.take_along_axis(cum, np.maximum(
            b - 1, 0)[:, None], axis=1)[:, 0], below)
        below = np.where(b > 0, prev.astype(np.int64), below)
        width = (hi - lo) / nb
        lo = lo + b * width
        hi = lo + width
    return (lo + hi) / 2


def global_quantiles(values: np.ndarray, probs: Sequence[float],
                     n_global: Optional[int] = None) -> np.ndarray:
    """np.quantile (linear interpolation) over the global multiset of a
    scattered column: locate the two adjacent order statistics per prob via
    `global_order_stats` and interpolate. Deterministic across cloud sizes."""
    v = np.asarray(values, np.float64)
    v = v[np.isfinite(v)]
    if n_global is None:
        n_global = int(global_sum(np.asarray([v.size], np.int64))[0])
    if n_global == 0:
        return np.full(len(probs), np.nan)
    t = np.asarray(probs, np.float64) * (n_global - 1)
    k = np.floor(t).astype(np.int64)
    frac = t - k
    k2 = np.minimum(k + 1, n_global - 1)
    ks = np.concatenate([k, k2])
    xs = global_order_stats(v, ks)
    xk, xk2 = xs[: len(k)], xs[len(k):]
    return xk + frac * (xk2 - xk)


# -- canonical row layout (pod training) -------------------------------------
#
# The quota layout above pads every rank's tail, so pad rows INTERLEAVE with
# real rows at rank boundaries in the global order. A padded block then holds
# a different subset of real rows than the same block of a single-process
# fit, and the f32 blocked fold — deterministic per layout — cannot be
# bit-identical across cloud sizes. The canonical layout fixes the geometry
# instead of the algorithm: all real rows stay contiguous in global ingest
# order, ALL pad sits at the global tail, and each rank owns an equal
# `npad // nproc` slice. Byte-range ingest already lands each rank within a
# few rows of its canonical slice, so the exchange moves only the misaligned
# boundary spans (exact byte transport), never the bulk.


def canonical_counts(counts: np.ndarray, npad: int) -> np.ndarray:
    """Per-rank REAL-row counts under the canonical equal split: rank r owns
    canonical rows [r·shard, (r+1)·shard) of [real rows | tail pad]."""
    counts = np.asarray(counts, np.int64)
    nproc = len(counts)
    if npad % nproc:
        raise ValueError(f"npad {npad} not divisible by nproc {nproc}")
    shard = npad // nproc
    n_global = int(counts.sum())
    starts = np.minimum(np.arange(nproc, dtype=np.int64) * shard, n_global)
    stops = np.minimum((np.arange(nproc, dtype=np.int64) + 1) * shard,
                       n_global)
    return stops - starts


def export_spans(src_counts: np.ndarray, dst_counts: np.ndarray,
                 rank: int) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """Pure routing math (unit-tested in-process): the (global_start, length)
    head and tail spans of `rank`'s source rows that fall OUTSIDE its
    destination range when the global row concatenation is re-split from
    `src_counts` to `dst_counts`. Both count vectors must sum to the same
    global total."""
    src = np.asarray(src_counts, np.int64)
    dst = np.asarray(dst_counts, np.int64)
    soff = int(src[:rank].sum())
    sn = int(src[rank])
    doff = int(dst[:rank].sum())
    dn = int(dst[rank])
    head_stop = min(soff + sn, doff)
    head = (soff, max(head_stop - soff, 0))
    tail_start = max(soff, doff + dn)
    tail = (tail_start, max(soff + sn - tail_start, 0))
    return head, tail


def exchange_rows(local: np.ndarray, src_counts: np.ndarray,
                  dst_counts: np.ndarray) -> np.ndarray:
    """Re-split the conceptual global row concatenation from `src_counts`
    to `dst_counts`: each rank exports only the rows outside its own
    destination range (one small allgather of the boundary spans, exact
    byte transport) and assembles its destination slice from the local
    overlap plus imports. O(misalignment) traffic, not O(n)."""
    a = np.ascontiguousarray(local)
    src = np.asarray(src_counts, np.int64)
    dst = np.asarray(dst_counts, np.int64)
    if int(src.sum()) != int(dst.sum()):
        raise ValueError(f"count mismatch: {src.sum()} != {dst.sum()}")
    if not multiprocess():
        return a
    import jax

    r = jax.process_index()
    soff, sn = int(src[:r].sum()), int(src[r])
    doff, dn = int(dst[:r].sum()), int(dst[r])
    if a.shape[0] != sn:
        raise ValueError(f"rank {r} holds {a.shape[0]} rows, counts say {sn}")
    (hs, hl), (ts, tl) = export_spans(src, dst, r)
    header = np.asarray([hs, hl, ts, tl], np.int64).tobytes()
    payload = (header + a[hs - soff: hs - soff + hl].tobytes()
               + a[ts - soff: ts - soff + tl].tobytes())
    blobs = allgather_bytes(payload)
    trail = a.shape[1:]
    rowbytes = int(a.dtype.itemsize * int(np.prod(trail, dtype=np.int64)))
    out = np.empty((dn,) + trail, a.dtype)
    ov_lo, ov_hi = max(soff, doff), min(soff + sn, doff + dn)
    if ov_hi > ov_lo:
        out[ov_lo - doff: ov_hi - doff] = a[ov_lo - soff: ov_hi - soff]
    covered = max(ov_hi - ov_lo, 0)
    for blob in blobs:
        ghs, ghl, gts, gtl = np.frombuffer(blob[:32], np.int64)
        off = 32
        for gstart, glen in ((int(ghs), int(ghl)), (int(gts), int(gtl))):
            span = blob[off: off + glen * rowbytes]
            off += glen * rowbytes
            lo, hi = max(gstart, doff), min(gstart + glen, doff + dn)
            if hi > lo:
                rows = np.frombuffer(span, a.dtype).reshape((glen,) + trail)
                out[lo - doff: hi - doff] = rows[lo - gstart: hi - gstart]
                covered += hi - lo
    if covered != dn:
        raise RuntimeError(
            f"rank {r}: canonical exchange covered {covered}/{dn} rows")
    return out


def to_canonical(local: np.ndarray, npad: int,
                 counts: Optional[np.ndarray] = None, fill=0) -> np.ndarray:
    """This rank's canonical slice (npad // nproc rows) of the global padded
    layout [all real rows in ingest order | tail pad]. Single-process: the
    local rows padded to npad — the exact layout a 1-device fit builds, which
    is what makes the pod blocked fold bit-identical to it."""
    a = np.ascontiguousarray(local)
    if not multiprocess():
        pad = npad - a.shape[0]
        if pad:
            a = np.concatenate(
                [a, np.full((pad,) + a.shape[1:], fill, a.dtype)])
        return a
    if counts is None:
        counts = row_counts(a.shape[0])
    out = exchange_rows(a, counts, canonical_counts(counts, npad))
    shard = npad // len(counts)
    pad = shard - out.shape[0]
    if pad:
        out = np.concatenate(
            [out, np.full((pad,) + out.shape[1:], fill, out.dtype)])
    return out


def from_canonical(local_padded: np.ndarray, npad: int,
                   counts: np.ndarray) -> np.ndarray:
    """Inverse of `to_canonical`: this rank's INGEST rows recovered from its
    canonical-layout slice (metric read-back — training margins, OOB sums —
    must pair with the local frame's response rows)."""
    counts = np.asarray(counts, np.int64)
    if not multiprocess():
        return np.ascontiguousarray(local_padded[: int(counts.sum())])
    import jax

    r = jax.process_index()
    canon = canonical_counts(counts, npad)
    return exchange_rows(
        np.ascontiguousarray(local_padded[: int(canon[r])]), canon, counts)


def local_shard(garr) -> np.ndarray:
    """This process's rows of a global row-sharded array, in device order."""
    shards = sorted(garr.addressable_shards, key=lambda s: s.index[0].start)
    return np.concatenate([np.asarray(s.data) for s in shards])


def to_local(a) -> np.ndarray:
    """Host view of `a`: the local shard for process-spanning global arrays,
    plain np.asarray otherwise — the one rule for bringing possibly-sharded
    values to the host in metric/scoring code."""
    if multiprocess() and getattr(a, "is_fully_addressable", True) is False:
        return local_shard(a)
    return np.asarray(a)
