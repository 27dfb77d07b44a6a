"""Host work buffers that outlive the call that filled them.

glibc hands any request above 32 MiB (the ceiling of its mmap threshold) to
`mmap` and gives it back with `munmap`, so every row-sized numpy temporary of
a frame past ~4M rows (58 MB as float64 at 7,250,000) is made of pages the
kernel has never touched: the arithmetic then waits for page faults, about
1 ms a MB on a v5e host's sandboxed kernel (`PERF.md` §5, §7 question 9),
and what a fault costs differs from one process and one machine to the next
where what the arithmetic costs does not. The host stages of a fit that walk
all rows (`DataInfo.device_design`'s statistics, `ModelMetricsBinomial.make`)
therefore compute in buffers taken here, which a thread keeps from one fit to
the next: after the first fit of a size they touch no new page.

A buffer is a thread's own (two fits on two threads never share one) and is
named by its use. `take` hands out the SAME memory for the same name every
time, so what is computed in it must not leave the function that took it:
results that a caller keeps are copied out or reduced to scalars first.
"""

from __future__ import annotations

import threading

import numpy as np

_local = threading.local()


def take(name: str, n: int, dtype) -> np.ndarray:
    """`n` uninitialized `dtype`s over this thread's buffer `name`, valid
    until this thread takes `name` again."""
    pool = _local.__dict__.setdefault("pool", {})
    need = int(n) * np.dtype(dtype).itemsize
    raw = pool.get(name)
    if raw is None or raw.nbytes < need:
        raw = pool[name] = np.empty(need, np.uint8)
    return raw[:need].view(dtype)


def fresh(name: str, n: int, dtype) -> np.ndarray:
    """`take`'s signature over a new array: for callers whose result is
    kept by someone else."""
    return np.empty(int(n), dtype)


def release() -> None:
    """Give this thread's buffers back."""
    _local.__dict__.pop("pool", None)


def blocks(n: int, size: int = 1 << 18):
    """`slice`s that cover `range(n)` in runs of `size`: a temporary made a
    block at a time stays in the cache and under malloc's own free lists."""
    for s in range(0, int(n), size):
        yield slice(s, min(int(n), s + size))
