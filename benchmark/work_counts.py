"""Required work of one step, from shapes alone, and the least time a chip
could take for it. The counts are of the ALGORITHM's work, kept here where a
later PR cannot change them: a kernel that does more than this (a one-hot
matmul in place of a scatter, six bf16 passes for one float32 product) does
not earn a higher share by it. One module per algorithm under counts/, found
by the configuration's `algo`."""

from __future__ import annotations

import os

import manifest

_PEAKS = manifest.load_json(os.path.join(manifest.HERE, "peaks.json"))


def peaks(device_kind: str) -> dict:
    if device_kind not in _PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json: an unknown device is an error")
    return _PEAKS[device_kind]


def least_time(work: dict, device_kind: str) -> dict:
    """Roofline floor of {"ops", "bytes"}: the larger of operations over the
    peak rate and bytes over the peak bandwidth, and which of the two."""
    pk = peaks(device_kind)
    t_ops = work["ops"] / pk["flops_bf16"]
    t_bytes = work["bytes"] / pk["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound": "ops" if t_ops >= t_bytes else "bytes"}


def counts(algo: str):
    return manifest.load_module("counts", algo)
