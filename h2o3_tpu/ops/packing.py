"""Sub-byte bin-code packing — the device-resident compressed code matrix.

The quantized (N, F) bin-code matrix is both the dominant fixed H2D cost
and, once resident, the dominant
per-level HBM read of the tree hot loop (every histogram pass streams it).
4/5/6-bit packing cuts both 2-4x — the ELLPACK-style compressed storage of
"XGBoost: Scalable GPU Accelerated Learning" (arXiv 1806.11248), which
keeps bit-packed feature codes resident and decodes in-kernel.

Layout: codes are packed ALONG ROWS in fixed groups so any row-slice at a
group boundary unpacks standalone (row-chunked consumers never touch
neighbouring groups):

=====  ==========  ===========  =========================================
bits   rows/group  bytes/group  bitstream
=====  ==========  ===========  =========================================
4      2           1            row codes MSB-first, 4 bits each
5      8           5            row codes MSB-first, 5 bits each
6      4           3            row codes MSB-first, 6 bits each
=====  ==========  ===========  =========================================

Consumers:

* ``unpack_device`` — whole-matrix widening on device. Its row groups are
  read through a reshape, ``packed.reshape(k, bytes, F)[:, i]``: the strided
  slice ``packed[i::bytes]`` it used to take lowers to a gather on the
  installed jax (38 ms a slice at 1.4M packed rows on a v5e). A full-width
  resident fit ships packed and materializes full width once.
* ``ops/histogram.build_code_operand`` — ONE program a fit widens the
  resident words into the Pallas histogram kernel's operand (feature-major
  float32, padded to the kernel's blocks) where the fit's plan runs that
  kernel on one device; the tree programs take the operand as an argument
  and widen nothing. The resident, cached, uploaded matrix stays packed.
* Every other tree program (CPU fits on the ``segment`` kernel, the
  blocked and mesh lanes, the streamed blocks, a level that fell back to
  ``segment``) widens in-graph, once per program (XLA shares the widened
  codes across the program's levels).
* ``models/tree._row_codes`` — the partition step's per-row
  selected-feature code: a dense select over the feature axis of the SAME
  feature-major codes the kernel takes (the fit's operand where there is
  one). Nothing gathers into the packed words: on the TPU a per-row
  gather costs ~20 ns a row, the select is a streaming read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# rows per pack group / packed bytes per group, by bit width
GROUP_ROWS = {4: 2, 5: 8, 6: 4}
GROUP_BYTES = {4: 1, 5: 5, 6: 3}


def pack_bits_for(nbins: int, nrows: int) -> int:
    """Narrowest usable packing for codes < nbins (0 = ship unpacked).
    Rows must be a multiple of the group size (padded row counts are
    multiples of 8)."""
    for bits, group in ((4, 2), (5, 8), (6, 4)):
        if nbins <= (1 << bits) and nrows % group == 0:
            return bits
    return 0


def packed_nrows(packed_rows: int, bits: int) -> int:
    """Unpacked row count of a packed array with `packed_rows` rows."""
    return packed_rows * 8 // bits


def pack_host(codes: np.ndarray, bits: int) -> np.ndarray:
    """Pack uint8 bin codes < 2^bits into `bits` bits per value along rows.
    bits ∈ {4, 5, 6}: {2, 8, 4} row-groups → {1, 5, 3} bytes."""
    if bits == 4:
        return (codes[0::2] << 4) | codes[1::2]
    if bits == 5:
        a, b, c, d, e, f, g, hh = (codes[i::8] for i in range(8))
        out = np.empty((5 * a.shape[0],) + codes.shape[1:], np.uint8)
        out[0::5] = (a << 3) | (b >> 2)
        out[1::5] = ((b & 0x3) << 6) | (c << 1) | (d >> 4)
        out[2::5] = ((d & 0xF) << 4) | (e >> 1)
        out[3::5] = ((e & 0x1) << 7) | (f << 2) | (g >> 3)
        out[4::5] = ((g & 0x7) << 5) | hh
        return out
    # 6-bit: stays uint8 end to end (max 63<<2 = 252)
    a, b, c, d = codes[0::4], codes[1::4], codes[2::4], codes[3::4]
    out = np.empty((3 * a.shape[0],) + codes.shape[1:], np.uint8)
    out[0::3] = (a << 2) | (b >> 4)
    out[1::3] = ((b & 0xF) << 4) | (c >> 2)
    out[2::3] = ((c & 0x3) << 6) | d
    return out


def pack_host_range(codes: np.ndarray, bits: int, r0: int, r1: int) -> np.ndarray:
    """Pack rows ``[r0, r1)`` of a full-width code matrix — the block-wise
    ingest half of the out-of-core path (ISSUE 14): building one streamed
    block touches O(block) host memory (a view slice plus the packed block
    output), never a whole-matrix packed transient. `r0`/`r1` must sit on
    pack-group boundaries (block grids are multiples of 8 rows, and every
    group size divides 8), so the block's bitstream is byte-identical to
    the corresponding slice of a whole-matrix `pack_host`."""
    group = GROUP_ROWS[bits]
    if r0 % group or r1 % group:
        raise ValueError(
            f"block [{r0}, {r1}) is not aligned to the {group}-row pack "
            f"group of {bits}-bit codes")
    return pack_host(codes[r0:r1], bits)


def unpack_host(packed: np.ndarray, bits: int) -> np.ndarray:
    """Inverse of `pack_host` on host numpy (the streamed GOSS gather's
    per-block widening) — bit-exact with `unpack_device`."""
    if bits == 4:
        k = packed.shape[0]
        out = np.empty((2 * k,) + packed.shape[1:], np.uint8)
        out[0::2] = packed >> 4
        out[1::2] = packed & 0xF
        return out
    if bits == 5:
        b = [packed[i::5].astype(np.uint16) for i in range(5)]
        k = packed.shape[0] // 5
        out = np.empty((8 * k,) + packed.shape[1:], np.uint8)
        out[0::8] = b[0] >> 3
        out[1::8] = ((b[0] & 0x7) << 2) | (b[1] >> 6)
        out[2::8] = (b[1] >> 1) & 0x1F
        out[3::8] = ((b[1] & 0x1) << 4) | (b[2] >> 4)
        out[4::8] = ((b[2] & 0xF) << 1) | (b[3] >> 7)
        out[5::8] = (b[3] >> 2) & 0x1F
        out[6::8] = ((b[3] & 0x3) << 3) | (b[4] >> 5)
        out[7::8] = b[4] & 0x1F
        return out
    b0 = packed[0::3].astype(np.uint16)
    b1 = packed[1::3].astype(np.uint16)
    b2 = packed[2::3].astype(np.uint16)
    k = packed.shape[0] // 3
    out = np.empty((4 * k,) + packed.shape[1:], np.uint8)
    out[0::4] = b0 >> 2
    out[1::4] = ((b0 & 0x3) << 4) | (b1 >> 4)
    out[2::4] = ((b1 & 0xF) << 2) | (b2 >> 6)
    out[3::4] = b2 & 0x3F
    return out


def _group_bytes(packed, bits: int):
    """The packed words as one (k, F) uint16 array per byte of a pack group.
    A reshape and a middle-axis index, not `packed[i::nbytes]`: the strided
    slice lowers to `stablehlo.gather` on the installed jax."""
    nbytes = GROUP_BYTES[bits]
    grouped = packed.reshape((-1, nbytes) + packed.shape[1:])
    return [grouped[:, i].astype(jnp.uint16) for i in range(nbytes)]


@functools.partial(jax.jit, static_argnames=("bits",))
def unpack_device(packed, bits: int):
    """Inverse of pack_host, on device: one widening program."""
    if bits == 4:
        vals = [packed >> 4, packed & 0xF]
    elif bits == 5:
        b = _group_bytes(packed, 5)
        vals = [
            b[0] >> 3,
            ((b[0] & 0x7) << 2) | (b[1] >> 6),
            (b[1] >> 1) & 0x1F,
            ((b[1] & 0x1) << 4) | (b[2] >> 4),
            ((b[2] & 0xF) << 1) | (b[3] >> 7),
            (b[3] >> 2) & 0x1F,
            ((b[3] & 0x3) << 3) | (b[4] >> 5),
            b[4] & 0x1F,
        ]
    else:
        b0, b1, b2 = _group_bytes(packed, 6)
        vals = [b0 >> 2, ((b0 & 0x3) << 4) | (b1 >> 4),
                ((b1 & 0xF) << 2) | (b2 >> 6), b2 & 0x3F]
    k = packed.shape[0] // GROUP_BYTES[bits]
    out = jnp.stack(vals, axis=1).reshape(
        (GROUP_ROWS[bits] * k,) + packed.shape[1:])
    return out.astype(jnp.uint8)
