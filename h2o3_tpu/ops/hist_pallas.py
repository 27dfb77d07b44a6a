"""Pallas tpu_hist kernel — fused gradient-histogram accumulation.

Reference parity: this is the TPU-native equivalent of XGBoost's CUDA
`gpu_hist` updater (shared-memory atomics histogram kernel inside
`libxgboost4j_gpu.so`) and of `hex/tree/DHistogram.updateHisto`'s per-row
accumulate loop (see SURVEY.md §3.2 — the hot loop of the whole platform).

Strategy: TPUs have no scatter-atomics, so the accumulation is expressed as
a one-hot matmul that rides the MXU — but unlike the XLA-level `onehot`
path in `histogram.py`, the kernel never materializes the (rows × nodes·bins)
one-hot in HBM: each grid step builds it for one row-chunk directly in VMEM,
multiplies, and accumulates into the output block, which stays resident
across the sequential TPU grid (output-revisiting pattern). HBM traffic is
therefore just codes-in + histogram-out.

Layout: grid = (row_chunks, F/8), feature blocks innermost; per row chunk
the (3L, R) node-weighted values are built once in scratch, and each step
computes hist[fb, 3L, 8·Bp] += weighted(3L,R) @ bin_onehot(R, 8·Bp).

The one-hot (ISSUE 36): built a FEATURE at a time on a bin axis padded to
the sublane tile, `Bp = bins_padded(B)`. A feature's code row is
broadcast over `Bp` sublanes and compared with a bin index that runs along
the sublanes of its `(Bp, R)` slab; the eight float32 slabs are stacked at
multiples of eight sublanes, which moves nothing, and the whole `(8·Bp, R)`
array is cast to bfloat16 once, on whole tiles. Why padded: with `B` no
multiple of 8 (HIGGS's 21) an 8-sublane tile of the `(8·B, R)` array
straddles two features, and Mosaic built it — then as
`repeat(codes, B) == iota % B` — through VMEM, one sublane-strided store,
1.4 loads, 0.75 sublane rotates and 4.3 selects for every one-hot vreg
(31.7 ms a HIGGS pass; 7.2 as it is built now). The padded bins match no
code (codes are < B; pad rows and pad features are -1), so their columns
are exact zeros, and the wrapper slices them off: the op's result keeps
its shape and its bits. The 0/1 is a `where` in float32 and not an
`astype` of the compare, which Mosaic lowers through int32 (a select AND
a convert a vreg). `tests/test_chip_compile.py` reads the lowered step and
holds it to no strided store, no rotate and no remainder but the weighted
scratch's `% L`.

Packed-code input (ISSUE 7): the device-RESIDENT matrix is the 4/5/6-bit
`ops.packing` word matrix; the kernel's operand is its feature-major
float32 widening (`ops.histogram.feature_major`). A one-device fit builds
it ONCE, in a program of its own, already padded to this kernel's blocks
(`ops.histogram.build_code_operand`), and hands it to every tree program;
the blocked and mesh lanes still widen in-graph, once per tree program.
The resident/cached/uploaded artifact stays packed either way. The operand
has a second reader in the tree program: the partition step's dense select
of each row's split-feature code (`models/tree._row_codes`). In-KERNEL
sub-byte decode was evaluated and deferred: the factored kernel reads
codes as 8-sublane f32 feature blocks, while Mosaic's int8 minimum tile is
(32, 128) — a u8 packed operand would force a 32-feature block
restructure (4× one-hot VMEM per step) or lane-strided unpacking of the
interleaved row groups, neither validatable without a chip in the loop.
See docs/perf.md appendix; ROADMAP items 1/3 stream the same packed
representation and inherit whichever decode lands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FACTORED_ROW_CHUNK = 8192
# The scoped-VMEM budget the kernel is compiled under, STATED in the
# pallas_call instead of left to the compiler's per-generation default (it
# equals the v5e default). `histogram._factored_row_chunk` sizes the row
# chunk against this number; tests/test_chip_compile.py holds the pair to
# what the v5e compiler accepts.
VMEM_LIMIT_BYTES = 16 << 20


_FB = 8  # features per block (TPU sublane granule)
# what the bin axis is padded to: the sublanes of a float32 tile. (16, the
# bfloat16 tile, is 10 % slower at 21 bins on the chip: PERF.md §6, PR 36)
_BIN_TILE = 8


def bins_padded(nbins: int) -> int:
    """`Bp`: the kernel's bin axis, `nbins` up to a multiple of the float32
    sublane tile, so that every 8-sublane tile of the one-hot belongs to ONE
    feature. A function of `nbins` alone; `nbins` itself at 16, 64, 256 and
    1,024."""
    return -(-nbins // _BIN_TILE) * _BIN_TILE


def _hist_kernel_factored(codes_ref, node_ref, vals_ref, out_ref, w_ref,
                          *, L: int, Bp: int):
    """Factored VMEM kernel: grid (row_chunks, F/8), feature-blocks innermost.

    Per chunk (at fb==0) the (3L, R) node-weighted value matrix is built once
    in scratch; each step builds ONE (8·Bp, R) bin one-hot covering its whole
    8-feature block, a feature's (Bp, R) slab at a time, and runs a single
    (3L,R)·(R,8·Bp) MXU matmul, accumulating into the (1, 3L, 8·Bp) output
    block. HBM traffic is codes-in + the small output blocks — the (R, L·B)
    one-hot never exists anywhere."""
    step = pl.program_id(0)
    fb = pl.program_id(1)

    @pl.when(fb == 0)
    def _weighted():
        # w[c·L+l, r] = vals[c, r] · [node[r] == l]
        l_idx = jax.lax.broadcasted_iota(jnp.int32, (3 * L, 1), 0) % L
        node = node_ref[...]                      # (1, R) i32
        mask = (node == l_idx).astype(jnp.float32)  # (3L, R)
        vals = vals_ref[...]                      # (3, R) f32
        vals3 = jnp.concatenate(
            [jnp.broadcast_to(vals[c][None, :], (L, vals.shape[1]))
             for c in range(3)], axis=0)          # (3L, R)
        w_ref[...] = vals3 * mask

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    R = w_ref.shape[1]
    wmat = w_ref[...].astype(jnp.bfloat16)
    # one (8·Bp, R) one-hot for the whole 8-feature block → ONE MXU matmul
    # per grid step instead of 8 tiny (3L,B) ones (output 3L × 8·Bp utilizes
    # the systolic array far better). Slab j is feature j: its code row over
    # Bp sublanes against the bin index of each sublane; a pad bin (>= B), a
    # pad row and a pad feature (-1) match nothing
    bins = jax.lax.broadcasted_iota(jnp.int32, (Bp, R), 0).astype(jnp.float32)
    slabs = [jnp.where(codes_ref[j:j + 1, :] == bins, 1.0, 0.0)
             for j in range(_FB)]                 # 8 × (Bp, R) f32
    bin_oh_t = jnp.concatenate(slabs, axis=0).astype(jnp.bfloat16)
    h = jax.lax.dot_general(
        wmat, bin_oh_t,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                              # (3L, 8·Bp)
    out_ref[0] += h


@functools.partial(jax.jit, static_argnames=("n_nodes", "nbins", "row_chunk",
                                             "n_features"))
def build_histograms_pallas_factored(
    codes_t_bf: jax.Array,   # (F, N) float32 — PRE-TRANSPOSED feature-major
    node_id: jax.Array,      # (N,) int32
    vals: jax.Array,         # (3, N) f32, weight-masked
    n_nodes: int,
    nbins: int,
    row_chunk: int = FACTORED_ROW_CHUNK,
    n_features: int = 0,
) -> jax.Array:
    """(n_nodes, F, nbins, 3) histogram; the TPU fast path for L·R fitting
    VMEM (the scratch is (3L, R) f32). `codes_t_bf` may arrive already
    padded with -1 (`ops.histogram.build_code_operand`: features to the
    8-feature block, rows to a multiple of `row_chunk`); `n_features` then
    says how many of its rows are features, and nothing of it is padded
    here."""
    Fin, Nin = codes_t_bf.shape
    F = n_features or Fin
    N = node_id.shape[0]
    L, B = n_nodes, nbins
    Bp = bins_padded(B)
    R = row_chunk
    npad = ((max(N, Nin) + R - 1) // R) * R
    Fpad = ((F + _FB - 1) // _FB) * _FB
    if (Fin, Nin) != (Fpad, npad):
        # pad codes with an out-of-range bin so padded rows match no bin
        codes_t_bf = jnp.pad(codes_t_bf, ((0, Fpad - Fin), (0, npad - Nin)),
                             constant_values=-1.0)
    if npad != N:
        node_id = jnp.pad(node_id.astype(jnp.int32), (0, npad - N))
        vals = jnp.pad(vals, ((0, 0), (0, npad - N)))
    node2 = node_id.astype(jnp.int32)[None, :]
    grid = (npad // R, Fpad // _FB)
    out = pl.pallas_call(
        functools.partial(_hist_kernel_factored, L=L, Bp=Bp),
        out_shape=jax.ShapeDtypeStruct((Fpad // _FB, 3 * L, _FB * Bp),
                                       jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_FB, R), lambda i, f: (f, i)),  # codes_t chunk
            pl.BlockSpec((1, R), lambda i, f: (0, i)),    # node chunk
            pl.BlockSpec((3, R), lambda i, f: (0, i)),    # vals chunk
        ],
        out_specs=pl.BlockSpec((1, 3 * L, _FB * Bp), lambda i, f: (f, 0, 0)),
        scratch_shapes=[pltpu.VMEM((3 * L, R), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="tree_hist_factored",
    )(codes_t_bf, node2, vals)
    # (Fpad/8, 3L, 8·Bp) → the real bins → (Fpad, 3L, B) → (L, F, B, 3)
    out = out.reshape(Fpad // _FB, 3 * L, _FB, Bp)
    if Bp != B:
        out = out[..., :B]
    out = out.transpose(0, 2, 1, 3)
    out = out.reshape(Fpad, 3 * L, B)[:F]
    return out.reshape(F, 3, L, B).transpose(2, 0, 3, 1)
