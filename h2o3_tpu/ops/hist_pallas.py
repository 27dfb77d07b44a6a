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
computes hist[fb, 3L, 8·B] += weighted(3L,R) @ bin_onehot(R, 8·B).

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


def _hist_kernel_factored(codes_ref, node_ref, vals_ref, out_ref, w_ref,
                          *, L: int, B: int):
    """Factored VMEM kernel: grid (row_chunks, F/8), feature-blocks innermost.

    Per chunk (at fb==0) the (3L, R) node-weighted value matrix is built once
    in scratch; each step builds ONE (8B, R) bin one-hot covering its whole
    8-feature block and runs a single (3L,R)·(R,8B) MXU matmul, accumulating
    into the (1, 3L, 8B) output block. HBM traffic is codes-in + the small
    output blocks — the (R, L·B) one-hot never exists anywhere."""
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
    # one (8B, R) one-hot for the whole 8-feature block → ONE MXU matmul per
    # grid step instead of 8 tiny (3L,B) ones (output 3L × 8B utilizes the
    # systolic array far better)
    fb_iota = jax.lax.broadcasted_iota(jnp.int32, (_FB * B, R), 0)
    b_of = (fb_iota % B).astype(jnp.float32)
    codes_blk = codes_ref[...]    # (8, R) f32
    code_rows = jnp.repeat(codes_blk, B, axis=0)             # (8B, R)
    bin_oh_t = (code_rows == b_of).astype(jnp.bfloat16)      # (8B, R)
    h = jax.lax.dot_general(
        wmat, bin_oh_t,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                              # (3L, 8B)
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
        functools.partial(_hist_kernel_factored, L=L, B=B),
        out_shape=jax.ShapeDtypeStruct((Fpad // _FB, 3 * L, _FB * B), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_FB, R), lambda i, f: (f, i)),  # codes_t chunk
            pl.BlockSpec((1, R), lambda i, f: (0, i)),    # node chunk
            pl.BlockSpec((3, R), lambda i, f: (0, i)),    # vals chunk
        ],
        out_specs=pl.BlockSpec((1, 3 * L, _FB * B), lambda i, f: (f, 0, 0)),
        scratch_shapes=[pltpu.VMEM((3 * L, R), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="tree_hist_factored",
    )(codes_t_bf, node2, vals)
    # (Fpad/8, 3L, 8B) → (Fpad, 3L, B) → (L, F, B, 3)
    out = out.reshape(Fpad // _FB, 3 * L, _FB, B).transpose(0, 2, 1, 3)
    out = out.reshape(Fpad, 3 * L, B)[:F]
    return out.reshape(F, 3, L, B).transpose(2, 0, 3, 1)
