"""Shared histogram-tree machinery — the engine under GBM / DRF / IF / XGBoost.

Reference parity: `h2o-algos/src/main/java/hex/tree/SharedTree.java`
(per-level driver loop), `hex/tree/DTree.java` (`DecidedNode`,
`UndecidedNode`, `Split.findBestSplitPoint` — argmax squared-error reduction
over bins), `hex/tree/ScoreBuildHistogram2.java` (the fused
score-build-histogram MRTask), and XGBoost's `gpu_hist` updater.

TPU-first redesign, not a translation:

* The reference grows trees with dynamic node objects and per-level chunk
  scans. Here a tree is a **perfect binary heap of static depth** (arrays of
  size 2^(D+1)-1) so the whole per-tree build is ONE jitted XLA program:
  unrolled levels, each = histogram → best-split → partition, all fused.
* Row partition state is a per-row level-local node index (the reference's
  "row-to-leaf assignment vec", `SharedTree` nids Vec); rows in decided-leaf
  subtrees keep flowing left so every depth-D cell inherits its deciding
  ancestor's rows — which makes the cell's Newton value equal the ancestor
  leaf's value, eliminating all dynamic control flow.
* Cross-host histogram merge is `lax.psum` (MRTask.reduce / Rabit allreduce).
* NAs live in a reserved last bin and traverse right; the split search can
  therefore isolate them (DHistogram's NA bucket semantics).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import packing
from ..ops.histogram import (build_histograms, feature_major,
                             ordered_axis_fold, record_partition_read)


class Tree(NamedTuple):
    """One decision tree as flat heap arrays (length 2^(D+1)-1)."""

    feat: jax.Array      # int32, split feature per node (0 where not split)
    bin: jax.Array       # int32, split bin per node
    thr: jax.Array       # f32, raw-value threshold (left iff x <= thr)
    is_split: jax.Array  # bool
    value: jax.Array     # f32, Newton leaf value at every node


def heap_size(depth: int) -> int:
    return 2 ** (depth + 1) - 1


def compact_switch_depth(max_depth: int, compact_cap: int) -> int:
    """First level handled by active-node compaction (max_depth = never) —
    the ONE switch rule shared by `build_tree` and the driver's fit-plan
    recorder (`ops.histogram.record_fit_plan`), so the recorded plan
    cannot diverge from the structure that actually runs."""
    if not compact_cap:
        return max_depth
    for d in range(max_depth):
        if 2 ** d > compact_cap:
            return d
    return max_depth


def histogram_level_plan(max_depth: int, compact_cap: int = 0):
    """(label, n_nodes) of each histogram pass a depthwise `build_tree`
    dispatches: d0 over 1 node, deeper dense levels over the PARENT count
    (sibling subtraction builds only left children), then the compact
    transition + per-level passes over compact_cap+1 slots. Consumed by
    the driver's per-fit kernel-plan recording."""
    d_sw = compact_switch_depth(max_depth, compact_cap)
    levels = [("d%d" % d, 1 if d == 0 else 2 ** (d - 1))
              for d in range(min(d_sw, max_depth))]
    if d_sw < max_depth:
        levels.append(("compact_transition", compact_cap + 1))
        levels += [("d%d" % d, compact_cap + 1)
                   for d in range(d_sw, max_depth)]
    return levels


# one-hot contraction beats a per-row dynamic gather on TPU by ~10× (the
# VPU has no fast per-lane table lookup; XLA serializes row gathers), but
# materializes an (N, L) operand — only worth it for small tables. For the
# partition's read of a row's code (`partition_read`) it governs only the
# fits that have no fit-lifetime code operand: with one, the select reads
# that operand at any width
_ONEHOT_LOOKUP_MAX = 128


def _lookup_int(table: jax.Array, idx: jax.Array, L: int) -> jax.Array:
    """table[idx] for an int32 table of length L (exact)."""
    if L > _ONEHOT_LOOKUP_MAX:
        return table[idx]
    oh = idx[:, None] == jnp.arange(L, dtype=jnp.int32)[None, :]
    return jnp.where(oh, table[None, :], 0).sum(axis=1)


def _lookup_bool(table: jax.Array, idx: jax.Array, L: int) -> jax.Array:
    """table[idx] for a bool table of length L."""
    if L > _ONEHOT_LOOKUP_MAX:
        return table[idx]
    oh = idx[:, None] == jnp.arange(L, dtype=jnp.int32)[None, :]
    return (oh & table[None, :]).any(axis=1)


def partition_read(n_features: int, code_operand: str = "program") -> str:
    """How a partition step reads a row's split-feature code:
    ``"select"`` (dense one-hot over the feature axis) or ``"gather"``
    (per-row gather from the row-major codes). The ONE rule, shared by
    `_row_codes` and the tree fit's plan
    (`ops.histogram.record_fit_plan`), so the recorded read cannot diverge
    from the one that runs.

    `code_operand` is the fit's form of the Pallas kernel's code operand
    (`ops.histogram.code_operand_form`). ``"fit"``: the tree program is
    handed the feature-major float32 operand, and the select reads it at
    ANY width. By bytes: the select streams F × N × 4 bytes a level, about
    6 ps a row a feature on a v5e, where the gather costs about 20 ns a
    row whatever F is — the select wins up to roughly 3,000 features, and
    an operand that wide would not fit on the chip. ``"program"`` (no
    operand: CPU ``segment`` fits, the mesh and blocks lanes, streamed
    blocks, the scoring walk, lossguide): the select would widen the
    codes feature-major inside every program, so frames wider than
    `_ONEHOT_LOOKUP_MAX` keep the gather."""
    if code_operand == "fit" or n_features <= _ONEHOT_LOOKUP_MAX:
        return "select"
    return "gather"


def _row_codes(codes: jax.Array, rf: jax.Array, pack_bits: int = 0,
               operand: Optional[jax.Array] = None) -> jax.Array:
    """codes[i, rf[i]] as int32 — the code of the feature row i's node
    split on; the one place every partition step and training-time walk
    over packed codes asks for it.

    The select reads densely over the feature axis of the (F, N) float32
    feature-major codes the Pallas histogram kernel takes: every level's
    select is one more streaming read of that buffer. On the TPU a per-row
    gather costs ~20 ns a row (228.6 ms at 11.5M rows) where the
    compare-select-sum is bound by bytes. Given `operand` — the fit's
    `ops.histogram.build_code_operand` array, padded past F features and N
    rows with -1 — the select reads its first F × N at every width, and
    `codes` is not read at all; without it, packed codes (`pack_bits` in
    {4, 5, 6}) are widened and laid feature-major in this program
    (`ops.histogram.feature_major`), and frames wider than
    `_ONEHOT_LOOKUP_MAX` keep the gather from the row-major codes (widened
    here when packed; `partition_read`). The operand holds the same codes
    as exact float32 integers and exactly one feature matches a row, so
    both reads give the same code."""
    if pack_bits:
        F = codes.shape[1]
        N = packing.packed_nrows(codes.shape[0], pack_bits)
    else:
        N, F = codes.shape
    read = partition_read(F, "program" if operand is None else "fit")
    record_partition_read(read)
    if operand is not None:
        codes_fm = operand[:F, :N]
    else:
        if pack_bits:
            codes = packing.unpack_device(codes, pack_bits)
        if read == "gather":
            return jnp.take_along_axis(
                codes, rf[:, None].astype(jnp.int32),
                axis=1)[:, 0].astype(jnp.int32)
        codes_fm = feature_major(codes)
    feat_oh = rf[None, :] == jnp.arange(F, dtype=jnp.int32)[:, None]
    return jnp.where(feat_oh, codes_fm, 0.0).sum(axis=0).astype(jnp.int32)


@jax.named_scope("tree.leaf")
def _leaf_totals(ids, vals3, nseg: int, axis_name, n_shard_blocks: int,
                 onehot_ok: bool):
    """Exact per-cell {Σw, Σg·w, Σh·w} totals of the final tree level —
    (nseg, 3). With ``n_shard_blocks`` the accumulation runs per contiguous
    row block and folds deterministically (`ordered_axis_fold`), so leaf
    values are bit-stable across device counts; otherwise the historical
    single-pass + psum formulation is preserved bit-for-bit.

    `onehot_ok` selects the small-heap MXU one-hot matmul (Precision.
    HIGHEST — the TPU default would truncate the per-leaf g/h sums to
    bf16); the selection depends only on nseg, so every block (and every
    device count) runs the same kernel."""
    use_oh = onehot_ok and nseg <= 2 * _ONEHOT_LOOKUP_MAX

    def one(ids_b, vals_b):
        if use_oh:
            oh = (ids_b[:, None] == jnp.arange(nseg, dtype=jnp.int32)[None, :]
                  ).astype(jnp.float32)
            return jnp.dot(vals_b, oh, preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.HIGHEST).T
        return jax.ops.segment_sum(vals_b.T, ids_b, num_segments=nseg)

    if n_shard_blocks > 0:
        n = ids.shape[0]
        rows = n // n_shard_blocks
        parts = [one(ids[b * rows:(b + 1) * rows],
                     vals3[:, b * rows:(b + 1) * rows])
                 for b in range(n_shard_blocks)]
        return ordered_axis_fold(jnp.stack(parts), axis_name)
    tot = one(ids, vals3)
    if axis_name is not None:
        tot = jax.lax.psum(tot, axis_name)
    return tot


def _node_totals(hist):
    """(L,) node totals {Σw, Σg, Σh}: feature 0's bins (every feature sums
    to the same totals) folded by an EXPLICIT pairwise halving tree. A
    plain `sum(axis=bins)` leaves the association to the compiler, and the
    installed XLA picks a different one for the shard_map program than for
    the one-device blocks program (observed: 1 ulp apart on the root of
    bit-identical histograms) — the same reason `ordered_axis_fold` pins
    the cross-block merge by its expression tree. Zero-padding to a power
    of two is exact."""
    x = hist[:, 0]                                  # (L, B, 3)
    nb = x.shape[1]
    p2 = 1 << max(nb - 1, 0).bit_length()
    if p2 != nb:
        x = jnp.pad(x, ((0, 0), (0, p2 - nb), (0, 0)))
    while x.shape[1] > 1:
        half = x.shape[1] // 2
        x = x[:, :half] + x[:, half:]
    return x[:, 0, 0], x[:, 0, 1], x[:, 0, 2]


def _split_sums(hist):
    """(WL, GL, HL, WR, GR, HR) of every candidate split of a histogram whose
    last two axes are (bins, {w, g, h}): left of a split at bin b is the
    forward cumsum through b, right of it the bins ABOVE b (the NA bin
    included) folded from the right end — a reverse cumsum shifted by one.
    The right-hand sums are never `total - left`: at 11M rows a node's
    H ~ 2.75e6 has a float32 ulp of 0.25, so a tail child of 5-15 rows
    (HR ~ 1-4) taken by subtraction is lost in the rounding of two sums
    six orders larger, its gain GR^2/(HR+lambda) explodes or turns NaN and
    the search splits 11 rows off the root. Folded from the right, a tail
    child's sums are made of the few small bins it holds and carry their own
    relative precision whatever the node's total is. Both split searches
    (`_fused_level_best` of `build_tree` and the streamed step;
    `_search_splits` of lossguide growth) read this one function."""
    ax = hist.ndim - 2

    def right(x):
        above = jax.lax.cumsum(x, axis=ax, reverse=True)     # bins >= b
        return jnp.concatenate(
            [jax.lax.slice_in_dim(above, 1, None, axis=ax),
             jnp.zeros_like(jax.lax.slice_in_dim(above, 0, 1, axis=ax))],
            axis=ax)

    w, g, h = hist[..., 0], hist[..., 1], hist[..., 2]
    return (jnp.cumsum(w, axis=ax), jnp.cumsum(g, axis=ax),
            jnp.cumsum(h, axis=ax), right(w), right(g), right(h))


@jax.named_scope("tree.split")
def _fused_level_best(hist, node_ok, feat_mask, keep, nbins: int, min_rows,
                      reg_lambda, reg_alpha, gsum, hsum,
                      monotone=None, lo_lvl=None, hi_lvl=None):
    """The split search of a dense or a compact level: ONE sequential pass
    over features computes each feature's (L, B) gain tile and folds it
    into a running per-node best, so a level emits only the (L,) winner
    tuple and never materializes (L, F, B) temporaries (cumsums,
    thresholded sums, gain, masks) that would round-trip HBM at every
    level (xgboost EvaluateSplits restructured as a running scan-argmax).

    Equal, bit for bit, to a flat ``argmax(gain.reshape(L, F·B))`` (the
    tests' reference, `tests/test_tree_split_sums.plain_level_best`):
    per-feature cumsums are the same per-lane folds as the (L, F, B)
    ``jnp.cumsum`` (lanes are independent), the running compare uses
    strict ``>`` so ties keep the EARLIEST feature/bin exactly like
    argmax's first-occurrence rule, and NaN gains (possible at
    reg_lambda=0) are treated as the maximum with first-occurrence order,
    matching argmax's NaN propagation.

    Returns (best_gain, best_feat, best_bin, vL_best, vR_best) — the
    child-value pair at the winning bin is only meaningful under
    `monotone` (it feeds the bound propagation); it is 0 where no
    admissible split exists, which the caller neutralizes via the
    do_split gate."""
    L, F, B = hist.shape[0], hist.shape[1], hist.shape[2]
    G, H = gsum[:, None], hsum[:, None]                      # (L, 1)
    # xgboost CalcSplitGain: L1 soft-threshold the gradient sums
    # before squaring (ThresholdL1); exact no-op at reg_alpha=0
    tl1 = lambda A: jnp.sign(A) * jnp.maximum(jnp.abs(A) - reg_alpha, 0.0)
    Gt = tl1(G)
    base = Gt * Gt / (H + reg_lambda)                        # (L, 1)
    bin_ok = (jnp.arange(nbins) < nbins - 1)[None, :]        # no NA-bin split
    mono_on = monotone is not None

    def body(f, carry):
        best_g, best_f, best_b, vl_b, vr_b = carry
        hf = jax.lax.dynamic_index_in_dim(hist, f, axis=1, keepdims=False)
        WL, GL, HL, WR, GR, HR = _split_sums(hf)             # (L, B) each
        GLt, GRt = tl1(GL), tl1(GR)
        gain = (GLt * GLt / (HL + reg_lambda)
                + GRt * GRt / (HR + reg_lambda) - base)
        ok = (WL >= min_rows) & (WR >= min_rows) & bin_ok
        ok = ok & (jax.lax.dynamic_index_in_dim(feat_mask, f,
                                                keepdims=False) > 0)
        ok = ok & node_ok[:, None]
        if keep is not None:
            ok = ok & jax.lax.dynamic_index_in_dim(
                keep, f, axis=1, keepdims=False)[:, None]
        if mono_on:
            # monotone_constraints (hex/tree Constraints / LightGBM): a
            # split on feature f with constraint c is admissible only
            # when c·(value_right − value_left) ≥ 0, where the child
            # values use the SAME soft-thresholded formula as
            # materialized node values and are clamped into the node's
            # inherited bounds. Bound propagation (in `build_tree`) then
            # guarantees zero violations.
            vL = jnp.clip(-GLt / (HL + reg_lambda + 1e-12),
                          lo_lvl[:, None], hi_lvl[:, None])
            vR = jnp.clip(-GRt / (HR + reg_lambda + 1e-12),
                          lo_lvl[:, None], hi_lvl[:, None])
            mc = jax.lax.dynamic_index_in_dim(monotone, f, keepdims=False)
            ok = ok & ((mc == 0) | (mc * (vR - vL) >= 0))
        gain = jnp.where(ok, gain, -jnp.inf)
        bb_f = jnp.argmax(gain, axis=1).astype(jnp.int32)     # (L,)
        g_f = jnp.take_along_axis(gain, bb_f[:, None], axis=1)[:, 0]
        better = (g_f > best_g) | (jnp.isnan(g_f) & ~jnp.isnan(best_g))
        best_g = jnp.where(better, g_f, best_g)
        best_f = jnp.where(better, f, best_f).astype(jnp.int32)
        best_b = jnp.where(better, bb_f, best_b)
        if mono_on:
            vl_b = jnp.where(better, jnp.take_along_axis(
                vL, bb_f[:, None], axis=1)[:, 0], vl_b)
            vr_b = jnp.where(better, jnp.take_along_axis(
                vR, bb_f[:, None], axis=1)[:, 0], vr_b)
        return best_g, best_f, best_b, vl_b, vr_b

    init = (jnp.full(L, -jnp.inf, jnp.float32), jnp.zeros(L, jnp.int32),
            jnp.zeros(L, jnp.int32), jnp.zeros(L, jnp.float32),
            jnp.zeros(L, jnp.float32))
    return jax.lax.fori_loop(0, F, body, init)


def value_at(table: jax.Array, idx: jax.Array) -> jax.Array:
    """table[idx] for a small f32 table (e.g. leaf values by heap index) as
    an MXU one-hot matvec. Precision.HIGHEST is required: the TPU default
    truncates f32 matmul operands to bf16, which would round every leaf
    value added to the boosting margins (the one-hot operand is exact in
    any precision, so HIGHEST recovers the exact gather semantics)."""
    L = table.shape[0]
    if L > 2 * _ONEHOT_LOOKUP_MAX:
        return table[idx]
    oh = (idx[:, None] == jnp.arange(L, dtype=jnp.int32)[None, :]
          ).astype(jnp.float32)
    return jnp.dot(oh, table, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_depth", "nbins", "hist_method", "axis_name", "mtries",
        "compact_cap", "pack_bits", "n_shard_blocks",
    ),
)
def build_tree(
    codes: jax.Array,        # (N, F) uint bin codes, or the `ops.packing`
    #                          packed (N·bits/8, F) words when pack_bits
    g: jax.Array,            # (N,) gradients
    h: jax.Array,            # (N,) hessians
    w: jax.Array,            # (N,) row weights (0 = masked/pad/OOB)
    feat_mask: jax.Array,    # (F,) f32 1/0 — column sampling
    edges: jax.Array,        # (F, nbins-2) raw-value right edges (+inf padded)
    max_depth: int,
    nbins: int,
    min_rows: float = 10.0,
    min_split_improvement: float = 0.0,
    reg_lambda: float = 1.0,
    reg_alpha: float = 0.0,
    hist_method: str = "auto",
    axis_name: Optional[str] = None,
    mtries: int = 0,
    mtries_rate=None,  # traced per-node column keep-probability; when set,
    #                    overrides the static mtries/F so DRF and XRT (and
    #                    every mtries value) share ONE compiled program
    key: Optional[jax.Array] = None,
    monotone: Optional[jax.Array] = None,  # (F,) ∈ {-1,0,1}
    max_abs_leaf=None,  # traced scalar: |leaf value| cap (GBM
    #                     max_abs_leafnode_pred / xgboost max_delta_step)
    compact_cap: int = 0,
    pack_bits: int = 0,
    n_shard_blocks: int = 0,
    operand: Optional[jax.Array] = None,  # the fit's Pallas code operand
):
    """Build one tree; returns (Tree, final_leaf_heap_idx (N,),
    gain_per_feature (F,), cover (T,) — Σ training row weights per heap node,
    recorded for path-dependent TreeSHAP (hex/genmodel TreeSHAP node weights).
    With compact_cap > 0, a 5th element is returned: an i32 overflow flag
    (see below).

    mtries > 0 samples ~mtries of F features per node per level (DRF's
    per-split column sampling, `hex/tree/drf/DRF.java` _mtry) — bernoulli
    approximation of exact without-replacement draws, same expectation.

    Scalar hyperparameters (min_rows, min_split_improvement, reg_*) are
    TRACED, not static: one compiled program serves every model that shares
    the structural config (shapes, depth, bins) — grids / CV / AutoML vary
    these scalars freely without recompiling.

    compact_cap > 0 switches levels wider than the cap to ACTIVE-NODE
    COMPACTION (DHistogram's allocate-only-active-nodes semantics, made
    static-shaped): deep levels track at most `compact_cap` live nodes in
    compact slots instead of materializing 2^d × F × B histograms that are
    overwhelmingly empty (measured: DRF depth-17 levels carry ~700 active
    nodes of 131k heap cells). Exactness is preserved: if the live-node
    count ever exceeds the cap, the returned overflow flag is nonzero and
    the caller must rebuild densely (the driver does). Requires
    monotone=None.

    pack_bits in {4, 5, 6} means `codes` is the `ops.packing` packed word
    matrix: without `operand` the histogram kernels widen it once per
    program, and the partition step reads each row's selected-feature code
    from the same widened codes by a dense select over the feature axis
    (`_row_codes`; no gather into the code matrix at
    F <= `_ONEHOT_LOOKUP_MAX`, nor at any F given `operand`).

    `operand` is the fit-lifetime form of the same codes: the Pallas
    histogram kernel's feature-major float32 operand, built once a fit by
    `ops.histogram.build_code_operand` (`code_operand_form` says for which
    fits). Given it, the kernel's levels and the partition's select read
    it at every frame width (`partition_read`) and this program widens
    nothing for them; only a level that fell back to ``segment`` still
    reads `codes`. The values are the same either way, so the tree is bit
    for bit the same as the one the gather read builds without it. One
    device only: the blocked reduction (`n_shard_blocks`) refuses it.

    n_shard_blocks > 0 (ISSUE 12) makes every row reduction (histograms
    and final leaf totals) use the shard-invariant blocked fold of
    `ops.histogram` — this call's rows are accumulated in that many
    contiguous blocks whose partials merge in a fixed order, across
    devices via `all_gather` when `axis_name` is set. An N-device
    shard_map'd call with S/N local blocks is then bit-identical to a
    1-device call with S blocks. 0 preserves the historical single-fold
    (+ psum) formulation bit-for-bit.
    """
    if pack_bits:
        F = codes.shape[1]
        N = packing.packed_nrows(codes.shape[0], pack_bits)
    else:
        N, F = codes.shape
    T = heap_size(max_depth)
    feat_a = jnp.zeros(T, jnp.int32)
    bin_a = jnp.zeros(T, jnp.int32)
    thr_a = jnp.zeros(T, jnp.float32)
    split_a = jnp.zeros(T, bool)
    value_a = jnp.zeros(T, jnp.float32)
    cover_a = jnp.zeros(T, jnp.float32)   # Σ row weights per node (TreeSHAP)

    idx = jnp.zeros(N, jnp.int32)          # level-local node index
    active = jnp.ones(1, bool)             # per-level-node: may still split
    gain_per_feature = jnp.zeros(F, jnp.float32)
    if key is None:
        key = jax.random.PRNGKey(0)
    BIG = jnp.float32(3.4e38)
    # per-level-node value bounds for monotone constraints (LightGBM-style
    # mid-point bound propagation; every node's value is clamped into them)
    lo_lvl = jnp.full(1, -BIG)
    hi_lvl = jnp.full(1, BIG)

    if compact_cap and monotone is not None:
        raise ValueError("compact_cap requires monotone=None")
    d_switch = compact_switch_depth(max_depth, compact_cap)
    # per-row frozen leaf id (absolute heap node) — maintained only when the
    # compact phase can run, since compaction stops flowing dead rows left
    row_leaf = jnp.zeros(N, jnp.int32) if d_switch < max_depth else None

    hist_prev = None
    for d in range(min(d_switch, max_depth)):
        L = 2 ** d
        base = L - 1                        # heap offset of this level
        if d == 0:
            hist = build_histograms(
                codes, idx, g, h, w, L, nbins, method=hist_method,
                axis_name=axis_name, pack_bits=pack_bits,
                n_shard_blocks=n_shard_blocks, operand=operand,
            )  # (L, F, B, 3)
        else:
            # sibling subtraction (the gpu_hist/LightGBM trick): build only
            # LEFT children histograms; right = parent − left. Halves the
            # histogram work at every level.
            is_left = (idx % 2 == 0)
            hist_left = build_histograms(
                codes, idx // 2, g, h, w * is_left.astype(w.dtype),
                L // 2, nbins, method=hist_method, axis_name=axis_name,
                pack_bits=pack_bits, n_shard_blocks=n_shard_blocks,
                operand=operand,
            )  # (L/2, F, B, 3) indexed by parent
            hist_right = hist_prev - hist_left
            hist = jnp.stack([hist_left, hist_right], axis=1).reshape(
                L, *hist_left.shape[1:]
            )
        hist_prev = hist

        wsum, gsum, hsum = _node_totals(hist)
        # Newton leaf value with elastic-net regularization (xgboost's
        # CalcWeight: soft-threshold G by alpha, shrink by lambda)
        gthr = jnp.sign(gsum) * jnp.maximum(jnp.abs(gsum) - reg_alpha, 0.0)
        node_val = (-gthr / (hsum + reg_lambda + 1e-12)).astype(jnp.float32)
        if max_abs_leaf is not None:
            # cap before monotone bounds so the bounds (which encode the
            # constraint) always win over the magnitude cap
            node_val = jnp.clip(node_val, -max_abs_leaf, max_abs_leaf)
        if monotone is not None:
            node_val = jnp.clip(node_val, lo_lvl, hi_lvl)
        value_a = value_a.at[base : base + L].set(node_val)
        cover_a = cover_a.at[base : base + L].set(wsum.astype(jnp.float32))

        # per-(node,feature) bernoulli keep with the same node psum'd RNG
        # on every host (key is replicated) so partitions stay consistent.
        keep = None
        if mtries > 0 or mtries_rate is not None:
            key, sub = jax.random.split(key)
            rate = mtries_rate if mtries_rate is not None else (mtries / F)
            keep = jax.random.uniform(sub, (L, F)) < rate
            keep = keep.at[:, 0].set(keep[:, 0] | ~keep.any(axis=1))  # >=1 kept

        best_gain, bf, bb, vLs, vRs = _fused_level_best(
            hist, active, feat_mask, keep, nbins, min_rows, reg_lambda,
            reg_alpha, gsum, hsum, monotone=monotone,
            lo_lvl=lo_lvl if monotone is not None else None,
            hi_lvl=hi_lvl if monotone is not None else None)
        do_split = best_gain > jnp.maximum(min_split_improvement, 1e-10)
        gain_per_feature = gain_per_feature + jax.ops.segment_sum(
            jnp.where(do_split, best_gain, 0.0).astype(jnp.float32), bf, num_segments=F
        )

        # raw threshold: edges[f][b] for b < nbins-2, +inf at the last value bin
        pad_edges = jnp.concatenate(
            [edges.astype(jnp.float32), jnp.full((F, 1), jnp.inf, jnp.float32)], axis=1
        )
        bthr = pad_edges[bf, jnp.minimum(bb, nbins - 2)]

        feat_a = feat_a.at[base : base + L].set(jnp.where(do_split, bf, 0))
        bin_a = bin_a.at[base : base + L].set(jnp.where(do_split, bb, 0))
        thr_a = thr_a.at[base : base + L].set(jnp.where(do_split, bthr, 0.0))
        split_a = split_a.at[base : base + L].set(do_split)

        # partition rows: decided-leaf rows flow left; splitters route by
        # code. All per-row lookups are one-hot contractions (L and F are
        # small, packed codes included) — a per-row gather here costs ~10×
        # more VPU time.
        with jax.named_scope("tree.partition"):
            rf = _lookup_int(bf, idx, L)
            rb = _lookup_int(bb, idx, L)
            rs = _lookup_bool(do_split, idx, L)
            rcode = _row_codes(codes, rf, pack_bits, operand)
            go_right = (rcode > rb) & rs
            idx = 2 * idx + go_right.astype(jnp.int32)
        if row_leaf is not None:
            row_leaf = jnp.where(rs, (2 ** (d + 1) - 1) + idx, row_leaf)
        active = jnp.repeat(do_split, 2)

        if monotone is not None:
            # propagate bounds to children: on a ±1-constrained split the
            # mid-point of the chosen split's child values caps the lower-
            # valued side and floors the higher-valued side. vLs/vRs are
            # the search's running carry of the SAME vL/vR its
            # admissibility check used.
            mid = 0.5 * (vLs + vRs)
            c = monotone[bf] * do_split.astype(monotone.dtype)
            # c=+1: left ≤ mid ≤ right; c=−1: mirrored; c=0: inherit as-is
            hi_left = jnp.where(c > 0, jnp.minimum(hi_lvl, mid), hi_lvl)
            lo_left = jnp.where(c < 0, jnp.maximum(lo_lvl, mid), lo_lvl)
            hi_right = jnp.where(c < 0, jnp.minimum(hi_lvl, mid), hi_lvl)
            lo_right = jnp.where(c > 0, jnp.maximum(lo_lvl, mid), lo_lvl)
            lo_lvl = jnp.stack([lo_left, lo_right], axis=1).reshape(2 * L)
            hi_lvl = jnp.stack([hi_left, hi_right], axis=1).reshape(2 * L)

    if d_switch >= max_depth:
        # pure dense build: final level values from exact per-cell totals.
        # For small heaps the f32 one-hot matmul (MXU) beats segment_sum's
        # sorted scatter ~3×; arithmetic stays f32 either way, only the
        # reduction tree differs.
        Lf = 2 ** max_depth
        basef = Lf - 1
        # Precision.HIGHEST inside (small heaps): TPU's default matmul
        # truncates f32 operands to bf16, which would round the per-leaf
        # g/h sums (leaf values)
        tot = _leaf_totals(idx, jnp.stack([w, g * w, h * w]), Lf,
                           axis_name, n_shard_blocks, onehot_ok=True)
        gthr_f = jnp.sign(tot[:, 1]) * jnp.maximum(jnp.abs(tot[:, 1]) - reg_alpha, 0.0)
        leaf_val = (-gthr_f / (tot[:, 2] + reg_lambda + 1e-12)).astype(jnp.float32)
        if max_abs_leaf is not None:
            leaf_val = jnp.clip(leaf_val, -max_abs_leaf, max_abs_leaf)
        if monotone is not None:
            leaf_val = jnp.clip(leaf_val, lo_lvl, hi_lvl)
        value_a = value_a.at[basef:].set(leaf_val)
        cover_a = cover_a.at[basef:].set(tot[:, 0].astype(jnp.float32))
        out = (
            Tree(feat_a, bin_a, thr_a, split_a, value_a),
            idx + basef,
            gain_per_feature,
            cover_a,
        )
        if compact_cap:
            return out + (jnp.int32(0),)
        return out

    # ---- compact phase: levels d_switch..max_depth with ≤ CAP live slots --
    CAP = compact_cap
    M = CAP // 2
    if 2 * M != CAP:
        raise ValueError("compact_cap must be even (slot pairs)")
    overflow = jnp.int32(0)
    L_t = 2 ** d_switch
    act_i = active.astype(jnp.int32)
    overflow += (act_i.sum() > CAP).astype(jnp.int32)
    sid_nodes = jnp.where(active, jnp.minimum(jnp.cumsum(act_i) - 1, CAP),
                          CAP)                                    # (L_t,)
    row_slot = sid_nodes[idx]                                     # (N,)
    slot_node = jnp.full(CAP + 1, -1, jnp.int32).at[sid_nodes].set(
        jnp.where(active, jnp.arange(L_t, dtype=jnp.int32), -1))
    # transition histogram: one fresh pass in slot space (no subtraction
    # available across the dense/compact boundary)
    slot_hist = build_histograms(
        codes, row_slot, g, h, w * (row_slot < CAP).astype(w.dtype),
        CAP + 1, nbins, method=hist_method, axis_name=axis_name,
        pack_bits=pack_bits, n_shard_blocks=n_shard_blocks, operand=operand)

    pad_edges_c = jnp.concatenate(
        [edges.astype(jnp.float32), jnp.full((F, 1), jnp.inf, jnp.float32)],
        axis=1)
    slot_iota = jnp.arange(CAP + 1, dtype=jnp.int32)

    for d in range(d_switch, max_depth):
        base = 2 ** d - 1
        valid = (slot_node >= 0) & (slot_iota < CAP)
        wsum, gsum, hsum = _node_totals(slot_hist)
        gthr = jnp.sign(gsum) * jnp.maximum(jnp.abs(gsum) - reg_alpha, 0.0)
        node_val = (-gthr / (hsum + reg_lambda + 1e-12)).astype(jnp.float32)
        if max_abs_leaf is not None:
            node_val = jnp.clip(node_val, -max_abs_leaf, max_abs_leaf)
        abs_node = jnp.where(valid, base + slot_node, T)   # T drops
        value_a = value_a.at[abs_node].set(
            jnp.where(valid, node_val, 0.0), mode="drop")
        cover_a = cover_a.at[abs_node].set(
            jnp.where(valid, wsum.astype(jnp.float32), 0.0), mode="drop")

        # split search over live slots (same math as the dense level)
        keep = None
        if mtries > 0 or mtries_rate is not None:
            key, sub = jax.random.split(key)
            rate = mtries_rate if mtries_rate is not None else (mtries / F)
            keep = jax.random.uniform(sub, (CAP + 1, F)) < rate
            keep = keep.at[:, 0].set(keep[:, 0] | ~keep.any(axis=1))
        best_gain, bf, bb, _, _ = _fused_level_best(
            slot_hist, valid, feat_mask, keep, nbins, min_rows, reg_lambda,
            reg_alpha, gsum, hsum)
        do = best_gain > jnp.maximum(min_split_improvement, 1e-10)
        gain_per_feature = gain_per_feature + jax.ops.segment_sum(
            jnp.where(do, best_gain, 0.0).astype(jnp.float32), bf,
            num_segments=F)
        bthr = pad_edges_c[bf, jnp.minimum(bb, nbins - 2)]
        feat_a = feat_a.at[abs_node].set(
            jnp.where(valid & do, bf, 0), mode="drop")
        bin_a = bin_a.at[abs_node].set(
            jnp.where(valid & do, bb, 0), mode="drop")
        thr_a = thr_a.at[abs_node].set(
            jnp.where(valid & do, bthr, 0.0), mode="drop")
        split_a = split_a.at[abs_node].set(valid & do, mode="drop")

        # partition rows (plain gathers: CAP-wide tables, N small)
        do = do & valid
        with jax.named_scope("tree.partition"):
            rs_do = do[row_slot]
            bf_r = bf[row_slot]
            bb_r = bb[row_slot]
            rcode = _row_codes(codes, bf_r, pack_bits, operand)
            go_right = (rcode > bb_r) & rs_do
        child_local = 2 * slot_node[row_slot] + go_right.astype(jnp.int32)
        row_leaf = jnp.where(rs_do, (2 ** (d + 1) - 1) + child_local,
                             row_leaf)

        # child slot assignment: split parents ranked, children interleaved
        do_i = do.astype(jnp.int32)
        rank = jnp.minimum(jnp.cumsum(do_i) - 1, M - 1)
        overflow += (do_i.sum() > M).astype(jnp.int32)
        new_row_slot = jnp.where(
            rs_do, 2 * rank[row_slot] + go_right.astype(jnp.int32), CAP)

        tgt = jnp.where(do, rank, M)                      # (CAP+1,) ∈ [0,M]
        pr = jnp.full(M + 1, CAP, jnp.int32).at[tgt].set(
            jnp.where(do, slot_iota, CAP))
        par_node = jnp.where(pr < CAP,
                             slot_node[jnp.minimum(pr, CAP)], -1)  # (M+1,)
        kids = jnp.stack([2 * par_node, 2 * par_node + 1], axis=1
                         ).reshape(2 * (M + 1))
        kids = jnp.where(kids < 0, -1, kids)
        new_slot_node = jnp.concatenate(
            [kids[:CAP], jnp.full(1, -1, jnp.int32)])

        # child histograms: LEFT from one masked pass in parent-slot space,
        # RIGHT by parent-minus-left (the sibling-subtraction trick)
        wl = w * ((~go_right) & rs_do).astype(w.dtype)
        hl = build_histograms(codes, row_slot, g, h, wl, CAP + 1, nbins,
                              method=hist_method, axis_name=axis_name,
                              pack_bits=pack_bits,
                              n_shard_blocks=n_shard_blocks, operand=operand)
        prc = jnp.minimum(pr, CAP)
        hl_p = hl[prc]
        hp_p = slot_hist[prc]
        pair = jnp.stack([hl_p, hp_p - hl_p], axis=1
                         ).reshape((2 * (M + 1),) + hl.shape[1:])
        slot_hist = jnp.concatenate(
            [pair[:CAP], jnp.zeros((1,) + hl.shape[1:], hl.dtype)])
        slot_node = new_slot_node
        row_slot = new_row_slot

    # final level: exact per-slot totals (dead rows sit in the trash slot)
    basef = 2 ** max_depth - 1
    valid = (slot_node >= 0) & (slot_iota < CAP)
    tot = _leaf_totals(row_slot, jnp.stack([w, g * w, h * w]), CAP + 1,
                       axis_name, n_shard_blocks, onehot_ok=False)
    gthr_f = jnp.sign(tot[:, 1]) * jnp.maximum(
        jnp.abs(tot[:, 1]) - reg_alpha, 0.0)
    leaf_val = (-gthr_f / (tot[:, 2] + reg_lambda + 1e-12)).astype(jnp.float32)
    if max_abs_leaf is not None:
        leaf_val = jnp.clip(leaf_val, -max_abs_leaf, max_abs_leaf)
    abs_node = jnp.where(valid, basef + slot_node, T)
    value_a = value_a.at[abs_node].set(
        jnp.where(valid, leaf_val, 0.0), mode="drop")
    cover_a = cover_a.at[abs_node].set(
        jnp.where(valid, tot[:, 0].astype(jnp.float32), 0.0), mode="drop")
    return (
        Tree(feat_a, bin_a, thr_a, split_a, value_a),
        row_leaf,
        gain_per_feature,
        cover_a,
        overflow,
    )


@jax.named_scope("tree.split")
def _search_splits(hist, feat_mask, nbins, min_rows, reg_lambda, reg_alpha):
    """Best (gain, feat, bin) per node for an (L, F, B, 3) histogram —
    the split search of `build_tree` without the level-wise bookkeeping
    (`hex/tree/DTree.Split.findBestSplitPoint`; xgboost EvaluateSplits)."""
    L, F = hist.shape[0], hist.shape[1]
    wsum, gsum, hsum = _node_totals(hist)
    WL, GL, HL, WR, GR, HR = _split_sums(hist)
    G, H = gsum[:, None, None], hsum[:, None, None]
    # xgboost CalcSplitGain: L1 soft-threshold before squaring (ThresholdL1)
    tl1 = lambda A: jnp.sign(A) * jnp.maximum(jnp.abs(A) - reg_alpha, 0.0)
    GLt, GRt, Gt = tl1(GL), tl1(GR), tl1(G)
    gain = (GLt * GLt / (HL + reg_lambda)
            + GRt * GRt / (HR + reg_lambda)
            - Gt * Gt / (H + reg_lambda))
    ok = (WL >= min_rows) & (WR >= min_rows)
    ok = ok & (jnp.arange(nbins)[None, None, :] < nbins - 1)  # NA bin
    ok = ok & (feat_mask[None, :, None] > 0)
    gain = jnp.where(ok, gain, -jnp.inf)
    flat = gain.reshape(L, F * nbins)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    return (best_gain, (best // nbins).astype(jnp.int32),
            (best % nbins).astype(jnp.int32), wsum, gsum, hsum)


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_depth", "nbins", "max_leaves", "hist_method", "axis_name",
    ),
)
def build_tree_lossguide(
    codes: jax.Array,        # (N, F) uint bin codes
    g: jax.Array,
    h: jax.Array,
    w: jax.Array,
    feat_mask: jax.Array,    # (F,) per-tree column mask
    edges: jax.Array,
    max_depth: int,
    nbins: int,
    max_leaves: int,
    min_rows: float = 1.0,
    min_split_improvement: float = 0.0,
    reg_lambda: float = 1.0,
    reg_alpha: float = 0.0,
    hist_method: str = "auto",
    axis_name: Optional[str] = None,
    max_abs_leaf=None,
):
    """Leaf-wise (best-first) growth — xgboost `grow_policy=lossguide`
    (`h2o-ext-xgboost/.../XGBoostModel.java` grow_policy passthrough to the
    native `hist` updater; LightGBM's growth strategy).

    TPU-first shape: the frontier is a fixed array of `max_leaves` leaf
    slots, each holding its node's histogram and cached best split; every
    iteration of a `lax.fori_loop` splits the best-gain slot, builds the
    LEFT child's histogram in one masked pass and derives the right child
    by parent-minus-left subtraction. All shapes are static, so one
    compiled program serves the whole forest. The tree still lives in the
    same depth-capped heap as `build_tree`, so scoring, packing, MOJO
    export and TreeSHAP are unchanged.

    Returns the same tuple as `build_tree`.
    """
    N, F = codes.shape
    T = heap_size(max_depth)
    S = max(2, min(max_leaves if max_leaves > 0 else 2 ** max_depth,
                   2 ** max_depth))
    # derived from codes (not a fresh constant) so that under shard_map the
    # fori_loop row-state carry is device-varying from iteration 0
    zeros_n = codes[:, 0].astype(jnp.int32) * 0

    hist0 = build_histograms(codes, zeros_n, g, h, w, 1, nbins,
                             method=hist_method, axis_name=axis_name)
    bg0, bf0, bb0, ws0, gs0, hs0 = _search_splits(
        hist0, feat_mask, nbins, min_rows, reg_lambda, reg_alpha)

    def newton(gs, hs):
        gthr = jnp.sign(gs) * jnp.maximum(jnp.abs(gs) - reg_alpha, 0.0)
        v = (-gthr / (hs + reg_lambda + 1e-12)).astype(jnp.float32)
        if max_abs_leaf is not None:
            v = jnp.clip(v, -max_abs_leaf, max_abs_leaf)
        return v

    def depth_of(node):
        # floor(log2(node+1)) by exact integer comparisons (max_depth small)
        return (node[..., None] + 1 >=
                2 ** jnp.arange(1, max_depth + 1, dtype=jnp.int32)
                ).sum(axis=-1).astype(jnp.int32)

    pad_edges = jnp.concatenate(
        [edges.astype(jnp.float32), jnp.full((F, 1), jnp.inf, jnp.float32)],
        axis=1)

    value_a = jnp.zeros(T, jnp.float32).at[0].set(newton(gs0, hs0)[0])
    cover_a = jnp.zeros(T, jnp.float32).at[0].set(ws0.astype(jnp.float32)[0])
    feat_a = jnp.zeros(T, jnp.int32)
    bin_a = jnp.zeros(T, jnp.int32)
    thr_a = jnp.zeros(T, jnp.float32)
    split_a = jnp.zeros(T, bool)

    slot_node = jnp.full(S, -1, jnp.int32).at[0].set(0)
    slot_hist = jnp.zeros((S,) + hist0.shape[1:], hist0.dtype
                          ).at[0].set(hist0[0])
    # root at depth 0 can always be considered (max_depth >= 1)
    slot_gain = jnp.full(S, -jnp.inf, jnp.float32).at[0].set(bg0[0])
    slot_feat = jnp.zeros(S, jnp.int32).at[0].set(bf0[0])
    slot_bin = jnp.zeros(S, jnp.int32).at[0].set(bb0[0])

    def body(t, st):
        (feat_a, bin_a, thr_a, split_a, value_a, cover_a,
         row_node, row_slot, slot_node, slot_hist,
         slot_gain, slot_feat, slot_bin, gain_pf) = st
        s_star = jnp.argmax(slot_gain).astype(jnp.int32)
        gain = slot_gain[s_star]
        do = gain > jnp.maximum(min_split_improvement, 1e-10)
        node = slot_node[s_star]
        bf = slot_feat[s_star]
        bb = slot_bin[s_star]
        left = 2 * node + 1
        right = 2 * node + 2
        new_slot = (t + 1).astype(jnp.int32)

        bthr = pad_edges[bf, jnp.minimum(bb, nbins - 2)]
        feat_a = feat_a.at[node].set(jnp.where(do, bf, feat_a[node]))
        bin_a = bin_a.at[node].set(jnp.where(do, bb, bin_a[node]))
        thr_a = thr_a.at[node].set(jnp.where(do, bthr, thr_a[node]))
        split_a = split_a.at[node].set(split_a[node] | do)

        in_node = row_slot == s_star
        rcode = jnp.take(codes, bf, axis=1).astype(jnp.int32)
        go_right = in_node & (rcode > bb) & do
        row_node = jnp.where(go_right, right,
                             jnp.where(in_node & do, left, row_node))
        row_slot = jnp.where(go_right, new_slot, row_slot)

        # left child = one masked histogram pass; right = parent − left
        wl = w * (in_node & ~go_right & do).astype(w.dtype)
        hist_l = build_histograms(codes, zeros_n, g, h, wl, 1, nbins,
                                  method=hist_method, axis_name=axis_name)[0]
        hist_r = slot_hist[s_star] - hist_l
        slot_hist = slot_hist.at[s_star].set(
            jnp.where(do, hist_l, slot_hist[s_star]))
        slot_hist = slot_hist.at[new_slot].set(
            jnp.where(do, hist_r, slot_hist[new_slot]))
        slot_node = slot_node.at[s_star].set(jnp.where(do, left, node))
        slot_node = slot_node.at[new_slot].set(
            jnp.where(do, right, slot_node[new_slot]))

        ch = jnp.stack([hist_l, hist_r])           # (2, F, B, 3)
        cg, cbf, cbb, cws, cgs, chs = _search_splits(
            ch, feat_mask, nbins, min_rows, reg_lambda, reg_alpha)
        cval = newton(cgs, chs)
        value_a = value_a.at[left].set(jnp.where(do, cval[0], value_a[left]))
        value_a = value_a.at[right].set(jnp.where(do, cval[1], value_a[right]))
        cover_a = cover_a.at[left].set(
            jnp.where(do, cws.astype(jnp.float32)[0], cover_a[left]))
        cover_a = cover_a.at[right].set(
            jnp.where(do, cws.astype(jnp.float32)[1], cover_a[right]))

        # children at the depth cap cannot split further
        can = depth_of(jnp.stack([left, right])) < max_depth
        cg = jnp.where(can, cg, -jnp.inf)
        slot_gain = slot_gain.at[s_star].set(jnp.where(do, cg[0], -jnp.inf))
        slot_gain = slot_gain.at[new_slot].set(
            jnp.where(do, cg[1], slot_gain[new_slot]))
        slot_feat = slot_feat.at[s_star].set(jnp.where(do, cbf[0], 0))
        slot_feat = slot_feat.at[new_slot].set(
            jnp.where(do, cbf[1], slot_feat[new_slot]))
        slot_bin = slot_bin.at[s_star].set(jnp.where(do, cbb[0], 0))
        slot_bin = slot_bin.at[new_slot].set(
            jnp.where(do, cbb[1], slot_bin[new_slot]))

        gain_pf = gain_pf + jnp.where(
            do & (jnp.arange(F, dtype=jnp.int32) == bf), gain, 0.0
        ).astype(jnp.float32)
        return (feat_a, bin_a, thr_a, split_a, value_a, cover_a,
                row_node, row_slot, slot_node, slot_hist,
                slot_gain, slot_feat, slot_bin, gain_pf)

    st = (feat_a, bin_a, thr_a, split_a, value_a, cover_a,
          zeros_n, zeros_n, slot_node, slot_hist,
          slot_gain, slot_feat, slot_bin, jnp.zeros(F, jnp.float32))
    st = jax.lax.fori_loop(0, S - 1, body, st)
    (feat_a, bin_a, thr_a, split_a, value_a, cover_a,
     row_node, _, _, _, _, _, _, gain_pf) = st
    return (
        Tree(feat_a, bin_a, thr_a, split_a, value_a),
        row_node,
        gain_pf,
        cover_a,
    )


def predict_codes(tree: Tree, codes: jax.Array, max_depth: int) -> jax.Array:
    """Leaf value per row, traversing on binned codes (training-time path)."""
    N = codes.shape[0]
    node = jnp.zeros(N, jnp.int32)
    for _ in range(max_depth):
        f = tree.feat[node]
        b = tree.bin[node]
        s = tree.is_split[node]
        c = jnp.take_along_axis(codes, f[:, None].astype(jnp.int32), axis=1)[:, 0]
        child = 2 * node + 1 + ((c.astype(jnp.int32) > b) & s).astype(jnp.int32)
        node = jnp.where(s, child, node)
    return tree.value[node]


def predict_codes_packed(tree: Tree, packed: jax.Array, bits: int,
                         max_depth: int) -> jax.Array:
    """Leaf value per row, traversing straight on the `ops.packing` packed
    word matrix (the streamed/GOSS margin-update path, ISSUE 14): each
    level reads the row's split-feature code via `_row_codes` (the block
    widened once per program, a dense select per level). With bits=0
    `packed` is a full-width code matrix and this is `predict_codes`."""
    if not bits:
        return predict_codes(tree, packed, max_depth)
    N = packing.packed_nrows(packed.shape[0], bits)
    node = jnp.zeros(N, jnp.int32)
    for _ in range(max_depth):
        f = tree.feat[node]
        b = tree.bin[node]
        s = tree.is_split[node]
        c = _row_codes(packed, f, bits)
        child = 2 * node + 1 + ((c > b) & s).astype(jnp.int32)
        node = jnp.where(s, child, node)
    return tree.value[node]


def predict_raw(tree: Tree, X: jax.Array, max_depth: int) -> jax.Array:
    """Leaf value per row on raw features (scoring path; NaN → right,
    mirroring the NA-bin-is-last training semantics)."""
    N = X.shape[0]
    node = jnp.zeros(N, jnp.int32)
    for _ in range(max_depth):
        f = tree.feat[node]
        t = tree.thr[node]
        s = tree.is_split[node]
        x = jnp.take_along_axis(X, f[:, None].astype(jnp.int32), axis=1)[:, 0]
        right = jnp.isnan(x) | (x > t)
        child = 2 * node + 1 + (right & s).astype(jnp.int32)
        node = jnp.where(s, child, node)
    return tree.value[node]


def stack_trees(trees) -> Tree:
    """Stack per-tree arrays into (ntrees, T) for vmapped forest scoring."""
    return Tree(*[jnp.stack([getattr(t, f) for t in trees]) for f in Tree._fields])


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_forest_raw(forest: Tree, X: jax.Array, max_depth: int) -> jax.Array:
    """Σ over trees of leaf values — (N,) or (ntrees, N) summed. The scoring
    analog of `hex/Model.score0` / `BigScore` MRTask (hex/Model.java).

    Reference walk, one gather-round per level. The production scoring path
    is `build_score_table` + `predict_forest_fused` below (~10× faster on
    deep forests); this stays as the oracle the fused path is tested
    against."""
    per_tree = jax.vmap(lambda t: predict_raw(t, X, max_depth))(forest)
    return per_tree.sum(axis=0)


# ---- fused forest scoring: subtree-fetch walk ---------------------------
#
# The per-level walk above issues one random gather per level per tree; on
# TPU a gather costs ~13 ns per gathered ROW regardless of row width (the
# payload rides the same HBM fetch), so a depth-20 forest pays 21
# gather-rounds where 5 would do. The fused scorer restructures the tree
# into per-round "subtree rows": one 128-lane row holds the (feat, split,
# thr) records of a node's next _SCORE_K levels (2^K-1 records × 2 f32),
# so each fetch round descends K levels using only in-register one-hot
# selects between fetches. A depth-20 walk = 4 subtree fetches + 1 leaf
# value gather (measured 386 ms vs 4379 ms for 64 trees × 50k rows on
# TPU v5e; depth-5: 118 ms vs 615 ms).
#
# Scoring analog of `hex/genmodel/algos/tree/SharedTreeMojoModel.scoreTree`
# / `hex/Model.java` BigScore — redesigned for TPU memory semantics.

_SCORE_K = 5                 # levels per fetch round: 2*(2^5-1)=62 ≤ 64 lanes
_SCORE_W2 = 64               # f32 lanes per anchor block
_SCORE_FOLD = 2              # anchor blocks per 128-lane row (8,128 tiling)
_XV_ONEHOT_MAX = 128         # one-hot X-value fetch only for F ≤ this


def score_round_meta(max_depth: int):
    """Static round plan: (base_level, levels_this_round, row_offset)."""
    meta, base, row_off = [], 0, 0
    while base < max_depth:
        k = min(_SCORE_K, max_depth - base)
        A = 2 ** base
        meta.append((base, k, row_off))
        row_off += (A + _SCORE_FOLD - 1) // _SCORE_FOLD
        base += k
    return tuple(meta), row_off


def build_score_table(forest: Tree, max_depth: int):
    """Heap forest → (walk, value): walk (nt, ROWS, 128) f32 subtree rows,
    value (nt, T) f32 leaf values. Jittable; one-time per model, cache the
    result. Minor dim is exactly 128 lanes so the (8,128) device tiling
    adds no padding (a (T, 6) minor dim would pad 21×)."""
    feat = jnp.asarray(forest.feat)
    nt, T = feat.shape
    enc = feat.astype(jnp.float32) * 2.0 + forest.is_split.astype(jnp.float32)
    thr = forest.thr.astype(jnp.float32)
    meta, _ = score_round_meta(max_depth)
    if not meta:                              # depth-0 stumps: root value only
        return jnp.zeros((nt, 1, _SCORE_FOLD * _SCORE_W2), jnp.float32), \
            forest.value.astype(jnp.float32)
    rows = []
    for (base, k, _row_off) in meta:
        A = 2 ** base
        recs = []
        for level in range(k):
            lo = 2 ** (base + level) - 1
            cnt = 2 ** level
            e = jax.lax.dynamic_slice_in_dim(enc, lo, A * cnt, 1)
            t = jax.lax.dynamic_slice_in_dim(thr, lo, A * cnt, 1)
            recs.append(jnp.stack([e.reshape(nt, A, cnt),
                                   t.reshape(nt, A, cnt)],
                                  axis=-1).reshape(nt, A, 2 * cnt))
        blk = jnp.concatenate(recs, axis=-1)          # (nt, A, 2*(2^k-1))
        pad = _SCORE_W2 - blk.shape[-1]
        if pad:
            blk = jnp.pad(blk, ((0, 0), (0, 0), (0, pad)))
        if A % _SCORE_FOLD:
            blk = jnp.pad(blk, ((0, 0), (0, _SCORE_FOLD - A % _SCORE_FOLD),
                                (0, 0)))
        rows.append(blk.reshape(nt, -1, _SCORE_FOLD * _SCORE_W2))
    walk = jnp.concatenate(rows, axis=1)
    return walk, forest.value.astype(jnp.float32)


build_score_table_jit = jax.jit(build_score_table,
                                static_argnames=("max_depth",))


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_forest_fused(walk: jax.Array, value: jax.Array, X: jax.Array,
                         max_depth: int) -> jax.Array:
    """Σ over trees of leaf values from a `build_score_table` pack.
    Matches `predict_forest_raw` (incl. NaN → right) to reduction-order
    rounding."""
    nt = walk.shape[0]
    N, F = X.shape
    node = jnp.zeros((nt, N), jnp.int32)
    fi = jnp.arange(F, dtype=jnp.int32)
    Xb = X[None]
    X_flat = X.reshape(-1)
    row_iota = jnp.arange(N, dtype=jnp.int32)[None, :]
    meta, _ = score_round_meta(max_depth)
    for (base, k, row_off) in meta:
        lvl_base = 2 ** base - 1
        a = jnp.clip(node - lvl_base, 0, 2 ** base - 1)
        # a row is live in this round iff its node reached level `base`
        # (rows frozen at shallower leaves keep node < lvl_base forever)
        active = node >= lvl_base
        ridx = (a >> 1) + row_off
        frow = jnp.take_along_axis(walk, ridx[:, :, None], axis=1)
        blk01 = jnp.where(((a & 1) == 1)[..., None],
                          frow[..., _SCORE_W2:], frow[..., :_SCORE_W2])
        rel = jnp.zeros_like(node)
        for level in range(k):
            cnt = 2 ** level
            rbase = 2 * (cnt - 1)
            blk = blk01[..., rbase: rbase + 2 * cnt].reshape(nt, N, cnt, 2)
            oh = rel[..., None] == jnp.arange(cnt, dtype=jnp.int32)
            e = jnp.where(oh, blk[..., 0], 0.0).sum(-1)
            t = jnp.where(oh, blk[..., 1], 0.0).sum(-1)
            ei = e.astype(jnp.int32)
            sp = (ei & 1) == 1
            f = ei >> 1
            if F <= _XV_ONEHOT_MAX:
                xv = jnp.where(f[..., None] == fi, Xb, 0.0).sum(-1)
            else:
                xv = jnp.take(X_flat, row_iota * F + f, mode="clip")
            right = (jnp.isnan(xv) | (xv > t)).astype(jnp.int32)
            go = active & sp
            node = jnp.where(go, 2 * node + 1 + right, node)
            rel = jnp.where(go, 2 * rel + right, rel)
            active = go
    v = jnp.take_along_axis(value, node, axis=1)
    return v.sum(axis=0)
