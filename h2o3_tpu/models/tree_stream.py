"""Streamed tree step — the out-of-core GBM/DRF driver (ISSUE 14).

The in-core fused path builds each tree as ONE jitted program over a
device-resident code matrix. When the packed matrix exceeds the device
budget, this module builds the SAME tree from per-block jitted pieces: the
level loop walks the `BlockStore`'s row-blocks in canonical order — while
the histogram kernel consumes block *b*, the H2D upload of block *b+1* is
already dispatched (`prefetch`, the `_score_event_async`
dispatch-before-block pattern) — and accumulates per-block histogram
partials with the same deterministic left-to-right f32 fold
(`ordered_axis_fold`) the in-core ``shard_mode="blocks"`` reduction uses.

Bit-exactness contract: every computation here reuses the in-core path's
own building blocks — `ops.histogram.run_block_kernel` (each partial is
exactly one block of the blocked in-core reduction), `_fused_level_best`
(the single-pass split search), `_lookup_int`/`_row_codes` (the
partition reads), `value_at` (the margin update) and the `_one_tree`
RNG-key derivation chain — so a streamed fit with sampling OFF is
BIT-IDENTICAL to the in-core fit sharing its block count S (pinned in
tests/test_tree_stream.py: forest, varimp, scoring history, early-stop
tree count, predictions). Per-level passes are FUSED per block visit:
entering level d, one block visit applies level d-1's partition and
accumulates level d's sibling-left histogram partial, so a tree streams
(depth+1)·S block reads, not 2·depth·S.

Gradient-based sampling (the paper's GOSS-shaped §sampling): past the
warm-up trees, keep the top-|g| rows plus an amplified random rest, gather
them into a compact packed sample, and build the tree on THAT — the
per-level histogram passes stream a fraction of the bytes; only the final
margin update walks every block once. Opt-in (``goss=True``), GBM
single-margin fits only, and by construction not bit-comparable to the
unsampled path.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import packing
from ..runtime import qos as _qos
from ..runtime import supervisor as _supervisor
from ..ops.histogram import (ordered_axis_fold, resolve_method,
                             run_block_kernel)
from . import distributions as dist_mod
from . import tree as treelib
from .tree import (_ONEHOT_LOOKUP_MAX, _fused_level_best, _lookup_bool,
                   _lookup_int, _row_codes, heap_size)

# -- jitted pieces ----------------------------------------------------------
#
# Each is a small program traced once per shape and dispatched per block /
# per level. The math inside mirrors `tree.build_tree` line for line (the
# comments there hold); only the orchestration differs.


@functools.partial(jax.jit, static_argnames=(
    "mode", "problem", "dist", "tw", "qa", "k"))
def _grads_jit(margins, y_d, mode: str, problem: str, dist: str,
               tw: float, qa: float, k: int):
    if mode == "drf":
        return -y_d[:, k], jnp.ones_like(y_d[:, k])
    if problem == "multinomial":
        p = jax.nn.softmax(margins, axis=1)
        return p[:, k] - y_d[:, k], p[:, k] * (1 - p[:, k])
    return dist_mod.grad_hess(dist, margins[:, 0], y_d[:, 0],
                              tweedie_power=tw, alpha=qa)


@functools.partial(jax.jit, static_argnames=(
    "npad", "F", "row_sampling", "col_sampling"))
def _sample_jit(key, rate_a, w_a, hp, npad: int, F: int,
                row_sampling: bool, col_sampling: bool):
    """The `_one_tree` sampling prologue, key chain included."""
    krow, kcol, ktree = jax.random.split(jax.random.fold_in(key, 0), 3)
    if row_sampling:
        row_mask = (jax.random.uniform(krow, (npad,)) < rate_a
                    ).astype(jnp.float32)
        wt = w_a * row_mask
    else:
        row_mask = jnp.ones(npad, jnp.float32)
        wt = w_a
    if col_sampling:
        fm = (jax.random.uniform(kcol, (F,)) < hp[6]).astype(jnp.float32)
        fm = fm.at[0].set(jnp.maximum(fm[0], 1 - fm.sum().clip(0, 1)))
    else:
        fm = jnp.ones(F, jnp.float32)
    return row_mask, wt, fm, ktree


@jax.jit
def _scale_jit(hp, m):
    return (hp[4] * jnp.power(hp[5], jnp.asarray(m, jnp.float32))
            ).astype(jnp.float32)


def _partition(codes_b, idx_b, bf, bb, do_split, L: int, pack_bits: int):
    """One block's row partition under a level decision — the build_tree
    partition reads, verbatim (a packed block widens standalone: block
    boundaries sit on pack-group boundaries)."""
    rf = _lookup_int(bf, idx_b, L)
    rb = _lookup_int(bb, idx_b, L)
    rs = _lookup_bool(do_split, idx_b, L)
    rcode = _row_codes(codes_b, rf, pack_bits)
    go_right = (rcode > rb) & rs
    return 2 * idx_b + go_right.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "nbins", "method", "pack_bits", "row_chunk"))
def _first_pass_jit(codes_b, g_b, h_b, wt_b, nbins: int, method: str,
                    pack_bits: int, row_chunk: Optional[int]):
    """Level-0 block partial: root histogram over one block."""
    node = jnp.zeros(g_b.shape[0], jnp.int32)
    vals = jnp.stack([wt_b, g_b * wt_b, h_b * wt_b]).astype(jnp.float32)
    return run_block_kernel(method, codes_b, node, vals, 1, nbins,
                            pack_bits, row_chunk)


@functools.partial(jax.jit, static_argnames=(
    "L_prev", "nbins", "method", "pack_bits", "row_chunk"))
def _level_pass_jit(codes_b, idx_b, g_b, h_b, wt_b, bf, bb, do_split,
                    L_prev: int, nbins: int, method: str, pack_bits: int,
                    row_chunk: Optional[int]):
    """The fused per-block visit of level d: apply level d-1's partition,
    then accumulate level d's sibling-LEFT histogram partial (right =
    parent − left happens on the merged histograms)."""
    idx_b = _partition(codes_b, idx_b, bf, bb, do_split, L_prev, pack_bits)
    is_left = (idx_b % 2 == 0)
    w_eff = wt_b * is_left.astype(wt_b.dtype)
    vals = jnp.stack([w_eff, g_b * w_eff, h_b * w_eff]).astype(jnp.float32)
    part = run_block_kernel(method, codes_b, idx_b // 2, vals, L_prev,
                            nbins, pack_bits, row_chunk)
    return idx_b, part


def _leaf_block_tot(ids_b, vals_b, nseg: int, use_oh: bool):
    """One block's exact {Σw, Σg·w, Σh·w} leaf totals — `_leaf_totals.one`."""
    if use_oh:
        oh = (ids_b[:, None] == jnp.arange(nseg, dtype=jnp.int32)[None, :]
              ).astype(jnp.float32)
        return jnp.dot(vals_b, oh, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST).T
    return jax.ops.segment_sum(vals_b.T, ids_b, num_segments=nseg)


@functools.partial(jax.jit, static_argnames=(
    "L_prev", "nseg", "use_oh", "pack_bits"))
def _leaf_pass_jit(codes_b, idx_b, g_b, h_b, wt_b, bf, bb, do_split,
                   L_prev: int, nseg: int, use_oh: bool, pack_bits: int):
    """Final block visit: last level's partition + exact leaf totals."""
    idx_b = _partition(codes_b, idx_b, bf, bb, do_split, L_prev, pack_bits)
    vals = jnp.stack([wt_b, g_b * wt_b, h_b * wt_b])
    return idx_b, _leaf_block_tot(idx_b, vals, nseg, use_oh)


@jax.jit
def _fold_jit(parts):
    """Deterministic left-to-right merge of stacked block partials — the
    SAME `ordered_axis_fold` the in-core blocked reduction pins."""
    return ordered_axis_fold(parts, None)


@jax.jit
def _sibling_merge_jit(hist_prev, left):
    right = hist_prev - left
    L = 2 * left.shape[0]
    return jnp.stack([left, right], axis=1).reshape((L,) + left.shape[1:])


@functools.partial(jax.jit, static_argnames=("nbins", "has_keep"))
def _level_decide_jit(hist, active, feat_mask, keep, edges, hp, gain_pf,
                      nbins: int, has_keep: bool):
    """Merged-histogram level decision: node values, fused split search,
    varimp fold and raw thresholds — build_tree's dense-level body."""
    F = edges.shape[0]
    wsum, gsum, hsum = treelib._node_totals(hist)
    gthr = jnp.sign(gsum) * jnp.maximum(jnp.abs(gsum) - hp[3], 0.0)
    node_val = (-gthr / (hsum + hp[2] + 1e-12)).astype(jnp.float32)
    node_val = jnp.clip(node_val, -hp[7], hp[7])
    best_gain, bf, bb, _, _ = _fused_level_best(
        hist, active, feat_mask, keep if has_keep else None, nbins,
        hp[0], hp[2], hp[3], gsum, hsum)
    do_split = best_gain > jnp.maximum(hp[1], 1e-10)
    gain_pf = gain_pf + jax.ops.segment_sum(
        jnp.where(do_split, best_gain, 0.0).astype(jnp.float32), bf,
        num_segments=F)
    pad_edges = jnp.concatenate(
        [edges.astype(jnp.float32), jnp.full((F, 1), jnp.inf, jnp.float32)],
        axis=1)
    bthr = pad_edges[bf, jnp.minimum(bb, nbins - 2)]
    return node_val, wsum, do_split, bf, bb, bthr, gain_pf


@jax.jit
def _leaf_values_jit(tot, hp):
    gthr_f = jnp.sign(tot[:, 1]) * jnp.maximum(jnp.abs(tot[:, 1]) - hp[3],
                                               0.0)
    leaf_val = (-gthr_f / (tot[:, 2] + hp[2] + 1e-12)).astype(jnp.float32)
    leaf_val = jnp.clip(leaf_val, -hp[7], hp[7])
    return leaf_val, tot[:, 0].astype(jnp.float32)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("k",))
def _margin_add_jit(margins, leaf_vals, k: int):
    return margins.at[:, k].add(leaf_vals)


@jax.jit
def _pack_jit(feat, bin_, thr, is_split, value, covers):
    """Tree fields + covers → one (K, T, 6) f32 array (shared_tree._pack)."""
    return jnp.stack(
        [feat.astype(jnp.float32), bin_.astype(jnp.float32), thr,
         is_split.astype(jnp.float32), value, covers], axis=-1)


@functools.partial(jax.jit, static_argnames=("pack_bits", "max_depth"))
def _predict_block_jit(tree, codes_b, pack_bits: int, max_depth: int):
    return treelib.predict_codes_packed(tree, codes_b, pack_bits, max_depth)


class _ResidentBlocks:
    """Trivial provider over already-resident device blocks (the GOSS
    compact sample) — same surface as BlockStore where the level loop
    needs it."""

    def __init__(self, dev_blocks: List):
        self._dev = dev_blocks

    def get(self, b: int):
        return self._dev[b]

    def prefetch(self, b: int) -> None:
        pass


class StreamedTreeStep:
    """Drop-in replacement for the driver's jitted `tree_jit`: the same
    (margins, oob_sum, oob_cnt, codes, y, w, rate, edges, mono, hp, key,
    m) → (margins, oob_sum, oob_cnt, packed, gains, overflow) contract,
    built from per-block programs over a `BlockStore` instead of one
    monolithic program over a resident matrix. `codes` is ignored (the
    store holds the matrix); `mono` must be all-zero (monotone fits are
    gated in-core)."""

    def __init__(self, cfg, store, seed: int = 0,
                 goss: Optional[Dict] = None):
        if cfg.n_shards <= 0 or cfg.npad % cfg.n_shards:
            raise ValueError("streamed step needs an aligned block grid")
        self.cfg = cfg
        self.store = store
        self.S = int(cfg.n_shards)
        self.rows = cfg.npad // self.S
        self.seed = int(seed)
        self.goss = goss
        if goss:
            a, b = goss["top_rate"], goss["other_rate"]
            frac = min(a + b * 1.25 + 0.02, 1.0)
            cap = int(cfg.npad * frac) + 8
            self.goss_cap = min(cfg.npad, ((cap + 7) // 8) * 8)

    # -- the streamed build_tree (dense depthwise, fused split) ------------

    def _build_streamed(self, provider, S: int, rows: int, g, h, wt, fm,
                        edges, hp, key):
        cfg = self.cfg
        D, nbins, F = cfg.max_depth, cfg.nbins, cfg.F
        pack_bits = cfg.pack_bits
        T = heap_size(D)
        feat_a = jnp.zeros(T, jnp.int32)
        bin_a = jnp.zeros(T, jnp.int32)
        thr_a = jnp.zeros(T, jnp.float32)
        split_a = jnp.zeros(T, bool)
        value_a = jnp.zeros(T, jnp.float32)
        cover_a = jnp.zeros(T, jnp.float32)
        gain_pf = jnp.zeros(F, jnp.float32)
        active = jnp.ones(1, bool)
        idx_blocks = [jnp.zeros(rows, jnp.int32) for _ in range(S)]
        dec = None
        hist_prev = None
        key_b = key
        for d in range(D):
            L = 2 ** d
            L_kernel = 1 if d == 0 else L // 2
            sel = resolve_method(L_kernel, nbins, cfg.hist_method)
            method, row_chunk = sel["method"], sel["row_chunk"]
            parts = []
            for b in range(S):
                # per-BLOCK QoS yield: the streamed grid is the natural
                # preemption point — serving dispatches slot in between
                # block visits instead of behind a whole level
                _qos.yield_point("tree_block")
                # supervisor heartbeat (ISSUE 20): a streamed fit's chunk
                # boundaries can be minutes apart — per-block pulses keep
                # its liveness signal fresh for the failure detector
                _supervisor.pulse("tree_stream", d * S + b)
                codes_b = provider.get(b)
                if d == 0:
                    part = _first_pass_jit(
                        codes_b, g[b * rows:(b + 1) * rows],
                        h[b * rows:(b + 1) * rows],
                        wt[b * rows:(b + 1) * rows],
                        nbins, method, pack_bits, row_chunk)
                else:
                    idx_b, part = _level_pass_jit(
                        codes_b, idx_blocks[b],
                        g[b * rows:(b + 1) * rows],
                        h[b * rows:(b + 1) * rows],
                        wt[b * rows:(b + 1) * rows], *dec,
                        L // 2, nbins, method, pack_bits, row_chunk)
                    idx_blocks[b] = idx_b
                # double buffer: block b's kernel is dispatched (async);
                # start block b+1's H2D now so transfer and compute overlap
                provider.prefetch((b + 1) % S)
                parts.append(part)
            merged = _fold_jit(jnp.stack(parts))
            hist = merged if d == 0 else _sibling_merge_jit(hist_prev,
                                                            merged)
            hist_prev = hist
            keep = None
            if cfg.has_mtries:
                key_b, sub = jax.random.split(key_b)
                keep = jax.random.uniform(sub, (L, F)) < hp[8]
                keep = keep.at[:, 0].set(keep[:, 0] | ~keep.any(axis=1))
            node_val, wsum, do_split, bf, bb, bthr, gain_pf = \
                _level_decide_jit(hist, active, fm, keep, edges, hp,
                                  gain_pf, nbins, keep is not None)
            base = L - 1
            value_a = value_a.at[base:base + L].set(node_val)
            cover_a = cover_a.at[base:base + L].set(
                wsum.astype(jnp.float32))
            feat_a = feat_a.at[base:base + L].set(
                jnp.where(do_split, bf, 0))
            bin_a = bin_a.at[base:base + L].set(jnp.where(do_split, bb, 0))
            thr_a = thr_a.at[base:base + L].set(
                jnp.where(do_split, bthr, 0.0))
            split_a = split_a.at[base:base + L].set(do_split)
            active = jnp.repeat(do_split, 2)
            dec = (bf, bb, do_split)
        # final level: exact per-cell totals, blocked + ordered fold
        Lf = 2 ** D
        basef = Lf - 1
        use_oh = Lf <= 2 * _ONEHOT_LOOKUP_MAX
        parts = []
        for b in range(S):
            _qos.yield_point("tree_block")
            codes_b = provider.get(b)
            idx_b, tot_b = _leaf_pass_jit(
                codes_b, idx_blocks[b], g[b * rows:(b + 1) * rows],
                h[b * rows:(b + 1) * rows], wt[b * rows:(b + 1) * rows],
                *dec, Lf // 2, Lf, use_oh, pack_bits)
            idx_blocks[b] = idx_b
            provider.prefetch((b + 1) % S)
            parts.append(tot_b)
        tot = _fold_jit(jnp.stack(parts))
        leaf_val, leaf_cover = _leaf_values_jit(tot, hp)
        value_a = value_a.at[basef:].set(leaf_val)
        cover_a = cover_a.at[basef:].set(leaf_cover)
        leaf_idx = jnp.concatenate(idx_blocks) + basef
        return (treelib.Tree(feat_a, bin_a, thr_a, split_a, value_a),
                leaf_idx, gain_pf, cover_a)

    # -- GOSS: gradient-based sampling ------------------------------------

    def _goss_active(self, m: int) -> bool:
        return self.goss is not None and m >= self.goss["start_tree"]

    def _gather_codes(self, sel: np.ndarray) -> np.ndarray:
        """Selected rows gathered from the HOST blocks into a compact
        full-width matrix — per-block unpack transients only."""
        cfg = self.cfg
        out = np.zeros((self.goss_cap, cfg.F),
                       np.uint8 if cfg.nbins <= 256 else np.uint16)
        rows, bits = self.rows, cfg.pack_bits
        blk = sel // rows
        pos = 0
        for b in np.unique(blk):
            rb = sel[blk == b] - b * rows
            # a restoring fetch: GOSS-on-disk reads only the blocks the
            # sample touches (all of them once, here) and the per-level
            # passes then stream just the compact sample — the disk tier
            # is where sampling pays most (arXiv 1806.11248)
            hb = self.store.fetch_host(int(b))
            dense = packing.unpack_host(hb, bits) if bits else hb
            out[pos:pos + len(rb)] = dense[rb]
            pos += len(rb)
        return out

    def _goss_tree(self, g, h, w_a, fm, edges, hp, ktree, m: int, scale):
        """One GOSS tree: build on the compact top-|g| + amplified-rest
        sample, then stream every block ONCE for the full-row margin
        update. Returns (scaled tree, gains, cover, full-row leaf
        values)."""
        cfg = self.cfg
        a, brate = self.goss["top_rate"], self.goss["other_rate"]
        amp = np.float32((1.0 - a) / brate)
        w_np = np.asarray(w_a, np.float32) > 0
        absg = np.where(w_np, np.abs(np.asarray(g, np.float32)), -1.0)
        n_real = max(int(w_np.sum()), 1)
        n_top = max(int(a * n_real), 1)
        # EXACTLY n_top rows (argpartition, deterministic for a given
        # input) — a `>= threshold` mask over-selects on tied |g| (e.g.
        # laplace/quantile sign-shaped gradients, where every row ties)
        # and the cap trim would then keep an index-biased subset
        top = np.zeros(absg.shape[0], bool)
        top[np.argpartition(absg, -n_top)[-n_top:]] = True
        rng = np.random.default_rng((self.seed + 7919 * (m + 1))
                                    & 0x7FFFFFFF)
        rest = (~top) & w_np & (rng.random(absg.shape[0])
                                < brate / max(1.0 - a, 1e-9))
        weight = np.where(top, np.float32(1.0),
                          np.where(rest, amp, np.float32(0.0))
                          ).astype(np.float32)
        sel = np.nonzero(weight > 0)[0]
        if len(sel) > self.goss_cap:
            sel = sel[:self.goss_cap]    # deterministic slack overflow trim
        cap = self.goss_cap
        codes_sel = self._gather_codes(sel)
        packed_sel = (packing.pack_host(codes_sel, cfg.pack_bits)
                      if cfg.pack_bits else codes_sel)
        dev = jnp.asarray(packed_sel)
        self.store.account_external_bytes(int(packed_sel.nbytes))
        sel_pad = np.zeros(cap, np.int32)
        sel_pad[:len(sel)] = sel
        sel_d = jnp.asarray(sel_pad)
        w_sel_np = np.zeros(cap, np.float32)
        w_sel_np[:len(sel)] = np.asarray(w_a, np.float32)[sel] * weight[sel]
        g_sel = jnp.take(g, sel_d)
        h_sel = jnp.take(h, sel_d)
        w_sel = jnp.asarray(w_sel_np)
        provider = _ResidentBlocks([dev])
        tr, _idx, gains, cover = self._build_streamed(
            provider, 1, cap, g_sel, h_sel, w_sel, fm, edges, hp, ktree)
        tr = tr._replace(value=tr.value * scale)
        vals = []
        for b in range(self.S):
            _qos.yield_point("tree_block")
            codes_b = self.store.get(b)
            vals.append(_predict_block_jit(tr, codes_b, cfg.pack_bits,
                                           cfg.max_depth))
            self.store.prefetch((b + 1) % self.S)
        return tr, gains, cover, jnp.concatenate(vals)

    # -- the step ----------------------------------------------------------

    def __call__(self, margins, oob_sum, oob_cnt, codes_d, y_a, w_a,
                 rate_a, edges_a, mono, hp, key, m):
        cfg = self.cfg
        m_int = int(m)
        key_t = jax.random.fold_in(key, m_int)
        row_mask, wt, fm, ktree = _sample_jit(
            key_t, rate_a, w_a, hp, cfg.npad, cfg.F,
            not cfg.no_row_sampling, cfg.has_col_sampling)
        scale = _scale_jit(hp, m_int)
        trs, covs = [], []
        gains_acc = jnp.zeros(cfg.F, jnp.float32)
        oob_inc = None
        for k in range(cfg.K):
            ktree = jax.random.fold_in(ktree, k)
            g, h = _grads_jit(margins, y_a, cfg.mode, cfg.problem, cfg.dist,
                              cfg.tweedie_power, cfg.quantile_alpha, k)
            if self._goss_active(m_int):
                tr, gains, cover, leaf_vals = self._goss_tree(
                    g, h, w_a, fm, edges_a, hp, ktree, m_int, scale)
            else:
                tr, leaf_idx, gains, cover = self._build_streamed(
                    self.store, self.S, self.rows, g, h, wt, fm, edges_a,
                    hp, ktree)
                tr = tr._replace(value=tr.value * scale)
                leaf_vals = treelib.value_at(tr.value, leaf_idx)
            margins = _margin_add_jit(margins, leaf_vals, k)
            if cfg.mode == "drf":
                col = leaf_vals * (1.0 - row_mask)
                oob_inc = (col[:, None] if oob_inc is None
                           else jnp.concatenate([oob_inc, col[:, None]],
                                                axis=1))
            trs.append(tr)
            covs.append(cover)
            gains_acc = gains_acc + gains
        stacked = treelib.Tree(
            *[jnp.stack([getattr(t, f) for t in trs])
              for f in treelib.Tree._fields])
        covers = jnp.stack(covs)
        packed = _pack_jit(stacked.feat, stacked.bin, stacked.thr,
                           stacked.is_split, stacked.value, covers)
        if oob_inc is not None:
            oob_sum = oob_sum + oob_inc
            oob_cnt = oob_cnt + (1.0 - row_mask)
        return margins, oob_sum, oob_cnt, packed, gains_acc, jnp.int32(0)
