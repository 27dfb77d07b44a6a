"""SharedTree driver — the boosting/forest loop over the jitted tree builder.

Reference parity: `h2o-algos/src/main/java/hex/tree/SharedTree.java`
(`Driver.computeImpl`: init counts → outer tree loop → score/early-stop) and
`hex/tree/gbm/GBM.java` (`GBMDriver.buildNextKTrees`: k trees per iteration,
one per class). Scoring cadence follows `score_tree_interval` /
`score_each_iteration`; early stopping is `hex/ScoreKeeper.java` semantics;
variable importance is squared-error-reduction per feature
(`hex/tree/SharedTree.java` varimp from split gains).

The per-tree step (gradients → histograms → splits → partition) is one XLA
program (see `tree.py`); on a multi-device cloud it runs under `shard_map`
with rows sharded over ``hosts`` and histogram merges as `lax.psum` —
replacing the MRTask RPC-tree reduce of `ScoreBuildHistogram2.java`.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

from ..runtime import phases as _phases_acct
from ..runtime import qos as _qos
from ..runtime import tracing as _tracing


class _Phase:
    """The tree fit's phase boundaries — the `water.util.Timer` per-stage
    logging analog for the training driver — and, from the same marks, its
    part of the fit's span tree (docs/observability.md): `_fit` runs inside
    ``with _Phase()``, which holds ONE ``fit.*`` span open at a time,
    ``fit.design`` from the start; a mark with ``then=`` ends it there and
    opens the next. Under it `stage()` holds one child span open at a time
    (``design.matrix``, ``iterate.setup``, ...): the stages tile their
    ``fit.*`` span, so no stretch of a tree fit is without a name."""

    _SUBTRACT_KEYS = _phases_acct.COMPILE_KEYS + ("collective",)

    def __init__(self):
        self.t = time.time()
        self._comp0 = _phases_acct.totals(self._SUBTRACT_KEYS)
        self._span = self._stage = None
        self.fit_span = self.stage_span = None   # the open Spans, for attrs

    def __enter__(self):
        self._open("fit.design")
        return self

    def __exit__(self, *exc):
        self._end_stage(*exc)
        return self._span.__exit__(*exc)

    def _open(self, name):
        self._span = _tracing.span(name, kind="fit")
        self.fit_span = self._span.__enter__()

    def _end_stage(self, *exc):
        if self._stage is not None:
            self._stage.__exit__(*(exc or (None, None, None)))
            self._stage = self.stage_span = None

    def stage(self, name):
        """End the open stage, if any, and open `name` as a child of the
        open ``fit.*`` span."""
        self._end_stage()
        self._stage = _tracing.span(name, kind="fit")
        self.stage_span = self._stage.__enter__()

    def mark(self, name, then=None):
        """Record a phase boundary into /3/Timeline. Boundaries also feed
        runtime.phases, which decomposes wall-clock into {h2d, compute,
        d2h, ...} buckets. `then` names the ``fit.*`` span that begins at
        this boundary."""
        from ..runtime.timeline import Timeline

        _phases = _phases_acct
        now = time.time()
        Timeline.record("train_phase", name, secs=round(now - self.t, 4))
        # compile/trace time inside this interval is already accounted by
        # the monitoring listener, and collective-fence waits by
        # mesh.collective_fence — subtract both so the compute bucket
        # holds execution time, not compilation or merge waits (the phase
        # split must sum to ≤ wall, never double-count)
        comp = _phases.totals(self._SUBTRACT_KEYS)
        _phases.add_mark(name, max(now - self.t - (comp - self._comp0), 0.0))
        self._comp0 = comp
        self.t = now
        if then is not None:
            self._end_stage()
            self._span.__exit__(None, None, None)
            self._open(then)


def _noting_miss(ph: _Phase, builder):
    """`builder` for a dataset-cache look-up made under stage `ph` holds
    open: called only on a miss, it says so on the stage's span (attr
    ``cache``, ``hit`` until then)."""
    sp = ph.stage_span

    def run():
        sp.annotate(cache="miss")
        return builder()

    return run


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..frame.binning import BinnedMatrix, bin_apply, build_bins
from ..frame.frame import Frame
from ..parallel import distdata
from ..parallel import mesh as cloudlib
from . import distributions as dist_mod
from . import tree as treelib
from .metrics import (
    ModelMetricsBinomial,
    ModelMetricsMultinomial,
    ModelMetricsRegression,
)
from .model_base import (SCORE_ROW_BUCKET, DataInfo, H2OEstimator,
                         H2OModel, ScoreKeeper, ScoringHistory,
                         response_info)


_predict_codes_jit = jax.jit(treelib.predict_codes, static_argnames=("max_depth",))

# rows a step of `_rows_above_edges`: one step is an (edges, 8,192) compare
# reduced along the rows, which XLA keeps in registers; under 2^16, which the
# packed counts need
_EDGE_COUNT_ROWS = 8192


def _edge_count_block(rows: int) -> int:
    """The row block of `_rows_above_edges` over `rows` padded rows: a
    tiny fit runs one block."""
    return min(_EDGE_COUNT_ROWS, rows)


def _rows_above_edges(qs, p, valid, pos):
    """For every edge `qs[j]`, the valid rows and the positive rows whose
    score `p` lies above it, in `searchsorted`'s order (a NaN score lies
    above every edge, nothing lies above a NaN edge), as two int32 arrays.
    A scan over row blocks with the edge axis major: no per-row bin index,
    no gather over the rows, no scatter, and no `(edges, rows)` array in
    memory. Both counts ride in one int32 a row, valid in the low 16 bits
    and positive in the high, so a block is one compare-and-sum; a block's
    sums stay under 2^16 in each half."""
    n = p.shape[0]
    blk = _edge_count_block(n)
    nblk = -(-n // blk)
    w = valid.astype(jnp.int32) | (pos.astype(jnp.int32) << 16)

    def blocks(a):
        return jnp.pad(a, (0, nblk * blk - n)).reshape(nblk, blk)

    def step(acc, xs):
        pb, wb = xs
        s = jnp.sum(jnp.where(~(pb[None, :] <= qs[:, None]), wb[None, :], 0),
                    axis=1)
        return (acc[0] + (s & 0xFFFF), acc[1] + (s >> 16)), None

    zero = jnp.zeros(qs.shape, jnp.int32)
    (n_all, n_pos), _ = jax.lax.scan(step, (zero, zero),
                                     (blocks(p), blocks(w)))
    nan_edge = jnp.isnan(qs)
    return jnp.where(nan_edge, 0, n_all), jnp.where(nan_edge, 0, n_pos)


@functools.partial(jax.jit, static_argnames=("nbins",))
def _binom_binned_stats(margins, y_d, n, nbins: int = 400):
    """AUC2-style 400-bin score histogram ON DEVICE (hex/AUC2.java): the
    quantile edges, per-bin (pos, neg) counts and the logloss/mse sums are
    the only things that cross the wire (~KBs instead of the 4·n-byte
    margin pull + a host rank sort).

    Bin `b` holds the rows with `qs[b-1] < p <= qs[b]` (`searchsorted`,
    side left): the rows above edge `b-1` less those above edge `b`, the
    valid total above no edge and nothing above edge `nbins`.

    `n` is TRACED (pad rows masked out), so CV folds padded to the parent
    frame's row shape reuse ONE compiled program instead of recompiling
    per fold row count (cold-start tax)."""
    valid = jnp.arange(margins.shape[0]) < n
    p = jax.nn.sigmoid(margins[:, 0])
    y = y_d[:, 0]
    qs = jnp.nanquantile(jnp.where(valid, p, jnp.nan),
                         jnp.linspace(0.0, 1.0, nbins))
    pos = valid & (y > 0.5)
    zero = jnp.zeros((1,), jnp.int32)

    def per_bin(total, above):
        return (jnp.concatenate([total[None], above])
                - jnp.concatenate([above, zero]))

    a_all, a_pos = _rows_above_edges(qs, p, valid, pos)
    c_all = per_bin(jnp.sum(valid, dtype=jnp.int32), a_all)
    c_pos = per_bin(jnp.sum(pos, dtype=jnp.int32), a_pos)
    npos = c_pos.astype(jnp.float32)
    nneg = (c_all - c_pos).astype(jnp.float32)
    pc = jnp.clip(p, 1e-15, 1 - 1e-15)
    nll = -jnp.sum(jnp.where(valid & (y > 0.5), jnp.log(pc), 0.0)
                   + jnp.where(valid & (y <= 0.5), jnp.log(1.0 - pc), 0.0))
    sq = jnp.sum(jnp.where(valid, (p - y) ** 2, 0.0))
    return qs, npos, nneg, nll, sq


def _event_loss_terms(margins, y_d, valid, inv_ntrees, mode: str,
                      problem: str, dist: str):
    """Per-row (loss·mask, mask) terms of the scoring-event mean loss —
    the ONE source of the event math, shared by the historical whole-array
    reduction (`_event_loss_device`) and the sharded blocked reduction
    (`_sharded_event_loss_fn`) so the two can never diverge. Clips use
    1e-7 (the f64 path's 1e-15 rounds to exactly 0/1 in f32, which would
    turn a saturated probability into an inf logloss)."""
    vf = valid.astype(jnp.float32)
    probs = _margins_to_preds(mode, problem, dist, margins, inv_ntrees, jnp)
    eps = 1e-7
    if problem == "binomial":
        pc = jnp.clip(probs[:, 1], eps, 1 - eps)
        y = y_d[:, 0]
        nll = -jnp.where(y > 0.5, jnp.log(pc), jnp.log1p(-pc))
        return nll * vf, vf
    if problem == "multinomial":
        pc = jnp.clip(probs, eps, 1.0)
        nll = -jnp.sum(jnp.log(pc) * y_d, axis=1)
        return nll * vf, vf
    sq = (probs[:, 0] - y_d[:, 0]) ** 2
    return sq * vf, vf


@functools.partial(jax.jit, static_argnames=("mode", "problem", "dist"))
def _event_loss_device(margins, y_d, valid, inv_ntrees, mode: str,
                       problem: str, dist: str):
    """Scoring-event mean loss ON DEVICE: ONE scalar is the only D2H — the
    host path pulled the full margin matrix (4·n·K bytes) to the host
    per event. The link mapping is _margins_to_preds (the same
    source model.predict uses); `inv_ntrees` is traced so every event of a
    fit reuses ONE compiled program. On a multi-process cloud the inputs
    are global sharded arrays, so the mean comes back global and
    replicated — no separate host collective needed."""
    num, den = _event_loss_terms(margins, y_d, valid, inv_ntrees, mode,
                                 problem, dist)
    return jnp.sum(num) / jnp.maximum(jnp.sum(den), 1e-12)


def _sharded_event_loss_fn(cloud, shard_mode: str, n_shards: int,
                           mode: str, problem: str, dist: str):
    """Deterministic scoring-event loss for sharded fits: per-block partial
    sums + `ordered_axis_fold`, mirroring the histogram merge, so the
    early-stopping decisions an N-device fit makes are bit-identical to
    the 1-device forced-shard lane's (a last-ulp loss difference at the
    stopping tolerance boundary would otherwise diverge the tree COUNT,
    not just the bits). Cached on the cloud like the step programs."""
    from ..ops.histogram import ordered_axis_fold

    local_blocks = (n_shards // cloud.size if shard_mode == "mesh"
                    else n_shards)
    axis = (cloudlib.ROWS_AXIS
            if shard_mode == "mesh" and cloud.size > 1 else None)
    key_ = ("event", local_blocks, axis, mode, problem, dist)
    with _STEP_FNS_LOCK:
        cache = cloud.__dict__.setdefault("_event_fns_cache", {})
        fn = cache.get(key_)
        if fn is not None:
            return fn

    def inner(margins, y_d, valid, inv_ntrees):
        num, den = _event_loss_terms(margins, y_d, valid, inv_ntrees,
                                     mode, problem, dist)
        rows = num.shape[0] // local_blocks
        parts = jnp.stack([
            jnp.stack([jnp.sum(num[b * rows:(b + 1) * rows]),
                       jnp.sum(den[b * rows:(b + 1) * rows])])
            for b in range(local_blocks)])
        # the ONE instrumented fence of the fit (ISSUE 13): the event-loss
        # program runs once per scoring interval, so per-lane arrival
        # stamps here profile collective skew without touching the
        # per-level histogram hot path
        tot = ordered_axis_fold(parts, axis, timing_tag="event_loss")
        return tot[0] / jnp.maximum(tot[1], 1e-12)

    if axis is not None:
        rspec = P(cloudlib.ROWS_AXIS)
        inner = cloudlib.shard_call(
            inner, cloud, in_specs=(rspec, rspec, rspec, P()),
            out_specs=P(), check_vma=False)
    fn = jax.jit(inner)
    with _STEP_FNS_LOCK:
        cloud.__dict__.setdefault("_event_fns_cache", {})[key_] = fn
    return fn


@functools.partial(jax.jit, static_argnames=("max_depth",))
def _predict_forest_codes_jit(forest, codes, max_depth: int):
    """Σ over a stacked forest of per-row leaf values on binned codes."""
    per_tree = jax.vmap(lambda t: treelib.predict_codes(t, codes, max_depth))(forest)
    return per_tree.sum(axis=0)


@functools.partial(jax.jit, static_argnames=("max_depth",),
                   donate_argnums=(2,))
def _margin_ffwd_jit(forest, codes, margins, k, max_depth: int):
    """Checkpoint fast-forward: add a restored class-k forest's leaf sums
    to the margins in ONE program (works on process-spanning arrays, where
    the eager .at add would be rejected)."""
    per_tree = jax.vmap(
        lambda t: treelib.predict_codes(t, codes, max_depth))(forest)
    return margins.at[:, k].add(per_tree.sum(axis=0))


@functools.partial(jax.jit, static_argnames=("max_depth",),
                   donate_argnums=(2,))
def _valid_margin_update(packed, codes_v, margins_v, k, max_depth: int):
    """Add class-k leaf sums of a packed tree chunk to the validation
    margins — one jitted program so it also runs on process-spanning
    (multi-host) arrays, where eager slicing is rejected. `k` is TRACED
    (dynamic slice): one compiled program serves all K classes instead of
    K compile-cache loads."""
    sl = jax.lax.dynamic_index_in_dim(packed, k, axis=1, keepdims=False)
    forest = treelib.Tree(
        sl[..., 0].astype(jnp.int32), sl[..., 1].astype(jnp.int32),
        sl[..., 2], sl[..., 3] > 0.5, sl[..., 4],
    )
    per_tree = jax.vmap(
        lambda t: treelib.predict_codes(t, codes_v, max_depth))(forest)
    return margins_v.at[:, k].add(per_tree.sum(axis=0))


# ---- DART dropout boosting (xgboost booster=dart; dart.cc) --------------
#
# Dropout granularity is a boosting ROUND: all K class trees of a round
# drop together, with one scale per round on the STORED (learn-rate-folded)
# leaf contributions. Commit normalization per xgboost docs: with k rounds
# dropped and learning rate lr, "tree" scales dropped rounds by k/(k+lr)
# and the new round by 1/(k+lr); "forest" scales both by 1/(1+lr).
# Scales are tracked host-side and baked into the packed leaf values after
# the loop, so scoring / MOJO / TreeSHAP see ordinary trees.


def _round_contribs(pk, codes, max_depth: int):
    """One packed round (K, T, C) → (N, K) leaf contributions on codes."""
    K = pk.shape[0]
    cs = []
    for k in range(K):
        t = treelib.Tree(pk[k, :, 0].astype(jnp.int32),
                         pk[k, :, 1].astype(jnp.int32),
                         pk[k, :, 2], pk[k, :, 3] > 0.5, pk[k, :, 4])
        cs.append(treelib.predict_codes(t, codes, max_depth))
    return jnp.stack(cs, axis=1)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def _dart_drop_sum_jit(chunks, scales, codes, max_depth: int):
    """Σ over selected rounds of scale·leaf values → (N, K) margin mass.
    `chunks` is a pow2-padded TUPLE of (1, K, T, C) round packs — selected
    host-side so the work is O(dropped), and concatenated INSIDE the jit so
    process-spanning (multi-host) arrays are handled; zero-scale pad
    entries contribute exactly 0."""
    packed_sel = jnp.concatenate(chunks, axis=0)
    return jax.vmap(
        lambda pk, s: s * _round_contribs(pk, codes, max_depth)
    )(packed_sel, scales).sum(axis=0)


@functools.partial(jax.jit, donate_argnums=(0,))
def _dart_sub_jit(margins, dsum):
    return margins - dsum


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("max_depth",))
def _dart_fix_jit(margins, packed_new, dsum, codes, c_coef, d_coef,
                  max_depth: int):
    """margins + c_coef·(new round's contribution) + d_coef·dsum — the
    post-step normalization (coefficients differ between the training
    margins, which already had dsum subtracted, and validation margins,
    which did not)."""
    c_new = _round_contribs(packed_new[0], codes, max_depth)
    return margins + c_coef * c_new + d_coef * dsum


@functools.partial(jax.jit, donate_argnums=(0,))
def _dart_scale_jit(pk, s):
    return pk.at[..., 4].multiply(s)


def _margins_to_preds(mode, problem, dist, m, inv_ntrees, xp):
    """margins → predictions — the ONE per-mode link mapping, parameterized
    by array module so host scoring (np) and the device event kernel (jnp)
    cannot diverge. `inv_ntrees` is a scalar (python float on host, traced
    on device)."""
    if mode == "drf":
        # DRF: leaf values are per-leaf response means; prediction is the
        # forest average (hex/tree/drf/DRFModel.score0 vote averaging)
        m = m * inv_ntrees
        if problem == "binomial":
            p1 = xp.clip(m[:, 0], 0.0, 1.0)
            return xp.stack([1 - p1, p1], axis=1)
        if problem == "multinomial":
            p = xp.clip(m, 0.0, None)
            s = p.sum(axis=1, keepdims=True)
            return xp.where(s > 0, p / xp.maximum(s, 1e-12), 1.0 / m.shape[1])
        return m[:, :1]
    if problem == "binomial":
        p1 = 1 / (1 + xp.exp(-m[:, 0]))
        return xp.stack([1 - p1, p1], axis=1)
    if problem == "multinomial":
        e = xp.exp(m - m.max(axis=1, keepdims=True))
        return e / e.sum(axis=1, keepdims=True)
    mm = m[:, 0]
    if dist in ("poisson", "gamma", "tweedie"):
        return xp.exp(mm)[:, None]
    return mm[:, None]


def probs_from_margins(mode, problem, dist, m: np.ndarray, ntrees: int) -> np.ndarray:
    """Host-side margins → predictions (train-time scoring + model.predict)."""
    return _margins_to_preds(mode, problem, dist, np.asarray(m),
                             1.0 / max(ntrees, 1), np)


def _metrics_for(problem, yvec, probs):
    if problem == "binomial":
        return ModelMetricsBinomial.make(np.asarray(yvec.data), probs[:, 1])
    if problem == "multinomial":
        return ModelMetricsMultinomial.make(np.asarray(yvec.data), probs)
    return ModelMetricsRegression.make(yvec.numeric_np(), probs[:, 0])


def frame_to_matrix(frame: Frame, x: Sequence[str], expected_domains=None):
    """Frame → (X float64 with NaN NAs, is_categorical, domains). Enums stay
    as integer codes (the DHistogram categorical-bins path), not one-hot.

    expected_domains (training-time domains, aligned with x) triggers test-
    frame adaptation: codes are remapped label→training-code, unseen levels
    become NA — `hex/Model.adaptTestForTrain` semantics."""
    cols, cats, doms = [], [], []
    for i, n in enumerate(x):
        v = frame.vec(n)
        col = v.numeric_np()
        exp = expected_domains[i] if expected_domains is not None else None
        if v.type == "enum" and exp is not None and v.domain != exp:
            lookup = {lbl: j for j, lbl in enumerate(exp)}
            remap = np.asarray(
                [lookup.get(lbl, -1) for lbl in (v.domain or [])], np.float64
            )
            codes = np.asarray(v.data)
            col = np.where(
                codes >= 0,
                remap[np.maximum(codes, 0)] if len(remap) else -1.0,
                -1.0,
            )
            col = np.where(col < 0, np.nan, col)
        cols.append(col)
        cats.append(v.type == "enum")
        doms.append(v.domain)
    return np.column_stack(cols), np.asarray(cats), doms


class _Targets(NamedTuple):
    """What a single-process tree fit derives from its response, weights
    and offset columns: the padded device response (npad, K) and weights
    (npad,), zero-weight tail included; the padded device offset or None;
    the initial margin f0 and the balance_classes priors; and the host
    response, kept only for DRF's out-of-bag scoring. None of it depends
    on the learning rate, row limits or seed, so a sweep's candidates on
    one frame share one (`dataset_cache.targets`). No tree program donates
    these arrays."""

    y_d: object
    w_d: object
    off_d: object
    f0: np.ndarray
    balance_dists: Optional[tuple]
    yk: Optional[np.ndarray]


class _StepCfg(NamedTuple):
    """STRUCTURAL configuration of the per-iteration tree-step program —
    only what changes the traced computation graph (shapes, depth, bins,
    problem/mode, static branches). Scalar hyperparameters (learn rate,
    min_rows, regularization, …) are TRACED inputs (the `hp` vector), so
    one compiled program serves every model sharing this cfg: CV folds,
    grid points, and AutoML steps vary scalars without recompiling.
    The jitted step functions are cached per (cfg, cloud)."""

    npad: int
    K: int
    F: int
    nbins: int
    problem: str
    dist: str
    mode: str
    max_depth: int
    has_mtries: bool   # the rate itself is TRACED (hp[8]) so DRF and XRT
    #                    share one program
    no_row_sampling: bool
    has_col_sampling: bool
    has_monotone: bool
    tweedie_power: float
    quantile_alpha: float
    hist_method: str = "auto"
    grow_policy: str = "depthwise"   # "lossguide" = xgboost leaf-wise
    max_leaves: int = 0              # lossguide leaf budget (0 = 2^depth)
    compact_cap: int = 0             # deep-level active-node compaction
    pack_bits: int = 0               # device-RESIDENT sub-byte code packing
    # sharded end-to-end training (ISSUE 12):
    #   "off"       — single-device semantics (also the H2O3_TREE_SHARD=0
    #                 escape hatch on a mesh: data stays on one device)
    #   "mesh"      — shard_map over the 1-D hosts mesh, blocked
    #                 deterministic histogram merge (all_gather + ordered
    #                 fold), rows sharded over devices
    #   "blocks"    — the SAME blocked reduction on one device, no mesh
    #                 (H2O3_TREE_SHARD=1: the forced-CPU lane that is
    #                 bit-identical to any mesh fit sharing n_shards)
    #   "mesh_psum" — the pre-ISSUE-12 shard_map + psum path, kept for
    #                 lossguide growth (multi-process fits run "mesh"
    #                 since ISSUE 18's pod lane)
    shard_mode: str = "off"
    n_shards: int = 0                # canonical total block count (S)
    # the form of the step programs' code argument (`_cfg_operand_form`):
    #   "program" — the resident codes; the program widens what it reads
    #   "fit"     — the pair (resident codes, the fit's Pallas code operand
    #               of `ops.histogram.build_code_operand`): the kernel's
    #               levels and the partition select read the operand
    code_operand: str = "program"


def _pack_hp(tp, lr, colp, mtries_rate=0.0) -> "jnp.ndarray":
    """The traced scalar hyperparameters, in a fixed layout:
    [min_rows, min_split_improvement, reg_lambda, reg_alpha, lr,
    learn_rate_annealing, col_sample_product, max_abs_leaf, mtries_rate]."""
    cap = float(tp.get("max_abs_leaf", np.inf))
    return jnp.asarray(
        [tp["min_rows"], tp["min_split_improvement"], tp["reg_lambda"],
         tp.get("reg_alpha", 0.0), lr, tp["learn_rate_annealing"], colp,
         cap if np.isfinite(cap) else 3.4e38, mtries_rate],
        jnp.float32)


# the fused scorer's dominant transient, per dispatch: the (trees, rows,
# 128) f32 record block `predict_forest_fused` gathers each round. 1 GiB of
# it (16k rows at 128 trees) compiles to 2.3 GB of temporaries for a v5e
_SCORE_PAGE_BYTES = 1 << 30


def _score_page_rows(n_trees: int) -> int:
    """Rows per scorer dispatch for a forest of `n_trees` (padded) trees —
    a multiple of SCORE_ROW_BUCKET, at least one bucket."""
    rows = _SCORE_PAGE_BYTES // (max(n_trees, 1) * 128 * 4)
    return max(SCORE_ROW_BUCKET,
               rows // SCORE_ROW_BUCKET * SCORE_ROW_BUCKET)


_STEP_FNS_CAP = 32
# the per-cloud step-program cache and the device-pack registry are shared
# by concurrent candidate fits (runtime/trainpool.py) — guard them
_STEP_FNS_LOCK = threading.Lock()
_DEV_PACKS_LOCK = threading.Lock()


@jax.jit
def _stack_args(*xs):
    return jnp.stack(xs)


@jax.jit
def _concat_args(*xs):
    return jnp.concatenate(xs, axis=0)


# sub-byte code packing lives in ops/packing.py since ISSUE 7 (the
# histogram kernels and the partition step consume the packed words
# directly); these aliases keep the driver's historical surface
from ..ops import histogram as _hist
from ..ops import packing as _packing
from ..ops.histogram import record_fit_plan as _record_fit_plan
from ..ops.histogram import resolve_method as _resolve_method

_pack_host = _packing.pack_host
_unpack_device = _packing.unpack_device
_pack_bits_for = _packing.pack_bits_for


def _cfg_operand_form(cfg: "_StepCfg") -> dict:
    """`ops.histogram.code_operand_form` of a step configuration's own
    levels, kernel and shard lane: `_make_step_cfg` takes `code_operand`
    from it, the fit and the warm-up thread the operand's `row_chunk`.
    Lossguide growth has no level plan and takes full-width codes."""
    levels = (treelib.histogram_level_plan(cfg.max_depth, cfg.compact_cap)
              if cfg.grow_policy != "lossguide" else [])
    return _hist.code_operand_form(levels, cfg.nbins, cfg.hist_method,
                                   cfg.shard_mode)


def _operand_shape(cfg: "_StepCfg") -> tuple:
    """Shape of `ops.histogram.build_code_operand`'s array for `cfg`."""
    return _hist.code_operand_shape(cfg.F, cfg.npad,
                                    _cfg_operand_form(cfg)["row_chunk"])


def _shard_plan(ndev: int, multiproc: bool, tp) -> tuple:
    """(shard_mode, n_shards) for one fit — the ONE place the ISSUE 12
    sharding decision is made (the warm-up thread and the training path
    must agree or they would warm different programs).

    Default: a multi-device single-process cloud runs the deterministic
    sharded path ("mesh"); one device runs unsharded ("off").
    ``H2O3_TREE_SHARD=0`` is the escape hatch (never shard — a broken mesh
    still trains, on one device); ``H2O3_TREE_SHARD=1`` forces the blocked
    reduction structure on a single device ("blocks") — the forced-CPU
    lane whose fits are bit-identical to mesh fits.

    n_shards (S) is the canonical block count: every row reduction runs as
    S ordered block partials regardless of how many devices they live on,
    so any two fits sharing S agree bitwise. S defaults to
    ``H2O3_TREE_SHARD_BLOCKS`` (8), raised to lcm(S, ndev) so each device
    holds a whole number of blocks — fits on 1/2/4/8 devices all share
    S=8 and are mutually bit-stable.

    Multi-process pod clouds (ISSUE 18) run the SAME deterministic "mesh"
    path over the global mesh: the canonical row layout (_fit's pod branch)
    keeps all real rows contiguous in global ingest order with the pad at
    the tail, so the S ordered block partials are the same sums a 1-device
    forced-shard fit computes and an N-process fit is bit-identical to it.
    Lossguide growth keeps the pre-ISSUE-12 shard_map + psum path
    ("mesh_psum") — on pods too. H2O3_TREE_SHARD=0 demotes a
    pod to mesh_psum rather than "off" (the data lives on other processes,
    so "train on one device" is not available there)."""
    import math

    env = os.environ.get("H2O3_TREE_SHARD", "").strip()
    lossguide = tp.get("grow_policy", "depthwise") == "lossguide"
    if multiproc:
        if env == "0" or lossguide:
            return ("mesh_psum" if ndev > 1 else "off"), 0
    elif env == "0":
        return "off", 0
    elif lossguide:
        return ("mesh_psum" if ndev > 1 else "off"), 0
    base = max(int(os.environ.get("H2O3_TREE_SHARD_BLOCKS", "8") or 8), 1)
    if ndev > 1:
        return "mesh", base * ndev // math.gcd(base, ndev)
    if env == "1":
        return "blocks", base
    return "off", 0


def _bucket_rows(npad: int) -> int:
    """Round a padded row count up to {1, 1.125, 1.25, ..., 2}·2^k so
    near-same-size datasets share compiled programs (≤12.5% pad overhead).
    Small shapes stay exact — their compiles are cheap and padding is not.
    H2O3_BUCKET_ROWS=0 disables (exact shapes; used by determinism tests
    to show padded-shape invariance of the trained model)."""
    if npad <= 8192 or os.environ.get("H2O3_BUCKET_ROWS", "1") == "0":
        return npad
    p = 1 << (npad.bit_length() - 1)
    for eighths in range(8, 17):
        cand = p * eighths // 8
        if cand >= npad:
            return cand
    return 2 * p


@jax.jit
def _sum_args(*xs):
    return sum(xs[1:], xs[0])


@jax.jit
def _copy_args(*xs):
    """Device copies (compact-cap chunk snapshot) — module-level so every
    chunk after the first is a jit dispatch-cache hit."""
    return tuple(x + 0 for x in xs)


def _tree_step_fns(cfg: _StepCfg, cloud):
    """(tree_jit, single_jit) for one step configuration, cached ON the
    cloud instance (keyed by cfg) so a mesh re-init naturally drops stale
    shard_map closures. LRU-bounded: evicting releases the jitted
    executables, so long-running servers sweeping many structural configs
    (depths/shapes) don't accumulate programs forever."""
    from collections import OrderedDict

    with _STEP_FNS_LOCK:
        cache = cloud.__dict__.setdefault("_step_fns_cache", OrderedDict())
        fns = cache.get(cfg)
        if fns is None:
            fns = _build_tree_step_fns(cfg, cloud)
            cache[cfg] = fns
            while len(cache) > _STEP_FNS_CAP:
                cache.popitem(last=False)
        else:
            cache.move_to_end(cfg)
        return fns


def _build_tree_step_fns(cfg: _StepCfg, cloud):
    """Construct (tree_jit, single_jit) for one step configuration.

    All data (including the monotone-constraint vector) arrives as
    ARGUMENTS — a closure-captured device array would be embedded in the
    HLO as a literal, defeating the persistent compilation cache and
    bloating programs. The code argument `codes_a` has the form
    `cfg.code_operand` names: the resident codes, or the pair of them and
    the fit's histogram-kernel operand, which the program then reads
    instead of widening the codes itself."""
    npad, K, F = cfg.npad, cfg.K, cfg.F

    def _grads(margins, y_d, k):
        if cfg.mode == "drf":
            return -y_d[:, k], jnp.ones_like(y_d[:, k])
        if cfg.problem == "multinomial":
            p = jax.nn.softmax(margins, axis=1)
            return p[:, k] - y_d[:, k], p[:, k] * (1 - p[:, k])
        return dist_mod.grad_hess(
            cfg.dist, margins[:, 0], y_d[:, 0],
            tweedie_power=cfg.tweedie_power, alpha=cfg.quantile_alpha,
        )

    def _build_one(codes, g, h, w, fm, edges, mono, hp, key):
        operand = None
        if cfg.code_operand == "fit":
            codes, operand = codes
        if cfg.grow_policy == "lossguide":
            lg_kwargs = dict(max_depth=cfg.max_depth, nbins=cfg.nbins,
                             max_leaves=cfg.max_leaves,
                             hist_method=cfg.hist_method)
            # consult the shard PLAN, not just the cloud size: under the
            # H2O3_TREE_SHARD=0 escape hatch the data is unsharded and
            # padded for one device — running collectives anyway would
            # defeat the hatch (and reject non-dividing npads)
            if cloud.size > 1 and cfg.shard_mode == "mesh_psum":
                rspec = P(cloudlib.ROWS_AXIS)

                def inner_lg(codes, g, h, w, fm, edges, mono, hp, key):
                    return treelib.build_tree_lossguide(
                        codes, g, h, w, fm, edges,
                        min_rows=hp[0], min_split_improvement=hp[1],
                        reg_lambda=hp[2], reg_alpha=hp[3],
                        max_abs_leaf=hp[7],
                        axis_name=cloudlib.ROWS_AXIS, **lg_kwargs,
                    )

                fn = cloudlib.shard_call(
                    inner_lg, cloud,
                    in_specs=(rspec, rspec, rspec, rspec, P(), P(), P(),
                              P(), P()),
                    out_specs=(
                        treelib.Tree(P(), P(), P(), P(), P()), rspec,
                        P(), P(),
                    ),
                    # the outputs ARE replicated (psum'd values re-enter
                    # the fori_loop frontier carry); the static check
                    # stays off
                    check_vma=False,
                )
                return fn(codes, g, h, w, fm, edges, mono, hp, key)
            return treelib.build_tree_lossguide(
                codes, g, h, w, fm, edges,
                min_rows=hp[0], min_split_improvement=hp[1],
                reg_lambda=hp[2], reg_alpha=hp[3], max_abs_leaf=hp[7],
                **lg_kwargs)
        kwargs = dict(max_depth=cfg.max_depth, nbins=cfg.nbins,
                      hist_method=cfg.hist_method,
                      compact_cap=cfg.compact_cap,
                      pack_bits=cfg.pack_bits)
        use_mesh = cloud.size > 1 and cfg.shard_mode in ("mesh", "mesh_psum")
        if use_mesh or cfg.shard_mode == "blocks":
            # ISSUE 12: the sharded tree step. ONE inner function serves
            # both lanes (the t5x-style fall-through contract, SNIPPETS.md
            # [1] via mesh.shard_call): on the mesh it runs under shard_map
            # with rows sharded and S/ndev local blocks per device; on one
            # device ("blocks") the identical body runs under plain jit
            # with all S blocks local — bit-identical by the ordered-fold
            # construction in ops/histogram.
            local_blocks = (cfg.n_shards // cloud.size
                            if cfg.shard_mode == "mesh" else
                            cfg.n_shards if cfg.shard_mode == "blocks"
                            else 0)
            axis = cloudlib.ROWS_AXIS if use_mesh else None
            rspec = P(cloudlib.ROWS_AXIS)

            def inner(codes, g, h, w, fm, edges, mono, hp, key):
                kw = dict(kwargs)
                if cfg.has_monotone:
                    kw["monotone"] = mono
                if cfg.has_mtries:
                    kw["mtries_rate"] = hp[8]
                return treelib.build_tree(
                    codes, g, h, w, fm, edges, key=key,
                    min_rows=hp[0], min_split_improvement=hp[1],
                    reg_lambda=hp[2], reg_alpha=hp[3], max_abs_leaf=hp[7],
                    axis_name=axis, n_shard_blocks=local_blocks, **kw,
                )

            out_specs = (
                treelib.Tree(P(), P(), P(), P(), P()), rspec, P(), P(),
            )
            if cfg.compact_cap:
                # overflow flag: derived from the merged histograms, so it
                # is identical (replicated) on every shard
                out_specs = out_specs + (P(),)
            fn = cloudlib.shard_call(
                inner, cloud,
                in_specs=(rspec, rspec, rspec, rspec, P(), P(), P(), P(),
                          P()),
                out_specs=out_specs,
                # the deterministic merge replicates via all_gather + fold,
                # which shard_map cannot statically infer. The outputs ARE
                # replicated on every path; the static check stays off.
                check_vma=False,
            )
            return fn(codes, g, h, w, fm, edges, mono, hp, key)
        if cfg.has_monotone:
            kwargs["monotone"] = mono
        if cfg.has_mtries:
            kwargs["mtries_rate"] = hp[8]
        return treelib.build_tree(
            codes, g, h, w, fm, edges, key=key, max_abs_leaf=hp[7],
            min_rows=hp[0], min_split_improvement=hp[1],
            reg_lambda=hp[2], reg_alpha=hp[3], operand=operand, **kwargs)

    def _one_tree(margins, codes_a, y_a, w_a, rate_a, edges_a, mono, hp,
                  key, m, g_ext=None, h_ext=None):
        """Build the K trees of boosting iteration m (traced int)."""
        krow, kcol, ktree = jax.random.split(jax.random.fold_in(key, 0), 3)
        # rate_a is per-row: constant sample_rate, or per-class rates when
        # sample_rate_per_class is set. With no sampling at all the
        # per-tree npad-point RNG draw is skipped entirely (static flag).
        if cfg.no_row_sampling:
            row_mask = jnp.ones(npad, jnp.float32)
            wt = w_a
        else:
            row_mask = (
                jax.random.uniform(krow, (npad,)) < rate_a
            ).astype(jnp.float32)
            wt = w_a * row_mask
        if cfg.has_col_sampling:
            fm = (jax.random.uniform(kcol, (F,)) < hp[6]).astype(jnp.float32)
            fm = fm.at[0].set(jnp.maximum(fm[0], 1 - fm.sum().clip(0, 1)))
        else:
            fm = jnp.ones(F, jnp.float32)
        scale = (hp[4] * jnp.power(hp[5], m.astype(jnp.float32))
                 ).astype(jnp.float32)
        trs, covs, gains_acc = [], [], jnp.zeros(F, jnp.float32)
        oob_inc = None
        ov_sum = jnp.int32(0)
        for k in range(K):
            ktree = jax.random.fold_in(ktree, k)
            if g_ext is not None:
                g, h = g_ext, h_ext
            else:
                g, h = _grads(margins, y_a, k)
            if cfg.compact_cap:
                tr, leaf_idx, gains, cover, ov = _build_one(
                    codes_a, g, h, wt, fm, edges_a, mono, hp, ktree)
                ov_sum = ov_sum + ov
            else:
                tr, leaf_idx, gains, cover = _build_one(
                    codes_a, g, h, wt, fm, edges_a, mono, hp, ktree)
            tr = tr._replace(value=tr.value * scale)
            # margins track Σ tree outputs for ALL modes: GBM boosting
            # margins, or DRF leaf-mean sums (÷ntrees at scoring time)
            leaf_vals = treelib.value_at(tr.value, leaf_idx)
            margins = margins.at[:, k].add(leaf_vals)
            if cfg.mode == "drf":
                # out-of-bag contribution (DRF OOB scoring): rows NOT
                # sampled into this tree accumulate its prediction
                col = leaf_vals * (1.0 - row_mask)
                oob_inc = col[:, None] if oob_inc is None else jnp.concatenate(
                    [oob_inc, col[:, None]], axis=1)
            trs.append(tr)
            covs.append(cover)
            gains_acc = gains_acc + gains
        stacked = treelib.Tree(
            *[jnp.stack([getattr(t, f) for t in trs]) for f in treelib.Tree._fields]
        )
        covers = jnp.stack(covs)                      # (K, T)
        return (margins, stacked, covers, gains_acc, oob_inc,
                (1.0 - row_mask), ov_sum)

    def _pack(stacked, covers):
        """Tree fields + covers → one f32 array (…, T, 6): a single D2H
        transfer moves a whole chunk of trees (each sync transfer through
        the host↔device link pays seconds of fixed latency)."""
        return jnp.stack(
            [stacked.feat.astype(jnp.float32),
             stacked.bin.astype(jnp.float32),
             stacked.thr,
             stacked.is_split.astype(jnp.float32),
             stacked.value,
             covers],
            axis=-1,
        )

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def tree_jit(margins, oob_sum, oob_cnt, codes_a, y_a, w_a, rate_a,
                 edges_a, mono, hp, key, m):
        margins, stacked, covers, gains, oob_inc, oob_mask, ov = _one_tree(
            margins, codes_a, y_a, w_a, rate_a, edges_a, mono, hp,
            jax.random.fold_in(key, m), m
        )
        if oob_inc is not None:
            oob_sum = oob_sum + oob_inc
            oob_cnt = oob_cnt + oob_mask
        return margins, oob_sum, oob_cnt, _pack(stacked, covers), gains, ov

    @functools.partial(jax.jit, donate_argnums=(0,))
    def single_tree_jit(margins, codes_a, y_a, w_a, rate_a, edges_a, mono,
                        hp, key, m, g_ext, h_ext):
        """One tree from gradients the caller computed (a custom
        objective's round): the trace calls it `jit_single_tree_jit`."""
        margins, stacked, covers, gains, _, _, _ = _one_tree(
            margins, codes_a, y_a, w_a, rate_a, edges_a, mono, hp,
            jax.random.fold_in(key, m), m, g_ext, h_ext)
        return margins, _pack(stacked, covers), gains

    return tree_jit, single_tree_jit


_DEV_PACKS: List = []  # weakrefs of models holding HBM forest packs (FIFO)


def pack_nbytes(pd) -> int:
    """HBM footprint of a packed forest — the ONE sizing rule shared by
    eviction and DKV accounting."""
    return int(np.prod(pd.shape)) * getattr(pd.dtype, "itemsize", 4)


def _register_dev_pack(model, budget: int) -> None:
    """Track device-resident forests; past `budget` total bytes, evict the
    OLDEST packs to host so long grid/AutoML runs on small-HBM devices
    cannot accumulate forests until allocation fails. The newest pack is
    never evicted (it is the model being trained)."""
    import weakref

    with _DEV_PACKS_LOCK:
        _DEV_PACKS.append(weakref.ref(model))
        live, total = [], 0
        for r in _DEV_PACKS:
            m = r()
            if m is not None and m.__dict__.get("_packed_dev") is not None:
                live.append(r)
                total += pack_nbytes(m._packed_dev)
        drop = 0
        while total > budget and drop < len(live) - 1:
            m = live[drop]()
            if m is not None:
                total -= pack_nbytes(m._packed_dev)
                m.release_device_forest()
            drop += 1
        _DEV_PACKS[:] = live[drop:]


class SharedTreeModel(H2OModel):
    algo = "sharedtree"

    def __init__(self, params, x, y, bm: BinnedMatrix, problem, nclass, domain,
                 distribution, f0, forest, max_depth, mode="gbm",
                 packed_dev=None, nclasses_packed=1):
        # report the concrete builder's algo (gbm/drf/...), not the shared base
        self.algo = getattr(params, "algo", self.algo)
        super().__init__(params)
        self.x = list(x)
        self.y = y
        self.bm = bm
        self.problem = problem
        self.nclass = nclass
        self.domain = domain
        self.distribution = distribution
        self.f0 = f0              # scalar or (K,) initial margin
        # device-resident pack: (ntrees, K, T, 6) in HBM. Deep heaps are
        # 12.6 MB/tree and every byte crosses the host↔device link, so the
        # host copy (mojo/save/tree-API consumers) is materialized LAZILY;
        # scoring slices the pack on device and never pays the transfer.
        self._packed_dev = packed_dev
        self._K_packed = nclasses_packed
        self.forest = forest      # list over classes of stacked Tree arrays
        self.max_depth = max_depth
        self.mode = mode          # 'gbm' (summed margins) | 'drf' (averaged leaves)
        if packed_dev is not None:
            self.ntrees_built = int(packed_dev.shape[0])
        else:
            self.ntrees_built = int(forest[0].feat.shape[0]) if forest else 0
        if packed_dev is None:
            self.covers = None    # list over classes of (ntrees, T) — TreeSHAP

    @property
    def forest(self):
        if self._forest is None and self._packed_dev is not None:
            self._materialize_host_forest()
        return self._forest

    @forest.setter
    def forest(self, v):
        self._forest = v
        self.__dict__.pop("_padded_forests", None)
        self.__dict__.pop("_score_tables", None)

    @property
    def covers(self):
        if self.__dict__.get("_covers") is None and self._packed_dev is not None:
            self._materialize_host_forest()
        return self.__dict__.get("_covers")

    @covers.setter
    def covers(self, v):
        self._covers = v

    def release_device_forest(self):
        """Materialize the host copy and free the HBM pack (eviction)."""
        if self.__dict__.get("_packed_dev") is not None:
            self._materialize_host_forest()
            self._packed_dev = None
            self.__dict__.pop("_padded_forests", None)
            self.__dict__.pop("_score_tables", None)

    def _materialize_host_forest(self):
        """The deferred forest D2H: one bulk transfer, then host slicing."""
        ap = np.asarray(self._packed_dev)
        forest, covers = [], []
        for k in range(self._K_packed):
            forest.append(treelib.Tree(
                np.ascontiguousarray(ap[:, k, :, 0]).astype(np.int32),
                np.ascontiguousarray(ap[:, k, :, 1]).astype(np.int32),
                np.ascontiguousarray(ap[:, k, :, 2]),
                ap[:, k, :, 3] > 0.5,
                np.ascontiguousarray(ap[:, k, :, 4]),
            ))
            covers.append(np.ascontiguousarray(ap[:, k, :, 5]))
        self._forest = forest
        self._covers = covers

    def summary(self):
        """ModelSummary of SharedTreeModel: tree count + depth/leaf stats."""
        s = super().summary()
        depths, leaves = [], []
        if self._forest is None and self._packed_dev is not None:
            # device reduction — stats without materializing the host forest
            issp = self._packed_dev[..., 3] > 0.5          # (nt, K, T)
            T = issp.shape[2]
            nd = jnp.floor(jnp.log2(jnp.arange(1, T + 1, dtype=jnp.float32)))
            d_tk = jnp.max(jnp.where(issp, nd[None, None, :] + 1, 0.0),
                           axis=2)                          # (nt, K)
            l_tk = issp.sum(axis=2) + 1
            depths = [int(v) for v in np.asarray(d_tk).ravel()]
            leaves = [int(v) for v in np.asarray(l_tk).ravel()]
        else:
            for stacked in self.forest:
                issp = np.asarray(stacked.is_split)
                node_depth = np.floor(np.log2(np.arange(1, issp.shape[1] + 1)))
                for t in range(issp.shape[0]):
                    d = node_depth[issp[t]].max() + 1 if issp[t].any() else 0
                    depths.append(int(d))
                    leaves.append(int(issp[t].sum() + 1))
        s.update(number_of_trees=self.ntrees_built,
                 min_depth=int(min(depths, default=0)),
                 max_depth=int(max(depths, default=0)),
                 mean_leaves=float(np.mean(leaves)) if leaves else 0.0)
        req = getattr(self, "requested_max_depth", self.max_depth)
        if req != self.max_depth:
            # the HBM-feasibility clamp reduced the user's max_depth — make
            # that visible in the model summary, not just a log line
            s.update(requested_max_depth=int(req),
                     max_depth_clamped_to=int(self.max_depth))
        return s

    def _matrix(self, frame: Frame) -> np.ndarray:
        X, _, _ = frame_to_matrix(frame, self.x, expected_domains=self.bm.domains)
        return X

    def _padded_forest(self, k: int):
        """Class-k forest with ntrees padded to the next power of two
        (zero-valued unsplit trees add 0 to the margin), cached on the
        model: models differing only in tree count share one compiled
        scoring program — AutoML/SE score many models per run — and
        repeated scoring reuses the same backing arrays."""
        cache = self.__dict__.setdefault("_padded_forests", {})
        if k not in cache:
            if self._forest is None and self._packed_dev is not None:
                # slice the device pack in HBM — scoring never pulls the
                # forest to host
                ap = self._packed_dev
                nt = int(ap.shape[0])
                bucket = 1 << (nt - 1).bit_length() if nt else 0
                sl = ap[:, k]                              # (nt, T, 6)
                if bucket != nt:
                    sl = jnp.concatenate(
                        [sl, jnp.zeros((bucket - nt,) + sl.shape[1:],
                                       sl.dtype)], axis=0)
                cache[k] = treelib.Tree(
                    sl[..., 0].astype(jnp.int32), sl[..., 1].astype(jnp.int32),
                    sl[..., 2], sl[..., 3] > 0.5, sl[..., 4])
                return cache[k]
            stacked = self.forest[k]
            nt = int(np.asarray(stacked.feat).shape[0])
            bucket = 1 << (nt - 1).bit_length() if nt else 0
            if bucket != nt:
                padn = bucket - nt
                stacked = treelib.Tree(*[
                    np.concatenate([np.asarray(f), np.zeros(
                        (padn,) + np.asarray(f).shape[1:],
                        np.asarray(f).dtype)], axis=0)
                    for f in stacked
                ])
            cache[k] = stacked
        return cache[k]

    @property
    def _n_class_forests(self) -> int:
        if self._forest is None and self._packed_dev is not None:
            return self._K_packed
        return len(self.forest)

    def _score_table(self, k: int):
        """Fused-scorer pack for class-k forest (treelib.build_score_table),
        cached beside `_padded_forests` — scoring fresh frames is the hot
        path for model_performance / AutoML leaderboard_frame / REST
        Predictions, and the pack build (~150 ms) amortizes across them."""
        cache = self.__dict__.setdefault("_score_tables", {})
        if k not in cache:
            cache[k] = treelib.build_score_table_jit(
                self._padded_forest(k), max_depth=self.max_depth)
            # the padded Tree slices are dead weight once the score pack
            # exists (fused is the default path); drop them so deep-forest
            # HBM peaks don't stack pack + padded forest + score table.
            # `_padded_forest` rebuilds on demand for the walk fallback /
            # tree-API consumers.
            self.__dict__.get("_padded_forests", {}).pop(k, None)
        return cache[k]

    # margin(s) on raw feature matrix
    def _margins(self, X: np.ndarray) -> np.ndarray:
        n = X.shape[0]
        fused = os.environ.get("H2O3_FOREST_SCORER", "fused") != "walk"
        K = self._n_class_forests
        tables = [self._score_table(k) if fused else self._padded_forest(k)
                  for k in range(K)]
        # row-bucket the jitted scorer's input: nearby frame sizes (CV
        # folds of 2667 vs 2666 rows) land on ONE compiled program instead
        # of recompiling per exact row count. Frames past one page are
        # scored page by page through that one program: the fused walk
        # gathers a (trees, rows, 128) f32 record block per round, which at
        # 1M rows × 100 trees is 51 GB — the chip's compiler refuses it
        # (16 GB HBM). Zero-filled pad rows walk the trees harmlessly and
        # are sliced off below.
        nt = max(int(t[0].shape[0]) for t in tables)
        page = _score_page_rows(nt)
        npad = cloudlib.pad_to_multiple(n, SCORE_ROW_BUCKET if n <= page
                                        else page)
        X = np.asarray(X, np.float32)
        if npad != n:
            X = np.concatenate([X, np.zeros((npad - n, X.shape[1]),
                                            np.float32)])
        page = min(page, npad)
        sums = [[] for _ in range(K)]
        for r0 in range(0, npad, page):
            Xj = jnp.asarray(X[r0:r0 + page])
            for k, tab in enumerate(tables):
                if fused:
                    s = treelib.predict_forest_fused(tab[0], tab[1], Xj,
                                                     self.max_depth)
                else:
                    s = treelib.predict_forest_raw(tab, Xj, self.max_depth)
                sums[k].append(s)
                if len(sums[k]) > 2:
                    # at most two pages in flight: each holds its gather
                    # transients on the device until it has run
                    sums[k][-3] = np.asarray(sums[k][-3])
        outs = []
        for k in range(K):
            f0k = self.f0 if np.ndim(self.f0) == 0 else self.f0[k]
            s = np.concatenate([np.asarray(p) for p in sums[k]])
            outs.append(s.astype(np.float64)[:n] + f0k)
        return np.column_stack(outs)

    def _margins_codes(self, codes: np.ndarray) -> np.ndarray:
        """Forest margins on PRE-BINNED codes (the CV fold-reuse holdout
        path): rows of the parent's `BinnedMatrix` score through
        `predict_codes` directly — no raw-matrix rebuild, no re-bin. Rows
        are bucketed like `_margins` so CV folds share compiled scorers;
        zero pad codes walk the trees harmlessly and are sliced off."""
        n = codes.shape[0]
        npad = cloudlib.pad_to_multiple(n, SCORE_ROW_BUCKET)
        if npad != n:
            codes = np.concatenate(
                [codes, np.zeros((npad - n, codes.shape[1]), codes.dtype)])
        cj = jnp.asarray(codes)
        outs = []
        for k in range(self._n_class_forests):
            stacked = jax.tree.map(jnp.asarray, self._padded_forest(k))
            s = _predict_forest_codes_jit(stacked, cj, self.max_depth)
            f0k = self.f0 if np.ndim(self.f0) == 0 else self.f0[k]
            outs.append(np.asarray(s, np.float64)[:n] + f0k)
        return np.column_stack(outs)

    def _probs_from_codes(self, codes: np.ndarray) -> np.ndarray:
        return self._finish_probs(self._margins_codes(codes))

    def _score_probs(self, X: np.ndarray, offset: Optional[np.ndarray] = None) -> np.ndarray:
        return self._finish_probs(self._margins(X), offset)

    def _finish_probs(self, m: np.ndarray,
                      offset: Optional[np.ndarray] = None) -> np.ndarray:
        if offset is not None and self.mode != "drf":
            m = m + offset[:, None]
        out = probs_from_margins(self.mode, self.problem, self.distribution,
                                 m, self.ntrees_built)
        dists = getattr(self, "balance_dists", None)
        if dists is not None and self.problem in ("binomial", "multinomial"):
            # hex.Model correctProbabilities: rescale balanced-trained
            # probabilities back to the prior class distribution
            prior, modeld = dists
            if self.problem == "binomial" and len(prior) == 2:
                ratio = np.asarray(prior) / np.maximum(np.asarray(modeld), 1e-12)
                out = out * ratio[None, :]
            else:
                out = out * (np.asarray(prior) / np.maximum(np.asarray(modeld), 1e-12))[None, :]
            out = out / np.maximum(out.sum(axis=1, keepdims=True), 1e-12)
        return out

    def _offset_of(self, frame: Frame) -> Optional[np.ndarray]:
        oc = self.parms._parms.get("offset_column") if hasattr(self.parms, "_parms") else None
        if oc and oc in frame.names:
            return frame.vec(oc).numeric_np()
        return None

    def predict(self, test_data: Frame) -> Frame:
        out = self._score_probs(self._matrix(test_data), self._offset_of(test_data))
        if self.problem in ("binomial", "multinomial"):
            lab = out.argmax(axis=1)
            d = {"predict": np.asarray(self.domain, dtype=object)[lab]}
            for i, cls in enumerate(self.domain):
                d[str(cls)] = out[:, i]
            cal = getattr(self, "calibrator", None)
            if cal is not None and self.problem == "binomial":
                # calibrate_model: appended cal_ columns (hex/tree
                # CalibrationHelper — Platt scaling / isotonic)
                p1 = cal(out[:, 1])
                d[f"cal_{self.domain[0]}"] = 1.0 - p1
                d[f"cal_{self.domain[1]}"] = p1
            fr = Frame.from_dict(d, column_types={"predict": "enum"})
            return fr
        return Frame.from_dict({"predict": out[:, 0]})

    def _make_metrics(self, frame: Frame):
        out = self._score_probs(self._matrix(frame), self._offset_of(frame))
        return _metrics_for(self.problem, frame.vec(self.y), out)

    def predict_contributions(self, test_data: Frame, output_format="Original",
                              top_n=0, bottom_n=0, compare_abs=False) -> Frame:
        """Per-row SHAP feature contributions + BiasTerm (path-dependent
        TreeSHAP — hex/genmodel TreeSHAP.java via Model.scoreContributions).
        Contributions are in the margin space (log-odds for GBM binomial,
        response for regression, probability for DRF binomial) and sum with
        BiasTerm to the raw prediction. Binomial/regression only, as in the
        reference."""
        if self.problem == "multinomial":
            raise ValueError(
                "predict_contributions is not supported for multinomial "
                "models (reference parity: hex/Model.scoreContributions)")
        if output_format not in ("Original", "Compact", "original", "compact"):
            raise ValueError("output_format must be 'Original' or 'Compact' "
                             "(they coincide here: enums stay integer-coded, "
                             "one column per input feature)")
        oc = (self.parms._parms.get("offset_column")
              if hasattr(self.parms, "_parms") else None)
        if oc:
            raise ValueError(
                "predict_contributions is not supported for models trained "
                "with an offset_column (reference parity)")
        covers = getattr(self, "covers", None)
        if not covers:
            raise ValueError(
                "this model has no recorded node covers "
                "(trained before TreeSHAP support); retrain to enable "
                "predict_contributions")
        from .tree_shap import compute_contributions

        X = self._matrix(test_data)
        scale = 1.0 / max(self.ntrees_built, 1) if self.mode == "drf" else 1.0
        stacked = self.forest[0]
        f0k = self.f0 if np.ndim(self.f0) == 0 else self.f0[0]
        contrib = compute_contributions(
            stacked.feat, stacked.thr, stacked.is_split, stacked.value,
            covers[0], X, scale, f0k)
        names = list(self.x) + ["BiasTerm"]
        if top_n or bottom_n:
            # top/bottom-N pairs per row: (feature, value) columns, ranked by
            # signed value (or |value| with compare_abs), BiasTerm excluded
            vals = contrib[:, :-1]
            keys = np.abs(vals) if compare_abs else vals
            order = np.argsort(-keys, axis=1, kind="stable")
            d = {}
            fn_arr = np.asarray(self.x, dtype=object)
            nf = len(self.x)
            tn = nf if top_n < 0 else min(top_n, nf)
            bn = nf if bottom_n < 0 else min(bottom_n, nf)
            for i in range(tn):
                sel = order[:, i]
                d[f"top_feature_{i + 1}"] = fn_arr[sel]
                d[f"top_value_{i + 1}"] = np.take_along_axis(
                    vals, sel[:, None], axis=1)[:, 0]
            for i in range(bn):
                sel = order[:, nf - 1 - i]
                d[f"bottom_feature_{i + 1}"] = fn_arr[sel]
                d[f"bottom_value_{i + 1}"] = np.take_along_axis(
                    vals, sel[:, None], axis=1)[:, 0]
            d["BiasTerm"] = contrib[:, -1]
            return Frame.from_dict(d)
        return Frame.from_dict({n2: contrib[:, j] for j, n2 in enumerate(names)})

    def staged_predict_proba(self, test_data: Frame) -> Frame:
        """Class-1 probability after each successive tree (binomial GBM) —
        `h2o-py ModelBase.staged_predict_proba` (hex/tree staged scoring)."""
        if self.problem != "binomial" or self.mode == "drf":
            raise ValueError("staged_predict_proba supports binomial "
                             "boosting models only (reference parity)")
        oc = (self.parms._parms.get("offset_column")
              if hasattr(self.parms, "_parms") else None)
        if oc or getattr(self, "balance_dists", None) is not None:
            raise ValueError(
                "staged_predict_proba is not supported for models trained "
                "with offset_column or balance_classes (staged margins "
                "would disagree with predict())")
        X = jnp.asarray(self._matrix(test_data), jnp.float32)
        stacked = self.forest[0]
        per_tree = np.asarray(jax.vmap(
            lambda t: treelib.predict_raw(t, X, self.max_depth)
        )(jax.tree.map(jnp.asarray, stacked)))            # (ntrees, N)
        f0k = self.f0 if np.ndim(self.f0) == 0 else self.f0[0]
        margins = f0k + np.cumsum(per_tree, axis=0)
        probs = 1.0 / (1.0 + np.exp(-margins))
        return Frame.from_dict(
            {f"T{t + 1}": probs[t] for t in range(probs.shape[0])})

    @staticmethod
    def _route_rows(feat_t, thr_t, issp_t, X, max_depth, visit=None):
        """Route all rows of X root→leaf through one heap tree (NaN and
        x > thr go right — the single NA-routing rule shared by scoring,
        leaf assignment and feature frequencies). `visit(split_mask,
        split_feature_per_row, goes_right)` is called once per level;
        returns the final heap node per row."""
        N = X.shape[0]
        node = np.zeros(N, np.int64)
        for _ in range(max_depth):
            s = issp_t[node]
            if not s.any():
                break
            f = feat_t[node]
            xv = X[np.arange(N), f]
            right = (np.isnan(xv) | (xv > thr_t[node])) & s
            if visit is not None:
                visit(s, f, right)
            node = np.where(s, 2 * node + 1 + right.astype(np.int64), node)
        return node

    def feature_frequencies(self, test_data: Frame) -> Frame:
        """Per row, how many times each feature decides the row's path,
        summed over all trees — `h2o-py ModelBase.feature_frequencies`
        (hex/tree/SharedTreeModel feature frequencies)."""
        X = self._matrix(test_data)
        N = X.shape[0]
        counts = np.zeros((N, len(self.x)), np.int64)

        def visit(s, f, right):
            np.add.at(counts, (np.nonzero(s)[0], f[s]), 1)

        for stacked in self.forest:
            feat = np.asarray(stacked.feat)
            thr = np.asarray(stacked.thr)
            issp = np.asarray(stacked.is_split)
            for t in range(self.ntrees_built):
                self._route_rows(feat[t], thr[t], issp[t], X,
                                 self.max_depth, visit)
        return Frame.from_dict(
            {n2: counts[:, j].astype(np.float64)
             for j, n2 in enumerate(self.x)})

    def predict_leaf_node_assignment(self, test_data: Frame,
                                     type: str = "Path") -> Frame:
        """Leaf assignment per (tree, class): decision-path strings ("LRL…")
        or heap node ids — `Model.scoreLeafNodeAssignment`
        (hex/tree/SharedTreeModel leaf_node_assignment)."""
        if type not in ("Path", "Node_ID"):
            raise ValueError("type must be 'Path' or 'Node_ID'")
        X = self._matrix(test_data)
        N = X.shape[0]
        d = {}
        ctypes_ = {}
        for k, stacked in enumerate(self.forest):
            feat = np.asarray(stacked.feat)
            thr = np.asarray(stacked.thr)
            issp = np.asarray(stacked.is_split)
            for t in range(self.ntrees_built):
                paths = [np.full(N, "", dtype=f"<U{self.max_depth}")]

                def visit(s, right, _p=paths):
                    step = np.where(s, np.where(right, "R", "L"), "")
                    _p[0] = np.char.add(_p[0], step)

                node = self._route_rows(
                    feat[t], thr[t], issp[t], X, self.max_depth,
                    (lambda s, f, right: visit(s, right))
                    if type == "Path" else None)
                col = (f"T{t + 1}.C{k + 1}")
                if type == "Path":
                    d[col] = paths[0].astype(object)
                    ctypes_[col] = "enum"
                else:
                    d[col] = node.astype(np.float64)
        return Frame.from_dict(d, column_types=ctypes_ or None)


class H2OSharedTreeEstimator(H2OEstimator):
    """Common GBM/DRF/IF driver. Subclasses set `_mode` ('gbm'|'drf')."""

    _mode = "gbm"

    def _tree_params(self) -> Dict:
        p = self._parms
        return dict(
            ntrees=int(p.get("ntrees", 50)),
            max_depth=int(p.get("max_depth", 5 if self._mode == "gbm" else 20)),
            min_rows=float(p.get("min_rows", 10.0 if self._mode == "gbm" else 1.0)),
            nbins=int(p.get("nbins", 20)),
            learn_rate=float(p.get("learn_rate", 0.1)),
            learn_rate_annealing=float(p.get("learn_rate_annealing", 1.0)),
            sample_rate=float(p.get("sample_rate", 1.0 if self._mode == "gbm" else 0.632)),
            col_sample_rate=float(p.get("col_sample_rate", 1.0)),
            col_sample_rate_per_tree=float(p.get("col_sample_rate_per_tree", 1.0)),
            min_split_improvement=float(p.get("min_split_improvement", 1e-5)),
            histogram_type=p.get("histogram_type", "AUTO"),
            hist_method=p.get("hist_method", "auto"),
            mtries=int(p.get("mtries", -1)) if "mtries" in p else 0,
            reg_lambda=float(p.get("reg_lambda"))
            if p.get("reg_lambda") is not None
            else (0.0 if self._mode == "drf" else 1.0),
            reg_alpha=float(p.get("reg_alpha") or 0.0) if "reg_alpha" in p else 0.0,
            max_abs_leaf=float(p.get("max_abs_leafnode_pred") or np.inf)
            if "max_abs_leafnode_pred" in p else np.inf,
            # gradient-based sampling (ISSUE 14, GOSS-shaped): opt-in, only
            # meaningful on the out-of-core streamed path — later trees
            # stream the top-|g| rows plus an amplified random rest
            goss=bool(p.get("goss", False)),
            # `is None` (not `or`): an explicit 0.0 must reach the
            # validator's 0 < rate < 1 check, not be swapped for the default
            goss_top_rate=float(
                0.2 if p.get("goss_top_rate") is None
                else p["goss_top_rate"]),
            goss_other_rate=float(
                0.1 if p.get("goss_other_rate") is None
                else p["goss_other_rate"]),
            goss_start_tree=p.get("goss_start_tree"),
        )

    def _resolved_mtries(self, tp, F, problem) -> int:
        """DRF's per-split column-sample count (hex/tree/drf/DRF.java
        _mtry defaults); 0 for non-DRF modes."""
        if self._mode != "drf":
            return 0
        mtries = tp["mtries"]
        if mtries in (-1, 0):
            return (max(1, int(np.sqrt(F))) if problem != "regression"
                    else max(1, F // 3))
        if mtries == -2:
            return F
        if mtries > F:
            raise ValueError(
                f"mtries={mtries} exceeds the {F} usable feature columns")
        return mtries

    def _make_step_cfg(self, tp, npad, K, F, nbins, problem, dist,
                       pack_bits: int = 0, shard_mode: str = "off",
                       n_shards: int = 0) -> _StepCfg:
        """The structural step config, derivable before any device upload —
        built identically by the early warm-up thread and the training path
        so both hit the same cached program. `pack_bits` is the resident
        code packing the caller resolved (0 = full-width);
        `shard_mode`/`n_shards` come from `_shard_plan`. `hist_method` is
        what the estimator (or H2O3_HIST_METHOD) named, a structural cfg
        field → program-cache key, so an in-process flip retraces instead
        of being silently frozen into a cached program;
        `ops.histogram.resolve_method` turns `auto` into a kernel.
        `code_operand` follows from the rest (`_cfg_operand_form`)."""
        mtries = self._resolved_mtries(tp, F, problem)
        colp = tp["col_sample_rate"] * tp["col_sample_rate_per_tree"]
        hist_method = os.environ.get(
            "H2O3_HIST_METHOD", tp.get("hist_method", "auto"))
        # an unknown name is refused here, before the warm-up thread or
        # the fit traces anything
        _resolve_method(1, nbins, hist_method)
        cfg = _StepCfg(
            npad=npad, K=K, F=F, nbins=nbins, problem=problem, dist=dist,
            mode=self._mode, max_depth=tp["max_depth"],
            has_mtries=mtries > 0,
            no_row_sampling=(tp["sample_rate"] >= 1.0
                             and not self._parms.get("sample_rate_per_class")),
            has_col_sampling=colp < 1.0,
            has_monotone=getattr(self, "_monotone_vec", None) is not None,
            tweedie_power=(float(self._parms.get("tweedie_power", 1.5))
                           if "tweedie_power" in self._parms else 1.5),
            quantile_alpha=(float(self._parms.get("quantile_alpha", 0.5))
                            if "quantile_alpha" in self._parms else 0.5),
            hist_method=hist_method,
            grow_policy=tp.get("grow_policy", "depthwise"),
            max_leaves=int(tp.get("max_leaves", 0)),
            pack_bits=int(pack_bits),
            shard_mode=shard_mode,
            n_shards=int(n_shards),
            # deep trees switch wide levels to active-node compaction
            # (measured: DRF depth-17 levels carry ~700 live nodes of 131k
            # heap cells). Off for monotone (needs per-node bounds) and
            # custom objectives (single-tree path keeps the simple shape);
            # the driver rebuilds a chunk densely if the cap overflows.
            compact_cap=(
                # sanitize: the slot pairing needs an even cap ≥ 2
                max(2, int(os.environ.get("H2O3_COMPACT_CAP", 4096)) // 2 * 2)
                if tp["max_depth"] > 12
                and getattr(self, "_monotone_vec", None) is None
                and getattr(self, "_objective_fn", None) is None
                else 0),
        )
        return cfg._replace(code_operand=_cfg_operand_form(cfg)["form"])

    @staticmethod
    def _validate_tree_params(tp) -> None:
        """Value-range validation (hex.ModelBuilder.init / SharedTree
        checkParams): reject nonsense LOUDLY instead of training a
        degenerate model — ntrees=0 'trains' to AUC 0.5, sample_rate=2
        silently clamps, learn_rate<=0 never moves the margin."""
        def bad(msg):
            raise ValueError(msg)

        if tp["ntrees"] < 1:
            bad(f"ntrees must be >= 1, got {tp['ntrees']}")
        if tp["max_depth"] < 1:
            bad(f"max_depth must be >= 1, got {tp['max_depth']} "
                "(0 = unlimited is not supported: the heap tree layout "
                "needs a finite depth cap)")
        for k in ("learn_rate", "learn_rate_annealing", "sample_rate",
                  "col_sample_rate", "col_sample_rate_per_tree"):
            v = tp.get(k)
            if v is not None and not (0.0 < v <= 1.0):
                bad(f"{k} must be in (0, 1], got {v}")
        if tp["nbins"] < 2:
            bad(f"nbins must be >= 2, got {tp['nbins']}")
        if tp["min_rows"] <= 0:
            bad(f"min_rows must be > 0, got {tp['min_rows']}")
        if tp.get("min_split_improvement", 0) < 0:
            bad("min_split_improvement must be >= 0, got "
                f"{tp['min_split_improvement']}")
        mt = tp.get("mtries", 0)
        if mt not in (-2, -1, 0) and mt < 1:
            bad(f"mtries must be -2, -1, or >= 1, got {mt}")
        if tp.get("goss"):
            a, b = tp["goss_top_rate"], tp["goss_other_rate"]
            if not (0.0 < a < 1.0 and 0.0 < b < 1.0 and a + b <= 1.0):
                bad("goss rates must satisfy 0 < goss_top_rate < 1, "
                    f"0 < goss_other_rate < 1, sum <= 1 (got {a}, {b})")
            st = tp.get("goss_start_tree")
            if st is not None and int(st) < 1:
                bad(f"goss_start_tree must be >= 1, got {st} (the first "
                    "trees must train unsampled to seed the gradients)")

    def _ooc_plan(self, tp, npad, F, nbins, resident_bits, shard_mode,
                  n_shards, K):
        """(n_blocks, goss_cfg) — the ONE out-of-core decision per fit
        (ISSUE 14). ``H2O3_TREE_OOC`` gates it: ``0`` never streams (the
        escape hatch — bit-identical to a plain in-core fit), ``1``
        always streams, ``auto`` (default) streams when the packed code
        matrix exceeds the stream budget (``H2O3_STREAM_BUDGET_MB``,
        default half the ledger's device capacity). The block count S is
        a multiple of ``H2O3_TREE_SHARD_BLOCKS`` — the PR 9 deterministic
        reduction grid — sized so a block is ~budget/4 (double buffer +
        headroom); ``H2O3_STREAM_BLOCKS`` forces it (tests pin the
        streamed-vs-in-core bit identity by sharing S).

        The disk tier rides the same decision: packed bytes past the HOST
        budget (``H2O3_STREAM_HOST_BUDGET_MB``; ``H2O3_TREE_OOC_DISK=0``
        disables) also stream — the store then spills overflow blocks to
        persist-backed files and restores them bit-identically, so "fits
        on disk" replaces "fits in host RAM" with the same contract.

        Mesh-sharded fits are ELIGIBLE since round 19 (the PR 11 gap):
        an oversubscribed mesh fit converts to the blocks lane and
        streams — bit-identity with the mesh fit holds transitively
        because both fold the same block grid in the same order (the
        ordered_axis_fold contract; S stays a multiple of the mesh grid
        via the ``base = max(base, n_shards)`` rule below).

        Ineligible fits (multiproc mesh_psum, checkpoint, DART, custom
        objectives, lossguide, monotone, nbins > 256) train in-core
        exactly as before; a goss request on an ineligible fit warns and
        trains unsampled."""
        env = (os.environ.get("H2O3_TREE_OOC", "auto").strip() or "auto")
        goss_cfg = None
        if tp.get("goss"):
            if self._mode != "gbm" or K != 1:
                raise ValueError(
                    "goss requires a GBM fit with a single margin "
                    "(binomial or regression response)")
            if tp["sample_rate"] < 1.0 \
                    or self._parms.get("sample_rate_per_class"):
                raise ValueError(
                    "goss replaces row sampling; keep sample_rate=1.0")
            start = tp.get("goss_start_tree")
            if start is None:
                start = max(1, int(tp["ntrees"]) // 10)
            goss_cfg = dict(top_rate=float(tp["goss_top_rate"]),
                            other_rate=float(tp["goss_other_rate"]),
                            start_tree=int(start))
        eligible = (env != "0"
                    and shard_mode in ("off", "blocks", "mesh")
                    and self._parms.get("checkpoint") is None
                    and not tp.get("dart")
                    and getattr(self, "_objective_fn", None) is None
                    and tp.get("grow_policy", "depthwise") != "lossguide"
                    and getattr(self, "_monotone_vec", None) is None
                    and nbins <= 256)
        if not eligible:
            if goss_cfg is not None:
                from ..runtime.log import Log

                Log.warn("goss: this fit is not eligible for the "
                         "out-of-core streamed path (see "
                         "docs/architecture.md); training unsampled in-core")
            return 0, None
        codes_bytes = (npad * F * resident_bits // 8 if resident_bits
                       else npad * F)
        from . import block_store as _bs

        budget = _bs.stream_budget_bytes()
        host_budget = _bs.stream_host_budget_bytes()
        over_host = host_budget > 0 and codes_bytes > host_budget
        if env != "1" and goss_cfg is None and codes_bytes <= budget \
                and not over_host:
            return 0, None
        base = max(int(os.environ.get("H2O3_TREE_SHARD_BLOCKS", "8") or 8),
                   1)
        if n_shards:
            # a forced-blocks fit keeps its grid a multiple of its S, so
            # the streamed reduction stays bit-compatible with it
            base = max(base, n_shards)
        forced = int(os.environ.get("H2O3_STREAM_BLOCKS", "0") or 0)
        if forced > 0:
            S = forced
        else:
            target = max(budget // 4, 1)
            needed = max(-(-codes_bytes // target), base)
            S = -(-needed // base) * base
        return max(min(S, max(npad // 8, 1)), 1), goss_cfg

    # -- CV fold reuse (model_base._run_cv fast path) -----------------------
    def _cv_can_reuse(self) -> bool:
        """Tree fits can slice the parent's binned codes per fold unless a
        feature needs the fold's raw x columns or frame-path scoring:
        checkpoint continuation (re-bins with the prior model's edges),
        monotone constraints (validated against the training frame's
        column types), and offset_column (per-fold validation metrics
        apply the holdout's offset through the frame scoring path)."""
        return (self._parms.get("checkpoint") is None
                and not self._parms.get("monotone_constraints")
                and not self._parms.get("offset_column"))

    def _cv_reuse_source(self, model, train: Frame):
        bm = getattr(model, "bm", None)
        if isinstance(bm, BinnedMatrix) and bm.codes is not None \
                and bm.codes.shape[0] == train.nrow:
            return bm
        return None

    def _cv_predict_codes(self, model: SharedTreeModel,
                          codes: np.ndarray) -> np.ndarray:
        """`_cv_predict` on pre-binned holdout codes (fold-reuse path)."""
        out = model._probs_from_codes(codes)
        if model.problem == "binomial":
            return out[:, 1]
        if model.problem == "multinomial":
            return out
        return out[:, 0]

    def _fit(self, x, y, train: Frame, valid: Optional[Frame]) -> SharedTreeModel:
        with _Phase() as _ph:
            return self._fit_phases(x, y, train, valid, _ph)

    def _fit_phases(self, x, y, train: Frame, valid: Optional[Frame],
                    _ph: _Phase) -> SharedTreeModel:
        _ph.stage("design.matrix")
        tp = self._tree_params()
        self._validate_tree_params(tp)
        seed = self._parms["_actual_seed"]
        yvec = train.vec(y)
        problem, nclass, domain = response_info(yvec)
        dist = dist_mod.infer_distribution(
            problem, self._parms.get("distribution", "AUTO")
        )
        if self._mode == "drf":
            # DRF trees fit raw response means (no boosting margin)
            dist = "gaussian" if problem == "regression" else dist

        # CV fold reuse (models/model_base._run_cv): the parent fit already
        # built the full frame's BinnedMatrix — folds slice its rows instead
        # of re-running frame_to_matrix + build_bins per fold (the
        # LightGBM/XGBoost-style CV over one quantized matrix).
        # H2O3_CV_REBIN=1 disables this upstream, restoring the seed path.
        cvr = self._parms.get("_cv_reuse")
        from . import dataset_cache as _dsc

        multiproc = distdata.multiprocess()
        cloud = cloudlib.cloud()
        ndev = cloud.size
        # ISSUE 12 / ISSUE 18: the ONE sharding decision for this fit,
        # taken up-front because the pod lane (deterministic multi-process
        # SPMD) changes the data layout and cache eligibility below. On a
        # pod the rows live in the CANONICAL global layout and every
        # reduction folds the global block order, so the fit is
        # bit-identical to the 1-device forced-shard fit sharing S.
        shard_mode, n_shards = _shard_plan(ndev, multiproc, tp)
        pod = multiproc and shard_mode == "mesh"
        # pod fits reuse the dataset cache: their builders are
        # collective-free (the canonical row exchange runs EAGERLY every
        # fit, before any builder, so a cache hit/miss divergence across
        # ranks can never strand one rank inside a collective)
        use_cache = (cvr is None and (pod or not multiproc)
                     and _dsc.enabled())
        if cvr is not None:
            pbm, cv_rows = cvr["bm"], np.asarray(cvr["rows"])
            X = None
            is_cat = np.asarray(pbm.is_categorical, bool)
            doms = list(pbm.domains)
            n, F = int(len(cv_rows)), int(pbm.codes.shape[1])
            nbins = int(pbm.nbins)
        else:
            _ph.stage_span.annotate(cache="hit" if use_cache else "off")
            if use_cache:
                X, is_cat, doms = _dsc.matrix(
                    train, x, builder=_noting_miss(
                        _ph, lambda: frame_to_matrix(train, x)))
            else:
                X, is_cat, doms = frame_to_matrix(train, x)
            n, F = X.shape
            # clamp nbins to max categorical cardinality like nbins_cats
            max_card = int(max([len(d) for d, c in zip(doms, is_cat) if c and d], default=0))
            nbins = max(tp["nbins"] + 1, min(max_card + 1, 1 << 10))
        # memory-feasibility depth clamp: the static level-complete heap
        # materializes ~2^D·F·nbins per-node histograms at the deepest level
        # (~96 B/bin-slot empirical, incl. XLA tile padding and co-resident
        # sibling buffers). The reference's dynamic trees shrink with the
        # data; the static heap must cap depth or the compile OOMs HBM
        # (e.g. DRF's default max_depth=20 at nbins=20 needs ~22 GB).
        # Skipped under checkpoint= (the prior model's heap depth governs —
        # new trees must concatenate onto the same heap shape).
        requested_depth = tp["max_depth"]
        if self._parms.get("checkpoint") is None:
            # this process's own device: on a pod, global device 0 belongs
            # to rank 0 and cannot be asked from the other ranks
            dev0 = jax.local_devices()[0]
            stats = dev0.memory_stats() or {}
            hbm_budget = int(stats.get("bytes_limit", 0)) // 2
            if not hbm_budget:
                if dev0.platform != "cpu":
                    raise RuntimeError(
                        f"{dev0.platform} device reports no memory_stats() "
                        "bytes_limit: cannot size the tree heap to its HBM")
                hbm_budget = 8 << 30    # the CPU backend reports no limit
            feas = tp["max_depth"]
            while feas > 4 and (1 << feas) * F * nbins * 96 > hbm_budget:
                feas -= 1
            if tp["max_depth"] > feas:
                from ..runtime.log import Log

                Log.warn(
                    f"max_depth={tp['max_depth']} clamped to {feas}: the "
                    f"level-complete heap's deepest histograms (F={F}, "
                    f"nbins={nbins}) would exceed the HBM budget "
                    f"({hbm_budget >> 30} GiB)")
                tp["max_depth"] = feas
        _ph.mark("frame_to_matrix")
        _ph.stage("design.bins")
        _ph.stage_span.annotate(cache="hit" if use_cache else "off")
        col_ranges = None
        if multiproc:
            # multi-host cloud: this process holds its ingest shard; global
            # facts come from collectives. The full tree feature envelope is
            # cloud-size-agnostic (custom objectives included — they run on
            # globally-gathered rows, see the contract at the custom_obj
            # branch below and docs/distributed.md).
            with np.errstate(all="ignore"):
                lmin = np.nanmin(np.where(np.isnan(X), np.inf, X), axis=0)
                lmax = np.nanmax(np.where(np.isnan(X), -np.inf, X), axis=0)
            gmin, gmax = distdata.global_minmax(lmin, lmax)
            col_ranges = np.stack([gmin, gmax], axis=1)
        col_qedges = None
        if multiproc and (tp["histogram_type"] == "QuantilesGlobal"):
            # distributed QuantilesGlobal: per-column GLOBAL quantile edges
            # via iterative histogram refinement (hex/quantile/Quantile.java
            # as a host collective) — every process derives identical edges
            nvalue = nbins - 1
            qs = np.linspace(0, 1, nvalue + 1)[1:-1]
            col_qedges = []
            for j in range(X.shape[1]):
                if is_cat[j]:
                    col_qedges.append(None)
                    continue
                colv = X[:, j]
                colv = colv[np.isfinite(colv)]
                col_qedges.append(
                    np.unique(distdata.global_quantiles(colv, qs)))
        if cvr is not None:
            # row-slice the parent's codes; edges/domains are shared objects
            # (the fold model scores through the SAME quantization grid)
            bm = BinnedMatrix(
                codes=pbm.codes[cv_rows], edges=pbm.edges, nbins=pbm.nbins,
                names=list(pbm.names), is_categorical=pbm.is_categorical,
                domains=list(pbm.domains))
        elif use_cache:
            bm = _dsc.bins(
                train, x, nbins, tp["histogram_type"], seed,
                builder=_noting_miss(_ph, lambda: build_bins(
                    X, nbins=nbins, histogram_type=tp["histogram_type"],
                    names=list(x), is_categorical=is_cat, domains=doms,
                    seed=seed, col_ranges=col_ranges,
                    col_quantile_edges=col_qedges)))
        else:
            bm = build_bins(
                X, nbins=nbins, histogram_type=tp["histogram_type"], names=list(x),
                is_categorical=is_cat, domains=doms, seed=seed,
                col_ranges=col_ranges, col_quantile_edges=col_qedges,
            )

        _ph.stage("design.vectors")
        mc = self._parms.get("monotone_constraints")
        if mc:
            # {col: ±1} → (F,) vector aligned with x (GBM monotone_constraints)
            vec = np.zeros(len(x), np.float32)
            for cname, d in dict(mc).items():
                if cname not in x:
                    raise ValueError(f"monotone_constraints: unknown column {cname!r}")
                if train.vec(cname).type == "enum":
                    raise ValueError(
                        f"monotone_constraints: {cname!r} is categorical — "
                        "constraints apply to numeric columns only")
                vec[list(x).index(cname)] = float(d)
            self._monotone_vec = jnp.asarray(vec)
        else:
            self._monotone_vec = None

        if self._parms.get("offset_column") and self._mode == "drf":
            # reference parity: DRF.init rejects offsets ("Offsets are not yet
            # supported for DRF") — and scoring here never applies them
            raise ValueError("offset_column is not supported for DRF")
        K = 1 if problem in ("regression", "binomial") else nclass

        def _host_targets():
            """The response side of the design on the host: float32 row
            weights (balance_classes factors folded in), the offset, the
            (n, K) float32 response and the initial margin f0 — global
            moments on a multi-host cloud, whose collectives keep this out
            of any dataset-cache builder there."""
            w = (
                train.vec(self._parms["weights_column"]).numeric_np()
                if self._parms.get("weights_column")
                else np.ones(n)
            ).astype(np.float32)
            # (prior_dist, model_dist) for score correction
            balance_dists = None
            if (self._parms.get("balance_classes")
                    and problem in ("binomial", "multinomial")):
                # class balancing as per-class row weights — expectation-
                # equal to the reference's minority oversampling
                # (ModelBuilder balance_classes / class_sampling_factors);
                # scoring applies the priorClassDist/modelClassDist
                # probability correction below
                codes_y = np.asarray(yvec.data)
                counts = np.bincount(
                    codes_y, minlength=nclass).astype(np.float64)
                n_bal = n
                if multiproc:
                    # global class distribution (the MRTask class-count reduce)
                    counts = distdata.global_sum(counts)
                    n_bal = float(counts.sum())
                csf = self._parms.get("class_sampling_factors")
                if csf is not None:
                    factors = np.asarray(csf, np.float64)
                else:
                    factors = n_bal / (len(counts) * np.maximum(counts, 1.0))
                cap = float(self._parms.get("max_after_balance_size", 5.0))
                factors = np.minimum(
                    factors, cap * n_bal / np.maximum(counts, 1.0))
                w = (w * factors[codes_y]).astype(np.float32)
                prior_dist = counts / counts.sum()
                model_w = counts * factors
                balance_dists = (prior_dist, model_w / model_w.sum())

            offset = (
                train.vec(self._parms["offset_column"]).numeric_np()
                .astype(np.float32)
                if self._parms.get("offset_column")
                else None
            )

            if problem == "regression":
                yk = yvec.numeric_np().astype(np.float32)[:, None]
            elif problem == "binomial":
                yk = np.asarray(yvec.data, np.float32)[:, None]
            else:
                codes = np.asarray(yvec.data)
                yk = np.zeros((n, K), np.float32)
                yk[np.arange(n), codes] = 1.0

            # initial margins (global moments on a multi-host cloud)
            if multiproc and not pod:
                sw = float(distdata.global_sum(np.asarray([w.sum()]))[0])
                swy = distdata.global_sum((yk * w[:, None]).sum(axis=0))
            elif pod and self._mode != "drf" \
                    and getattr(self, "_objective_fn", None) is None:
                # pod determinism: f0 must match the 1-device comparator's
                # host computation BITWISE, and a sum of per-rank partials
                # does not (numpy's pairwise reduction groups differently).
                # The response/weight columns are small — gather them exactly
                # (byte transport, rank order = global ingest order) and run
                # the single-process formulas on the global vectors.
                yk_g = distdata.allgather_rows(yk)
                w_g = distdata.allgather_rows(w)
            if self._mode == "drf":
                f0 = np.zeros(K, np.float32)
            elif problem == "multinomial":
                pri = (np.average(yk_g, axis=0, weights=w_g) if pod
                       else swy / max(sw, 1e-12) if multiproc
                       else np.average(yk, axis=0, weights=w))
                f0 = np.log(np.clip(pri, 1e-10, 1.0)).astype(np.float32)
            elif getattr(self, "_objective_fn", None) is not None:
                # custom objectives start at 0 margin
                f0 = np.zeros(1, np.float32)
            elif multiproc and not pod and dist in ("quantile", "laplace"):
                # order-statistic inits need GLOBAL quantiles of the response
                alpha = (float(self._parms.get("quantile_alpha", 0.5))
                         if dist == "quantile" else 0.5)
                f0 = np.asarray([np.float32(
                    distdata.global_quantiles(yk[:, 0], [alpha])[0])])
            else:
                f0 = np.float32(dist_mod.init_margin(
                    dist, yk_g[:, 0] if pod else yk[:, 0],
                    w_g if pod else w,
                    mu=(float(swy[0]) / max(sw, 1e-12))
                    if (multiproc and not pod) else None,
                    alpha=float(self._parms.get("quantile_alpha", 0.5))))
                f0 = np.asarray([f0])
            return w, offset, yk, f0, balance_dists

        # `ndev_eff` is the device count the data will actually span — 1
        # under the H2O3_TREE_SHARD=0 escape hatch even on a mesh
        # (everything lands on the default device, exactly the 1-device
        # code path).
        ndev_eff = ndev if shard_mode in ("mesh", "mesh_psum") else 1
        # every mesh shard AND every deterministic reduction block must be
        # an equal, 8-row-aligned slice (pack groups divide 8)
        row_mult = max(ndev_eff * 8, n_shards * 8, 8)
        if multiproc and not pod:
            quota = distdata.local_quota(n)
            npad = quota * jax.process_count()
            pad = quota - n          # LOCAL padding (zero-weight rows)
        else:
            n_layout = n
            if pod:
                # pod canonical layout (ISSUE 18): the padded GLOBAL shape
                # comes from the SAME formula the 1-device comparator runs
                # on the same global row count — identical npad and block
                # grid are two legs of the bit-identity argument (the
                # third is the canonical row order, parallel/distdata.py)
                _counts = distdata.row_counts(n)
                n_layout = int(_counts.sum())
            npad = cloudlib.pad_to_multiple(n_layout, row_mult)
            # row-count bucketing (the ntrees-bucketing trick, applied to
            # rows): CV folds and near-same-size frames land on a shared
            # padded shape, so they reuse ONE compiled tree program instead
            # of paying a compile-cache load each. ≤12.5% extra zero-weight
            # rows — exact no-ops.
            # bucket values are (2^k/8)·{8..16} — divisible by any power-of-
            # two shard count but not e.g. a 6-device mesh or the blocked
            # reduction's S·8 grid, so round back up to the row multiple to
            # keep shard_map's equal-shard (and equal-block) invariant
            npad = cloudlib.pad_to_multiple(_bucket_rows(npad), row_mult)
            # CV fold fits inherit the parent fit's padded row count
            # (_npad_floor): the fold then reuses the parent's ALREADY-LOADED
            # executable instead of paying a second compile-cache load for
            # the smaller bucket;
            # the extra rows are zero-weight no-ops (deep trees included:
            # active-node compaction made deep fold compute cheap, so one
            # shared program beats a second multi-second program load)
            floor = int(self._parms.get("_npad_floor") or 0)
            if floor > npad and floor % row_mult == 0:
                npad = floor
            pad = npad - n_layout
            if pod:
                # equal per-rank slice of the canonical layout. row_mult is
                # a multiple of ndev·8 on the pod lane and the process
                # count divides the device count, so the slice is 8-aligned
                # (pack groups and local device shards both divide it).
                quota = npad // jax.process_count()
                pad = quota - int(distdata.canonical_counts(
                    _counts, npad)[jax.process_index()])

        def padr(a, fill=0):
            if pod:
                # canonical relayout: rows move to the global-order slice
                # this rank owns (a COLLECTIVE — call sites run it eagerly,
                # never inside a dataset-cache builder)
                return distdata.to_canonical(a, npad, counts=_counts,
                                             fill=fill)
            if a.ndim == 1:
                return np.concatenate([a, np.full(pad, fill, a.dtype)])
            return np.concatenate([a, np.full((pad,) + a.shape[1:], fill, a.dtype)])

        def unpadr(a):
            """Inverse of padr for metric read-back: this rank's REAL rows
            in INGEST order (pod slices hold canonical-order rows that must
            pair with the local frame's response)."""
            if pod:
                return distdata.from_canonical(np.asarray(a), npad, _counts)
            return np.asarray(a)[:n]

        _ph.mark("build_bins")
        _ph.stage("design.codes")

        # ---- resident sub-byte code packing (ISSUE 7 tentpole) -----------
        # The device-resident code matrix stays PACKED for the whole fit:
        # the histogram kernels and the partition step read its widened
        # form (built once a fit where the plan runs the Pallas kernel —
        # "the histogram kernel's code operand" below — else once per tree
        # program) — so the matrix the dataset cache holds in HBM
        # (and ships through the host↔device link) shrinks 2-4×. Paths that
        # score `predict_codes` against the resident matrix (DART dropout,
        # checkpoint fast-forward) and the lossguide builder keep full width.
        # Pod fits keep the packed-resident win: quota is 8-aligned, so
        # packing this rank's canonical slice equals slicing the packed
        # global matrix — same bytes the 1-device comparator holds.
        resident_bits = 0
        if ((pod or not multiproc)
                and self._parms.get("checkpoint") is None
                and not tp.get("dart")
                and tp.get("grow_policy", "depthwise") != "lossguide"
                and nbins <= 256):
            resident_bits = _pack_bits_for(nbins, npad)

        # ---- out-of-core streaming (ISSUE 14 tentpole) -------------------
        # When the packed code matrix exceeds the stream budget (or
        # H2O3_TREE_OOC=1 forces it), the fit streams host-resident blocks
        # through a bounded device set instead of uploading the matrix. A
        # streamed fit IS an S-block deterministic reduction: cfg takes
        # the shard_mode="blocks" decisions (histogram dispatch, blocked
        # scoring-event loss, host metrics path), so the in-core
        # comparator (H2O3_TREE_OOC=0 with H2O3_TREE_SHARD=1 sharing S)
        # is bit-identical by construction — pinned in
        # tests/test_tree_stream.py.
        ooc_blocks, goss_cfg = 0, None
        if not multiproc and shard_mode in ("off", "blocks", "mesh"):
            ooc_blocks, goss_cfg = self._ooc_plan(
                tp, npad, F, nbins, resident_bits, shard_mode, n_shards, K)
        elif tp.get("goss"):
            # multi-process fits never stream, but a goss request must
            # fail/warn IDENTICALLY to the 1-device path — not be
            # silently dropped by the shard gate
            self._ooc_plan(tp, npad, F, nbins, resident_bits, shard_mode,
                           n_shards, K)
        if ooc_blocks:
            # mesh-gap closure (round 19): an oversubscribed mesh fit
            # converts to the single-lane blocks reduction and streams —
            # ndev_eff MUST drop to 1 with it (the codes never get a
            # device_put to a row sharding; the store uploads per block).
            # Bit-identity with the mesh fit holds because S stays a
            # multiple of the mesh grid and both fold blocks in order.
            shard_mode, n_shards = "blocks", ooc_blocks
            ndev_eff = 1
            row_mult = max(n_shards * 8, 8)
            npad = cloudlib.pad_to_multiple(
                _bucket_rows(cloudlib.pad_to_multiple(n, row_mult)),
                row_mult)
            floor = int(self._parms.get("_npad_floor") or 0)
            if floor > npad and floor % row_mult == 0:
                npad = floor
            pad = npad - n
            if resident_bits:
                resident_bits = _pack_bits_for(nbins, npad)

        # ---- background program warm-up ----------------------------------
        # The first dispatch of the tree-step program pays trace + XLA
        # compile-cache load in the
        # calling thread, and the big H2D uploads below are synchronous
        # over the same link. Overlap the two: a daemon thread traces
        # and dispatches the step program on device-CREATED dummy zeros (no
        # H2D traffic) while this thread streams the real data up.
        # Checkpoint continuation mutates max_depth/nbins after this point,
        # so it skips the early warm-up (rare path; costs only the load).
        warm_thread = None
        if self._parms.get("checkpoint") is None \
                and getattr(self, "_objective_fn", None) is None \
                and not multiproc and not ooc_blocks \
                and os.environ.get("H2O3_WARM_THREAD", "1") != "0":
            cfg_early = self._make_step_cfg(tp, npad, K, F, nbins, problem,
                                            dist, pack_bits=resident_bits,
                                            shard_mode=shard_mode,
                                            n_shards=n_shards)
            # sweep-warm reuse: when this config's step program is already
            # built in-process (a CV fold after its parent, or a repeat
            # grid/AutoML candidate), the dummy warm execution is pure
            # waste — a full tree step on zeros competing with the sweep's
            # real work.
            if cfg_early in cloud.__dict__.get("_step_fns_cache", {}):
                cfg_early = None
            code_dt = jnp.uint8 if nbins <= 256 else jnp.uint16
            # packed codes: the dummy matrix takes the packed shape so the
            # warm trace IS the real program
            codes_shape = ((npad * resident_bits // 8, F) if resident_bits
                           else (npad, F))
            drf = self._mode == "drf"
            # the thread continues the fit's trace under ``fit.design``, so
            # what it compiles or loads lands on the fit's span tree
            design = _ph.fit_span

            def _warm():
                with _tracing.attach(design.trace_id, design.span_id,
                                     name="design.warm", kind="fit"):
                    _warm_programs()

            def _warm_programs():
                try:
                    tj, _ = _tree_step_fns(cfg_early, cloud)
                    codes_dummy = jnp.zeros(codes_shape, code_dt)
                    if cfg_early.code_operand == "fit":
                        # one-device fits only: no sharding below
                        codes_dummy = (codes_dummy, jnp.zeros(
                            _operand_shape(cfg_early), jnp.float32))
                    args = [
                        jnp.zeros((npad, K), jnp.float32),                # margins
                        jnp.zeros((npad, K) if drf else (1, K), jnp.float32),
                        jnp.zeros(npad if drf else 1, jnp.float32),
                        codes_dummy,                                      # codes
                        jnp.zeros((npad, K), jnp.float32),                # y
                        jnp.zeros(npad, jnp.float32),                     # w
                        jnp.ones(npad, jnp.float32),                      # rate
                        jnp.zeros((F, nbins - 2), jnp.float32),           # edges
                        jnp.zeros(F, jnp.float32),                        # mono
                        jnp.zeros(9, jnp.float32),                        # hp
                        jax.random.PRNGKey(0),
                        np.int32(0),
                    ]
                    if ndev_eff > 1:
                        # shard exactly the args the real call shards
                        # (mono/hp/key stay uncommitted there — committing
                        # them here would compile a different executable)
                        rs_ = cloud.row_sharding()
                        rep = cloud.replicated()
                        shardings = [rs_, rs_ if drf else None,
                                     rs_ if drf else None, rs_, rs_, rs_,
                                     rs_, rep, None, None, None, None]
                        args = [a if s is None else jax.device_put(a, s)
                                for a, s in zip(args, shardings)]
                    out = tj(*args)
                    # the warm execution must FINISH before the real tree
                    # programs dispatch — a CPU mesh deadlocks on two
                    # concurrent collective executables (collective_fence)
                    cloudlib.collective_fence(out[0])
                    # also pre-load the other per-config program of a cold
                    # run: the device-side AUC2 training-metrics reduction
                    # (warm ALL programs of a config, not
                    # just the first tree program)
                    if (problem == "binomial" and dist == "bernoulli"
                            and self._mode == "gbm" and ndev == 1
                            and shard_mode == "off"):
                        _binom_binned_stats(
                            jnp.zeros((npad, K), jnp.float32),
                            jnp.zeros((npad, K), jnp.float32),
                            jnp.int32(npad))
                except Exception:  # warm-up is advisory; real call reports
                    pass

            if cfg_early is not None:
                warm_thread = threading.Thread(target=_warm, daemon=True)
                warm_thread.start()

        edges = np.full((F, nbins - 2), np.float32(np.inf), np.float32)
        for j, e in enumerate(bm.edges):
            edges[j, : min(len(e), nbins - 2)] = e[: nbins - 2]

        if multiproc:
            # each process supplies its ingest shard of the global arrays,
            # homed on its own devices (the DKV chunk-home placement)
            if pod:
                from ..runtime import phases as _phases_mod

                # the relayout collective runs EAGERLY; the cache builder
                # below only packs + assembles the global array
                # (make_array_from_process_local_data is metadata-only),
                # so a per-rank cache hit/miss divergence is harmless.
                # No rank ever materializes the global matrix: per-host
                # pack + H2D is the 1/N canonical slice.
                codes_canon = padr(bm.codes)

                def _build_codes_pod():
                    if resident_bits:
                        packed = _pack_host(codes_canon, resident_bits)
                        _phases_mod.add("h2d", 0.0, packed.nbytes)
                        return distdata.global_row_array(
                            packed, quota * resident_bits // 8, cloud)
                    _phases_mod.add("h2d", 0.0, codes_canon.nbytes)
                    return distdata.global_row_array(codes_canon, quota,
                                                     cloud)

                if use_cache:
                    codes_d = _dsc.device_codes(
                        train, x, nbins, tp["histogram_type"], seed, npad,
                        builder=_build_codes_pod, pack_bits=resident_bits,
                        n_devices=ndev_eff)
                else:
                    codes_d = _build_codes_pod()
            else:
                codes_d = distdata.global_row_array(padr(bm.codes), quota,
                                                    cloud)
            _ph.stage("design.state")
            w, offset, yk, f0, balance_dists = _host_targets()
            y_d = distdata.global_row_array(
                padr(yk).astype(np.float32), quota, cloud)
            w_d = distdata.global_row_array(padr(w), quota, cloud)
            edges_d = distdata.replicated_array(edges, cloud)
            rs_m = cloud.row_sharding()
            margins = jax.jit(
                lambda: jnp.broadcast_to(
                    jnp.asarray(f0)[None, :], (npad, K)).astype(jnp.float32),
                out_shardings=rs_m)()
            if offset is not None:
                off_g = distdata.global_row_array(padr(offset), quota, cloud)
                margins = jax.jit(lambda m, o: m + o[:, None],
                                  out_shardings=rs_m)(margins, off_g)
        else:
            from ..runtime import phases as _phases_mod

            def _build_codes_dev():
                # the bin-code matrix is the biggest fixed H2D cost: it
                # ships as 4/5/6-bit packed words wherever it can. Resident
                # packing KEEPS it packed in HBM, 2-4x smaller, and the tree
                # kernels consume the words directly; a full-width resident
                # fit packs for the transfer only and widens on device. On a
                # mesh the upload is ROW-SHARDED straight from HOST memory
                # (packed word groups align with the 8-row shard grid): each
                # chip receives only its slice — staging the whole matrix on
                # one device and resharding would make per-chip HBM peak
                # equal the GLOBAL matrix. A full-width sharded upload (rare:
                # nbins>256 / dart / checkpoint on a mesh) ships unpacked:
                # pack-for-transfer targets the single host↔device link and
                # would stage everything on one chip.
                rs_codes = (cloud.row_sharding() if ndev_eff > 1 else None)
                widen = 0
                with _tracing.span("design.pack", kind="fit"):
                    host = padr(bm.codes)
                    if resident_bits:
                        host = _pack_host(host, resident_bits)
                    elif rs_codes is None and host.dtype == np.uint8:
                        widen = _pack_bits_for(nbins, host.shape[0])
                        if widen:
                            host = _pack_host(host, widen)
                _phases_mod.add("h2d", 0.0, host.nbytes)
                with _tracing.span("design.upload", kind="fit",
                                   bytes_h2d=int(host.nbytes)):
                    if rs_codes is not None:
                        return jax.device_put(host, rs_codes)
                    dev = jnp.asarray(host)
                    return _unpack_device(dev, widen) if widen else dev

            ooc_store = None
            cache_codes = (use_cache and not ooc_blocks
                           and (ndev_eff == 1 or shard_mode == "mesh"))
            if ooc_blocks:
                # out-of-core: the matrix NEVER uploads whole. Packed
                # blocks are built O(block) from the padded codes and live
                # on host; the bounded device resident set fills lazily as
                # the streamed level loop walks them. Cached like
                # device_codes so a sweep packs the blocks once.
                def _build_store():
                    from . import block_store as _bs

                    return _bs.BlockStore.from_codes(
                        padr(bm.codes), n_blocks=ooc_blocks,
                        pack_bits=resident_bits, register=not use_cache)

                if use_cache:
                    ooc_store = _dsc.blocked_codes(
                        train, x, nbins, tp["histogram_type"], seed, npad,
                        builder=_build_store, pack_bits=resident_bits,
                        n_blocks=ooc_blocks)
                else:
                    ooc_store = _build_store()
                codes_d = None
            elif cache_codes:
                # sweep-level reuse: every candidate sharing this
                # (frame, x, nbins, histogram) trains off ONE device-resident
                # code matrix — the pack + H2D upload happens once. The
                # packing mode AND the shard layout key the cache entry: a
                # packed and a full-width consumer (or a 1-device and an
                # 8-shard consumer) never share an artifact.
                _ph.stage_span.annotate(cache="hit")
                codes_d = _dsc.device_codes(
                    train, x, nbins, tp["histogram_type"], seed, npad,
                    builder=_noting_miss(_ph, _build_codes_dev),
                    pack_bits=resident_bits, n_devices=ndev_eff)
            else:
                _ph.stage_span.annotate(cache="off")
                codes_d = _build_codes_dev()
            _ph.stage("design.state")

            def _build_targets():
                w, offset, yk, f0, balance_dists = _host_targets()
                if yk.size and bool(np.all((yk >= 0) & (yk <= 255)
                                           & (yk == np.floor(yk)))):
                    # integer-ish response (class indicators, counts): ship
                    # uint8 over the host↔device link (4× smaller) and
                    # widen on device
                    _phases_mod.add("h2d", 0.0, npad)
                    y_d = jnp.asarray(
                        padr(yk.astype(np.uint8))).astype(jnp.float32)
                else:
                    _phases_mod.add("h2d", 0.0, 4 * npad)
                    y_d = jnp.asarray(padr(yk))
                if np.all(w == 1.0):
                    # trivial weights: build on device (zero-weight padded
                    # tail) instead of pushing 4·npad bytes of 1.0s over
                    # the link
                    w_d = (jnp.ones(npad, jnp.float32).at[n:].set(0.0)
                           if pad else jnp.ones(npad, jnp.float32))
                else:
                    _phases_mod.add("h2d", 0.0, 4 * npad)
                    w_d = jnp.asarray(padr(w))
                off_d = (None if offset is None
                         else jnp.asarray(padr(offset)))
                if ndev_eff > 1:
                    rs = cloud.row_sharding()
                    y_d = jax.device_put(y_d, rs)
                    w_d = jax.device_put(w_d, rs)
                return _Targets(y_d, w_d, off_d, f0, balance_dists,
                                yk if self._mode == "drf" else None)

            if cache_codes:
                # the response side is the same for every candidate on the
                # frame: built and uploaded once, like the codes. The key
                # is everything `_build_targets` reads beyond the columns
                _ph.stage_span.annotate(cache="hit")
                csf = self._parms.get("class_sampling_factors")
                tkey = (
                    problem, nclass, dist, self._mode,
                    getattr(self, "_objective_fn", None) is not None,
                    float(self._parms.get("quantile_alpha", 0.5))
                    if dist == "quantile" else None,
                    bool(self._parms.get("balance_classes")),
                    None if csf is None else tuple(map(float, csf)),
                    float(self._parms.get("max_after_balance_size", 5.0)),
                    npad, ndev_eff)
                tg = _dsc.targets(
                    train, x, (y, self._parms.get("weights_column"),
                               self._parms.get("offset_column")), tkey,
                    builder=_noting_miss(_ph, _build_targets))
            else:
                _ph.stage_span.annotate(cache="off")
                tg = _build_targets()
            y_d, w_d, off_d, f0, balance_dists, yk = tg
            _phases_mod.add("h2d", 0.0, edges.nbytes)
            edges_d = jnp.asarray(edges)

            if ndev_eff > 1:
                codes_d = jax.device_put(codes_d, cloud.row_sharding())
                edges_d = jax.device_put(edges_d, cloud.replicated())

            margins = jnp.broadcast_to(jnp.asarray(f0)[None, :], (npad, K)).astype(jnp.float32)
            if off_d is not None:
                margins = margins + off_d[:, None]
            if ndev_eff > 1:
                margins = jax.device_put(margins, cloud.row_sharding())

        # real-row mask for device-side event metrics (pads excluded); on a
        # multi-process cloud it is global, so event sums come back global
        if multiproc:
            if pod:
                # canonical pad lives at the GLOBAL tail, so no exchange:
                # a slice is real up to its canonical row count
                cc_r = int(distdata.canonical_counts(
                    _counts, npad)[jax.process_index()])
                row_mask_d = distdata.global_row_array(
                    (np.arange(quota) < cc_r).astype(np.float32), quota,
                    cloud)
            else:
                row_mask_d = distdata.global_row_array(
                    np.ones(n, np.float32), quota, cloud)
        else:
            row_mask_d = (jnp.arange(npad) < n).astype(jnp.float32)
            if ndev_eff > 1:
                row_mask_d = jax.device_put(row_mask_d, cloud.row_sharding())

        # checkpoint= continue-training: restore the prior forest and fast-
        # forward margins (SharedTree checkpoint restart — `_parms.checkpoint`
        # compat checks + tree restore in hex/tree/SharedTree.java)
        prior_stacked: List = []
        n_prior = 0
        ckpt = self._parms.get("checkpoint")
        if ckpt is not None:
            pm = ckpt.model if hasattr(ckpt, "model") else ckpt
            if not isinstance(pm, SharedTreeModel):
                raise ValueError("checkpoint must be a prior tree model")
            # compatible iff the user asks for the prior heap depth OR the
            # same ORIGINAL depth the prior fit clamped down from (the HBM
            # clamp must not break continuation with identical parameters)
            depth_ok = tp["max_depth"] in (
                pm.max_depth, getattr(pm, "requested_max_depth", pm.max_depth))
            if not depth_ok or pm.nclass != nclass:
                raise ValueError(
                    "checkpoint incompatible: max_depth/nclass must match "
                    "(SharedTree checkpoint parameter compatibility checks)"
                )
            tp["max_depth"] = pm.max_depth
            # re-bin the CURRENT training data with the prior model's edges so
            # split bins stay aligned with the restored trees
            bm = pm.bm
            nbins = bm.nbins
            edges_np = np.full((F, nbins - 2), np.inf, np.float32)
            for j, e in enumerate(bm.edges):
                edges_np[j, : min(len(e), nbins - 2)] = e[: nbins - 2]
            n_prior = pm.ntrees_built
            f0 = np.asarray(pm.f0).reshape(-1).astype(np.float32)
            prior_stacked = list(pm.forest)
            prior_replicated: List = []   # reused by the valid fast-forward
            if multiproc:
                # every rank restored the SAME artifact (the model object the
                # user passed exists identically on each process); codes are
                # this rank's shard, the forest is replicated, margins fast-
                # forward inside jit programs
                codes_d = distdata.global_row_array(
                    padr(bin_apply(bm, X)), quota, cloud)
                edges_d = distdata.replicated_array(edges_np, cloud)
                rs_m = cloud.row_sharding()
                margins = jax.jit(
                    lambda f: jnp.broadcast_to(
                        f[None, :], (npad, K)).astype(jnp.float32),
                    out_shardings=rs_m)(f0)
                for k in range(K):
                    forest_k = jax.tree.map(
                        lambda a: distdata.replicated_array(
                            np.asarray(a), cloud), pm.forest[k])
                    prior_replicated.append(forest_k)
                    margins = _margin_ffwd_jit(
                        forest_k, codes_d, margins, jnp.int32(k),
                        tp["max_depth"])
                if offset is not None:
                    off_g = distdata.global_row_array(padr(offset), quota,
                                                      cloud)
                    margins = jax.jit(lambda m, o: m + o[:, None],
                                      out_shardings=rs_m)(margins, off_g)
            else:
                codes_d = jnp.asarray(padr(bin_apply(bm, X)))
                edges_d = jnp.asarray(edges_np)
                margins = jnp.broadcast_to(
                    jnp.asarray(f0)[None, :], (npad, K)).astype(jnp.float32)
                for k in range(K):
                    margins = _margin_ffwd_jit(
                        jax.tree.map(jnp.asarray, pm.forest[k]), codes_d,
                        margins, jnp.int32(k), tp["max_depth"])
                if off_d is not None:
                    margins = margins + off_d[:, None]
                if ndev_eff > 1:
                    codes_d = jax.device_put(codes_d, cloud.row_sharding())
                    edges_d = jax.device_put(edges_d, cloud.replicated())
                    margins = jax.device_put(margins, cloud.row_sharding())

        cfg = self._make_step_cfg(tp, npad, K, F, nbins, problem, dist,
                                  pack_bits=resident_bits,
                                  shard_mode=shard_mode, n_shards=n_shards)
        if ooc_blocks and cfg.compact_cap:
            # the streamed level loop is dense-only; deep streamed fits
            # keep exactness by skipping active-node compaction (the
            # in-core comparator must match)
            cfg = cfg._replace(compact_cap=0)
        # ---- the histogram kernel's code operand, once a fit ---------------
        # The codes do not change during a fit, so where the plan runs the
        # Pallas kernel on one device (`_cfg_operand_form`) ONE program
        # widens them here into the kernel's feature-major float32 operand
        # and every tree program takes that as an argument; a tree program
        # that widened for itself did the same 1.3 GB of work every tree
        # (225 of a 456 ms HIGGS tree). Dispatched, not waited for: the
        # device builds it while the host goes on. It is this fit's alone —
        # the resident, cached artefact stays the packed `codes_d` — and
        # goes with the fit's other device state.
        codes_arg = codes_d
        operand_bytes = 0
        if cfg.code_operand == "fit":
            _ph.stage("design.operand")
            operand_d = _hist.build_code_operand(
                codes_d, cfg.pack_bits, _cfg_operand_form(cfg)["row_chunk"])
            operand_bytes = int(operand_d.nbytes)
            _ph.stage_span.annotate(bits=cfg.pack_bits, bytes=operand_bytes)
            codes_arg = (codes_d, operand_d)

        # validation margins tracked incrementally per tree (the Score pass of
        # SharedTree.Driver on the validation frame) — early stopping uses the
        # validation metric when a validation_frame is given (ScoreKeeper).
        # Built AFTER the checkpoint block so codes_v uses the active binning
        # and margins_v is fast-forwarded through the restored forest.
        valid_state = None
        if valid is not None:
            _ph.stage("design.validation")
            Xv, _, _ = frame_to_matrix(valid, x, expected_domains=bm.domains)
            codes_np_v = bin_apply(bm, Xv)
            yvv = valid.vec(y)
            n_v = valid.nrow          # LOCAL valid rows on a multi-proc cloud
            if problem == "regression":
                ykv = yvv.numeric_np().astype(np.float32)[:, None]
            elif problem == "binomial":
                ykv = np.asarray(yvv.data, np.float32)[:, None]
            else:
                cv = np.asarray(yvv.data)
                ykv = np.zeros((n_v, K), np.float32)
                ykv[np.arange(n_v), cv] = 1.0
            if multiproc:
                # each process scores its ingest shard of the valid frame;
                # metric pieces are globally reduced in _score_event
                quota_v = distdata.local_quota(n_v)
                codes_v = distdata.global_row_array(codes_np_v, quota_v,
                                                    cloud)
                y_dev_v = distdata.global_row_array(ykv, quota_v, cloud)
                vmask_d = distdata.global_row_array(
                    np.ones(n_v, np.float32), quota_v, cloud)
                rs_v = cloud.row_sharding()
                margins_v = jax.jit(
                    lambda f: jnp.broadcast_to(
                        f[None, :],
                        (quota_v * jax.process_count(), K)
                    ).astype(jnp.float32),
                    out_shardings=rs_v)(np.asarray(f0).reshape(-1))
            else:
                codes_v = _phases_acct.accounted_h2d(
                    lambda: jnp.asarray(codes_np_v), codes_np_v.nbytes)
                y_dev_v = jnp.asarray(ykv)
                vmask_d = jnp.ones(n_v, jnp.float32)
                margins_v = jnp.broadcast_to(
                    jnp.asarray(np.asarray(f0).reshape(-1))[None, :],
                    (n_v, K)).astype(jnp.float32)
            if n_prior:
                for k in range(K):
                    forest_k = (prior_replicated[k] if multiproc else
                                jax.tree.map(jnp.asarray, prior_stacked[k]))
                    margins_v = _margin_ffwd_jit(
                        forest_k, codes_v, margins_v, jnp.int32(k),
                        tp["max_depth"])
            if self._parms.get("offset_column") and self._parms["offset_column"] in valid.names:
                off_v = valid.vec(self._parms["offset_column"]).numeric_np().astype(np.float32)
                if multiproc:
                    off_g = distdata.global_row_array(off_v, quota_v, cloud)
                    margins_v = jax.jit(lambda m, o: m + o[:, None],
                                        out_shardings=rs_v)(margins_v, off_g)
                else:
                    margins_v = margins_v + jnp.asarray(off_v)[:, None]
            # slot 1 deliberately None: the host ykv copy it used to hold is
            # superseded by the device y_dev_v (slot 4); indices are stable
            valid_state = [codes_v, None, margins_v, n_v, y_dev_v, vmask_d]

        _ph.mark("device_put", then="fit.iterate")
        _ph.stage("iterate.setup")
        key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
        ntrees_target = max(int(tp["ntrees"]) - n_prior, 0)
        gain_total = np.zeros(F, np.float64)
        stopper = (
            ScoreKeeper(
                int(self._parms.get("stopping_rounds", 0)),
                self._default_stopping_metric(problem),
                float(self._parms.get("stopping_tolerance", 1e-3)),
            )
            if int(self._parms.get("stopping_rounds", 0)) > 0
            else None
        )
        score_interval = int(self._parms.get("score_tree_interval", 0) or 0)
        lr = tp["learn_rate"] if self._mode == "gbm" else 1.0
        max_runtime = float(self._parms.get("max_runtime_secs", 0) or 0)
        t0 = time.time()
        history: List[Dict] = []
        built = 0

        # ---- ONE jitted program per boosting iteration -------------------
        # Per-call overhead matters: every dispatch pays a host round-trip,
        # so sampling, gradients, the K tree builds,
        # and the margin updates are fused into a single XLA program — the
        # analog of the fused ScoreBuildHistogram2 pass (hex/tree/
        # ScoreBuildHistogram2.java fuses scoring into histogram building).
        colp = tp["col_sample_rate"] * tp["col_sample_rate_per_tree"]
        custom_obj = getattr(self, "_objective_fn", None)
        mono_vec = getattr(self, "_monotone_vec", None)
        # per-fit kernel plan (ISSUE 7 satellite): resolve + record which
        # histogram kernel each level will actually run (method, pallas
        # row_chunk, VMEM-pressure fallbacks — logged once per fit) into
        # the metrics registry and the /3/Profiler `tree` fold, so the
        # auto-dispatch is never guesswork. Shares resolve_method with
        # build_histograms, so the plan cannot diverge from reality.
        if cfg.grow_policy != "lossguide":
            plan_levels = treelib.histogram_level_plan(cfg.max_depth,
                                                      cfg.compact_cap)
            plan_read = treelib.partition_read(F, cfg.code_operand)
        else:
            plan_levels = [("lossguide_node", 1)]
            plan_read = None
        plan_tag = (f"{getattr(self, 'algo', self._mode)}:{K}x{tp['ntrees']}t"
                    f"_d{cfg.max_depth}")
        _record_fit_plan(
            plan_tag, plan_levels, nbins, cfg.hist_method,
            pack_bits=cfg.pack_bits,
            n_shards=cfg.n_shards, n_devices=ndev_eff,
            partition_read=plan_read,
            rank=getattr(custom_obj, "rank_plan", None),
            code_operand=cfg.code_operand, operand_bytes=operand_bytes)
        # per-lane collective skew of THIS fit (ISSUE 13): fences recorded
        # after this sequence point belong to this fit (training is
        # serialized on meshes via training_guard)
        lane_seq0 = cloudlib.lane_seq()
        # fit trace span: a dashboard reading /3/Trace sees how many chips
        # (and reduction blocks) this fit actually spanned
        _ph.fit_span.annotate(n_devices=ndev_eff, n_shards=cfg.n_shards,
                              pack_bits=cfg.pack_bits,
                              shard_mode=cfg.shard_mode)
        # sharded fits score through the blocked deterministic loss (the
        # early-stop decision must be bit-stable across device counts);
        # unsharded fits keep the historical whole-array reduction
        loss_fn = None
        if cfg.shard_mode in ("mesh", "blocks"):
            loss_fn = _sharded_event_loss_fn(
                cloud, cfg.shard_mode, cfg.n_shards, self._mode, problem,
                dist)
        if warm_thread is not None:
            warm_thread.join()
        stream0 = None
        if ooc_blocks:
            # the streamed out-of-core step replaces the monolithic jitted
            # tree program: same call contract, per-block programs inside
            # (models/tree_stream.py). custom objectives / DART / compact
            # never reach here (gated in _ooc_plan), so _single_jit is
            # unused on this path.
            from . import tree_stream as _tstream

            _tree_jit = _tstream.StreamedTreeStep(cfg, ooc_store,
                                                  seed=seed, goss=goss_cfg)
            _single_jit = None
            stream0 = dict(ooc_store.counters)
            ooc_store.peak_window_start()   # THIS fit's resident watermark
        else:
            _tree_jit, _single_jit = _tree_step_fns(cfg, cloud)
        mono_d = (jnp.asarray(mono_vec) if mono_vec is not None
                  else jnp.zeros(F, jnp.float32))
        hp_d = _pack_hp(
            tp, lr, colp,
            mtries_rate=self._resolved_mtries(tp, F, problem) / max(F, 1))
        if multiproc:
            # small per-call args go in as host numpy (identical on every
            # process ⇒ jit replicates them); locally-committed jnp arrays
            # would carry a single-device sharding the global mesh rejects
            mono_d = np.asarray(mono_d)
            hp_d = np.asarray(hp_d)
            key = np.asarray(key)

        def _train_chunk(margins, oob_sum, oob_cnt, key, m0, nsteps: int,
                         tree_fn=None):
            """nsteps async per-tree dispatches (NOT lax.scan: a scan body
            defeats XLA's onehot→reduction fusion and materializes the
            (rows × nodes·bins) one-hot in HBM, ~300× slower; sequential
            cached-jit enqueues pipeline on device with ~µs host overhead)."""
            tree_fn = tree_fn or _tree_jit
            packed_list, gains_list, ov_list = [], [], []
            for i in range(nsteps):
                margins, oob_sum, oob_cnt, packed, gains, ov = tree_fn(
                    margins, oob_sum, oob_cnt, codes_arg, y_d, w_d, rate_d,
                    edges_d, mono_d, hp_d, key, np.int32(m0 + i)
                )
                # CPU mesh: one collective executable in flight at a time
                cloudlib.collective_fence(margins)
                packed_list.append(packed)
                gains_list.append(gains)
                ov_list.append(ov)
            # jitted combine only on multi-host meshes (eager stack/sum
            # would reject process-spanning arrays there). Single-process
            # stays EAGER: a jitted multi-arg combine has been observed to
            # interleave with in-flight collective tree programs on the
            # XLA:CPU thunk pool and deadlock the all-reduce rendezvous.
            if distdata.multiprocess() or ndev_eff == 1:
                # single REAL device has no collective programs in flight,
                # so the jitted combine is safe there too — and it turns
                # ~2·nsteps eager dispatches per chunk (each paying the
                # host↔device latency) into three
                return (margins, oob_sum, oob_cnt,
                        _stack_args(*packed_list), _sum_args(*gains_list),
                        _sum_args(*ov_list))
            return (margins, oob_sum, oob_cnt,
                    jnp.stack(packed_list), sum(gains_list), sum(ov_list))

        # chunking: one device dispatch per `chunk` trees (remote dispatch
        # latency amortization); scoring/stopping checks at chunk boundaries
        need_host_each = (
            custom_obj is not None
            or bool(self._parms.get("score_each_iteration"))
        )
        dart = tp.get("dart")
        if need_host_each or dart:
            chunk = 1
        elif score_interval:
            chunk = score_interval
        elif stopper is not None:
            chunk = max(1, min(10, ntrees_target))
        else:
            chunk = min(25, max(ntrees_target, 1))

        m = 0
        # per-row sampling rate: constant sample_rate, or per-class rates
        # (sample_rate_per_class, hex/tree SharedTree class sampling)
        srpc = self._parms.get("sample_rate_per_class")
        if srpc and problem not in ("binomial", "multinomial"):
            raise ValueError("sample_rate_per_class requires a categorical "
                             "response (classification only)")
        if srpc:
            rates_np = np.asarray(list(srpc), np.float32)
            if len(rates_np) != nclass:
                raise ValueError(
                    f"sample_rate_per_class needs {nclass} entries, got {len(rates_np)}")
            rate_rows = rates_np[np.asarray(yvec.data, np.int64)]
            rate_d = (distdata.global_row_array(
                          padr(rate_rows.astype(np.float32)), quota, cloud)
                      if multiproc
                      else jnp.asarray(padr(rate_rows.astype(np.float32))))
        elif multiproc:
            rate_d = distdata.sharded_full(
                (npad,), np.float32(tp["sample_rate"]), jnp.float32, cloud)
        else:
            rate_d = jnp.full(npad, np.float32(tp["sample_rate"]))
        row_sampled = tp["sample_rate"] < 1.0 or bool(srpc)
        if ndev_eff > 1 and not multiproc:
            rate_d = jax.device_put(rate_d, cloud.row_sharding())
        # DRF OOB accumulators (out-of-bag prediction sums / counts per row)
        if self._mode == "drf":
            if multiproc:
                oob_sum = distdata.sharded_full((npad, K), 0.0, jnp.float32,
                                                cloud)
                oob_cnt = distdata.sharded_full((npad,), 0.0, jnp.float32,
                                                cloud)
            else:
                oob_sum = jnp.zeros((npad, K), jnp.float32)
                oob_cnt = jnp.zeros(npad, jnp.float32)
                if ndev_eff > 1:
                    oob_sum = jax.device_put(oob_sum, cloud.row_sharding())
                    oob_cnt = jax.device_put(oob_cnt, cloud.row_sharding())
        elif multiproc:
            # unused placeholders, replicated via implicit np conversion
            oob_sum = np.zeros((1, K), np.float32)
            oob_cnt = np.zeros(1, np.float32)
        else:
            oob_sum = jnp.zeros((1, K), jnp.float32)  # unused placeholder
            oob_cnt = jnp.zeros(1, jnp.float32)
        packed_chunks: List = []   # device-resident (nsteps, K, T, 5) arrays
        gains_chunks: List = []    # device-resident (F,) arrays
        packed_host: List = []     # flushed-to-host chunks (OOM guard)
        dev_bytes = 0
        # deep forests (heap 2^(d+1) nodes × 5 fields × K) can exceed HBM if
        # the whole run stays device-resident — flush to host past this
        # budget. Generous by default (a v5e chip has 16 GB): a flush
        # costs minutes of D2H, an HBM-resident pack costs bytes
        _PACK_BUDGET = int(os.environ.get("H2O3_PACK_BUDGET_MB", 4096)) << 20

        def _flush_packed():
            nonlocal dev_bytes
            for pk in packed_chunks:
                packed_host.append(np.asarray(pk))
            packed_chunks.clear()
            dev_bytes = 0
        # custom objective on a multi-process cloud: the gathered-global
        # response and this rank's row offset are loop-invariant
        _y_glob_d = None
        _row_off = 0
        _row_counts = None
        _nn_loc = n
        if custom_obj is not None and multiproc:
            import jax as _jax

            if pod:
                # canonical slices concatenated in rank order ARE the
                # global ingest order — gather/scatter below need no
                # reordering, only the canonical per-rank counts
                _row_counts = distdata.canonical_counts(_counts, npad)
                _nn_loc = int(_row_counts[_jax.process_index()])
            else:
                _row_counts = distdata.row_counts(n)
            y_loc = distdata.to_local(y_d)[:_nn_loc]
            y_loc = (y_loc[:, 0] if y_loc.ndim == 2 else y_loc)
            _y_glob_d = jnp.asarray(
                distdata.allgather_rows(np.asarray(y_loc, np.float32)))
            _row_off = int(_row_counts[: _jax.process_index()].sum())
        # DART per-round state: one stored-contribution scale per committed
        # round (host floats), a dedicated RNG (deterministic from seed)
        dart_scales: List[float] = []
        dart_rng = np.random.default_rng(
            (int(self._parms["_actual_seed"]) + 7919) & 0x7FFFFFFF)

        def _run_chunk(margins, oob_sum, oob_cnt, m0, nsteps):
            """One chunk of tree dispatches, incl. the compact-cap
            overflow-rebuild guard (exactness is never traded)."""
            if cfg.compact_cap:
                # snapshot the mutable (donated) state: if any tree in the
                # chunk overflows the compact-slot cap, the chunk is
                # rebuilt DENSELY from here
                snap = _copy_args(margins, oob_sum, oob_cnt)
            margins, oob_sum, oob_cnt, packed, gains, ov = _train_chunk(
                margins, oob_sum, oob_cnt, key, m0, nsteps=nsteps)
            if cfg.compact_cap and int(np.asarray(ov)) > 0:
                from ..runtime.log import Log

                Log.warn(
                    f"tree chunk at m={m0}: compact-node cap "
                    f"{cfg.compact_cap} overflowed — rebuilding the "
                    "chunk with dense levels")
                dense_jit, _ = _tree_step_fns(
                    cfg._replace(compact_cap=0), cloud)
                margins, oob_sum, oob_cnt = snap
                margins, oob_sum, oob_cnt, packed, gains, _ = _train_chunk(
                    margins, oob_sum, oob_cnt, key, m0, nsteps=nsteps,
                    tree_fn=dense_jit)
            return margins, oob_sum, oob_cnt, packed, gains

        # ---- mid-fit checkpointing (ISSUE 20 tentpole) -------------------
        # Snapshot the LIVE loop state every H2O3_CKPT_TREES trees so a
        # killed or aborted fit resumes here instead of at tree 0. The
        # saved margins/OOB/gain arrays are the exact f32 values — a
        # forest fast-forward (`_margin_ffwd_jit`) rounds differently than
        # the incremental per-tree adds, and resume must be BIT-IDENTICAL
        # to the undisturbed fit. Per-rank shards are saved in the pod
        # canonical layout, so a fit that lost ranks restores on ONE host
        # by rank-ordered concatenation (the degrade path), with the shard
        # plan S pinned in the run fingerprint. Gated off for paths whose
        # loop state lives elsewhere (DART round scales, custom-objective
        # host state, out-of-core streams, checkpoint= continuations);
        # H2O3_CKPT=0 disables everything (bit-identical escape hatch).
        from ..runtime import faults as _rfaults
        from ..runtime import supervisor as _sup

        ckpt_fp = None
        ckpt_every = _sup.ckpt_every_trees()
        ckpt_dirp = _sup.ckpt_dir()
        ckpt_rank = jax.process_index() if multiproc else 0
        ckpt_nproc = jax.process_count() if multiproc else 1
        if (_sup.ckpt_enabled() and ckpt_dirp and not dart
                and custom_obj is None and not ooc_blocks
                and not prior_stacked):
            _glob_rows = (int(_counts.sum()) if pod
                          else (npad if multiproc else n))
            ckpt_fp = _sup.run_fingerprint(
                mode=self._mode, problem=problem, cols=list(x), y=y,
                rows=int(_glob_rows), npad=int(npad), K=int(K), F=int(F),
                nbins=int(nbins), seed=int(seed),
                n_shards=int(cfg.n_shards), ntrees=int(tp["ntrees"]),
                max_depth=int(tp["max_depth"]),
                learn_rate=float(tp.get("learn_rate") or 0.0),
                sample_rate=float(tp.get("sample_rate") or 1.0),
                col_sample=float(colp),
                min_rows=float(tp.get("min_rows") or 1.0),
                dist=str(dist), has_valid=valid_state is not None)

        def _save_fit_ckpt():
            """Commit one snapshot: forest-so-far + f32 gain partial sum
            (restored as gains_chunks[0], the same left-fold prefix) +
            live margins/OOB local shards + scoring history + early-stop
            cursor. The .part+rename commit and torn-write rejection live
            in runtime/supervisor."""
            _flush_packed()
            all_p = (packed_host[0] if len(packed_host) == 1
                     else np.concatenate(packed_host, axis=0))
            gacc = None
            for g in gains_chunks:
                gh = np.asarray(g, np.float32)
                gacc = gh if gacc is None else gacc + gh
            arrays = dict(
                packed=all_p,
                gains=(gacc if gacc is not None
                       else np.zeros(F, np.float32)),
                margins=(distdata.to_local(margins) if multiproc
                         else np.asarray(margins)))
            if self._mode == "drf":
                arrays["oob_sum"] = (distdata.to_local(oob_sum)
                                     if multiproc else np.asarray(oob_sum))
                arrays["oob_cnt"] = (distdata.to_local(oob_cnt)
                                     if multiproc else np.asarray(oob_cnt))
            if valid_state is not None:
                arrays["margins_v"] = (
                    distdata.to_local(valid_state[2]) if multiproc
                    else np.asarray(valid_state[2]))
            meta = dict(
                history=history,
                stopper_history=(list(stopper.history)
                                 if stopper is not None else None),
                has_valid=valid_state is not None, npad=int(npad),
                n_shards=int(cfg.n_shards))
            _sup.save_fit_checkpoint(
                ckpt_dirp, "tree", ckpt_fp, built, arrays, meta,
                rank=ckpt_rank, nproc=ckpt_nproc)

        if ckpt_fp is not None:
            rec = _sup.latest_fit_checkpoint(ckpt_dirp, "tree", ckpt_fp)
            ok = (rec is not None and 0 < rec["step"] <= ntrees_target
                  and (rec["nproc"] == ckpt_nproc
                       or (ckpt_nproc == 1
                           and not rec["meta"].get("has_valid"))))
            if multiproc:
                # consensus: every rank restores the same snapshot or none
                # (a rank-divergent restore would deadlock the collectives)
                ok = distdata.global_all(bool(ok))
            if ok:
                sh = rec["shards"]
                meta0 = rec["meta"]

                def _rows_back(name):
                    """One checkpointed row-sharded state array back onto
                    the CURRENT topology: same-nproc ranks recommit their
                    own shard; a shrunken (1-host) resume concatenates the
                    rank shards — canonical layout makes that the global
                    padded array."""
                    if multiproc and rec["nproc"] == ckpt_nproc:
                        return distdata.global_row_array(
                            sh[ckpt_rank][name], quota, cloud)
                    a = (sh[0][name] if rec["nproc"] == 1 else
                         np.concatenate([s[name] for s in sh], axis=0))
                    a = jnp.asarray(a)
                    if ndev_eff > 1:
                        a = jax.device_put(a, cloud.row_sharding())
                    return a

                margins = _rows_back("margins")
                if self._mode == "drf":
                    oob_sum = _rows_back("oob_sum")
                    oob_cnt = _rows_back("oob_cnt")
                if valid_state is not None and "margins_v" in sh[0]:
                    if multiproc:
                        valid_state[2] = distdata.global_row_array(
                            sh[ckpt_rank]["margins_v"], quota_v, cloud)
                    else:
                        valid_state[2] = jnp.asarray(sh[0]["margins_v"])
                # forest + gain prefix are replicated — rank 0's copy is
                # everyone's copy
                packed_host.append(np.asarray(sh[0]["packed"], np.float32))
                gains_chunks.append(np.asarray(sh[0]["gains"], np.float32))
                history.extend(meta0.get("history") or [])
                if stopper is not None and meta0.get("stopper_history"):
                    stopper.history = [
                        float(v) for v in meta0["stopper_history"]]
                m = built = int(rec["step"])
                _sup.note_mid_fit_resume("tree", m, restored=m)
        ckpt_last_m = built
        _sup.fit_started("tree", ckpt_fp or "", ntrees_target)

        # overlapped chunk scoring (ISSUE 7 tentpole part 3): double-buffer
        # — chunk m+1's tree programs are ENQUEUED while chunk m's metric
        # transfers and evaluates, so the device stays busy through
        # score_tree_interval instead of idling at every chunk boundary.
        # Gated to paths whose scoring event runs on device (the DRF OOB
        # event pulls host arrays) and OFF under DART and custom
        # objectives (inherently host-synced, chunk=1) and
        # compact-cap fits (their overflow-flag pull is a host sync, so a
        # "speculative" chunk would complete synchronously before the stop
        # decision — strictly worse than the sequential path).
        # (Out-of-core fits skip chunk-level speculation: the streamed step
        # is host-driven, so a "speculative" chunk would consume real
        # stream bandwidth synchronously before the stop decision — the
        # double buffer lives INSIDE its level loop instead.)
        # (checkpointing also disables overlap: the speculative chunk
        # donates the very margins buffers the snapshot saver reads)
        overlap = (not multiproc
                   and custom_obj is None and not dart
                   and not cfg.compact_cap and not ooc_blocks
                   and not (self._mode == "drf" and row_sampled)
                   and ckpt_fp is None
                   and os.environ.get("H2O3_TREE_OVERLAP", "1") != "0")
        spec = None        # speculatively dispatched next chunk (+ nsteps)
        spec_snap = None   # pre-dispatch state copies (its buffers donate)

        def _discard_spec():
            """Abandon the speculative chunk on an early stop: restore the
            pre-dispatch state copies (the spec's programs donated the live
            buffers) and drop its outputs — trees past the stopping point
            vanish exactly as if the sequential path never built them."""
            nonlocal spec, margins, oob_sum, oob_cnt
            if spec is not None:
                margins, oob_sum, oob_cnt = spec_snap
                spec = None

        _ph.stage("iterate.dispatch")
        while m < ntrees_target:
            # QoS chunk-boundary yield: while a serving dispatch is in
            # flight the next chunk's programs hold back here.
            _qos.yield_point("tree_chunk")
            # in-process candidate-crash injection (kill-and-resume pins)
            # + supervisor heartbeat: liveness at every chunk boundary
            _rfaults.check("supervisor.fit_abort", detail=f"m={m}")
            _sup.pulse("tree", m)
            nsteps = min(chunk, ntrees_target - m)
            drop_idx = ()
            dsum = dsum_v = None
            if dart and m > 0 and dart_rng.random() >= dart["skip_drop"]:
                mask = dart_rng.random(m) < dart["rate_drop"]
                if dart["one_drop"] and not mask.any():
                    mask[int(dart_rng.integers(0, m))] = True
                drop_idx = tuple(int(i) for i in np.nonzero(mask)[0])
            if drop_idx:
                # margins_eff = margins − Σ dropped rounds (this run's
                # rounds only; a checkpointed prior forest stays frozen).
                # Host-side selection of the dropped round packs keeps the
                # device work O(dropped); padded to pow2 (zero scales) to
                # bound program variants.
                nb = 1 << (len(drop_idx) - 1).bit_length()
                sel_chunks = tuple(packed_chunks[i] for i in drop_idx)
                sel_chunks += (packed_chunks[drop_idx[0]],) * (
                    nb - len(drop_idx))
                sc = np.zeros(nb, np.float32)
                sc[: len(drop_idx)] = [dart_scales[i] for i in drop_idx]
                sc_d = jnp.asarray(sc)
                dsum = _dart_drop_sum_jit(sel_chunks, sc_d, codes_d,
                                          tp["max_depth"])
                margins = _dart_sub_jit(margins, dsum)
                cloudlib.collective_fence(margins)
                if valid_state is not None:
                    dsum_v = _dart_drop_sum_jit(sel_chunks, sc_d,
                                                valid_state[0],
                                                tp["max_depth"])
            if custom_obj is not None:
                # Custom-objective contract (cloud-size-agnostic, the
                # reference's MRTask stance for hex/tree/SharedTree.java):
                # the objective sees the GLOBAL rows in global row order —
                # margin vector in, (g, h) vectors out, all length
                # N_global. On multi-process clouds the driver gathers the
                # margins host-side (N·4 bytes per rank per round), every
                # rank runs the objective on identical inputs, and each
                # rank scatters back its own row range. Per-query host
                # structures (lambdarank) therefore see whole queries even
                # when they span ingest-shard boundaries.
                if multiproc:
                    m_loc = distdata.to_local(margins)[:_nn_loc]
                    m_loc = (m_loc[:, 0] if m_loc.ndim == 2
                             else m_loc).astype(np.float32)
                    # fixed-size gather: ONE collective per round (counts
                    # are loop-invariant, gathered once above)
                    m_glob = distdata.allgather_rows_padded(
                        m_loc, quota, _row_counts)
                    g_g, h_g = custom_obj(jnp.asarray(m_glob), _y_glob_d)
                    g_g = np.asarray(g_g)[_row_off: _row_off + _nn_loc]
                    h_g = np.asarray(h_g)[_row_off: _row_off + _nn_loc]
                    if pod:
                        # rows are already this rank's canonical slice —
                        # global_row_array pads to quota, no exchange
                        g_ext = distdata.global_row_array(
                            g_g.astype(np.float32), quota, cloud)
                        h_ext = distdata.global_row_array(
                            h_g.astype(np.float32), quota, cloud)
                    else:
                        g_ext = distdata.global_row_array(
                            padr(g_g.astype(np.float32)), quota, cloud)
                        h_ext = distdata.global_row_array(
                            padr(h_g.astype(np.float32)), quota, cloud)
                else:
                    g_ext, h_ext = custom_obj(margins[:, 0], y_d[:, 0])
                margins, packed, gains = _single_jit(
                    margins, codes_arg, y_d, w_d, rate_d, edges_d, mono_d,
                    hp_d, key, jnp.int32(m), g_ext, h_ext
                )
                cloudlib.collective_fence(margins)
                packed = packed[None]
                nsteps = 1
            elif spec is not None:
                # consume the chunk dispatched while the PREVIOUS chunk's
                # metric was in flight (overlapped chunk scoring)
                margins, oob_sum, oob_cnt, packed, gains, nsteps = spec
                spec = None
            else:
                margins, oob_sum, oob_cnt, packed, gains = _run_chunk(
                    margins, oob_sum, oob_cnt, m, nsteps)
            # chunks stay on device until the post-loop bulk D2H (sync
            # transfers are not free), unless the accumulated forest would
            # blow the HBM budget
            packed_chunks.append(packed)
            gains_chunks.append(gains)
            dev_bytes += int(np.prod(packed.shape)) * 4
            if dev_bytes > _PACK_BUDGET and not dart:
                # dart never flushes: dropout selection needs every prior
                # round on device (dart forests are shallow/small)
                _flush_packed()
            if valid_state is not None:
                for k in range(K):
                    valid_state[2] = _valid_margin_update(
                        packed, valid_state[0], valid_state[2],
                        jnp.int32(k), tp["max_depth"])
                cloudlib.collective_fence(valid_state[2])
            if dart:
                k_d = len(drop_idx)
                if k_d:
                    lr = tp["learn_rate"]
                    if dart["normalize_type"] == "forest":
                        fd = fn = 1.0 / (1.0 + lr)
                    else:                      # "tree"
                        fd = k_d / (k_d + lr)
                        fn = 1.0 / (k_d + lr)
                    margins = _dart_fix_jit(
                        margins, packed, dsum, codes_d,
                        jnp.float32(fn - 1.0), jnp.float32(fd),
                        tp["max_depth"])
                    cloudlib.collective_fence(margins)
                    if valid_state is not None:
                        valid_state[2] = _dart_fix_jit(
                            valid_state[2], packed, dsum_v, valid_state[0],
                            jnp.float32(fn - 1.0), jnp.float32(fd - 1.0),
                            tp["max_depth"])
                        cloudlib.collective_fence(valid_state[2])
                    for i in drop_idx:
                        dart_scales[i] *= fd
                    dart_scales.append(fn)
                else:
                    dart_scales.append(1.0)
            m += nsteps
            built = m

            do_score = (
                (score_interval and built % score_interval == 0)
                or self._parms.get("score_each_iteration")
                or (stopper is not None and not score_interval)
            )
            # REST job cancellation takes effect at scoring boundaries —
            # single-process only (a per-rank host decision would diverge a
            # multi-process cloud)
            if (self.job is not None and jax.process_count() == 1):
                self.job.check_cancelled()
            if do_score:
                if self._mode == "drf" and row_sampled and n_prior == 0:
                    # score on OOB predictions (DRF scoring history is OOB;
                    # pulls host arrays — stays synchronous, overlap off).
                    # unpadr restores INGEST order on pods; the host event
                    # path pairs the means with the local response, so pass
                    # the host yk (identical values to y_d, same layout).
                    osum = unpadr(distdata.to_local(oob_sum)).astype(np.float64)
                    ocnt = unpadr(distdata.to_local(oob_cnt)).astype(np.float64)
                    have = ocnt > 0
                    mnp = unpadr(distdata.to_local(margins)).astype(np.float64)
                    oob_mean = np.where(have[:, None],
                                        osum / np.maximum(ocnt[:, None], 1.0),
                                        mnp / max(built, 1))
                    ev0 = self._score_event(problem, dist,
                                            oob_mean * max(built, 1),
                                            yk, w_d, n, built + n_prior)
                    fin = lambda ev0=ev0: ev0
                else:
                    # ENQUEUE the device loss program(s) now; block later
                    fin = self._score_event_async(
                        problem, dist, margins, y_d, w_d, n,
                        built + n_prior, row_mask=row_mask_d,
                        loss_fn=loss_fn)
                vfin = None
                if valid_state is not None:
                    vfin = self._score_event_async(
                        problem, dist, valid_state[2],
                        valid_state[4], None, valid_state[3],
                        built + n_prior, row_mask=valid_state[5],
                    )
                if overlap and m < ntrees_target:
                    # double-buffer: enqueue chunk m+1's tree programs
                    # BEFORE blocking on chunk m's metric scalar — the
                    # device crunches the next chunk through the host's
                    # metric wait + stopping decision. If the decision is
                    # "stop", the speculative chunk is discarded and the
                    # pre-dispatch state restored (bit-exact either way).
                    if stopper is not None or max_runtime:
                        spec_snap = _copy_args(margins, oob_sum, oob_cnt)
                    sp_n = min(chunk, ntrees_target - m)
                    spec = _run_chunk(margins, oob_sum, oob_cnt,
                                      m, sp_n) + (sp_n,)
                ev = fin()
                if vfin is not None:
                    vev = vfin()
                    ev.update({f"validation_{k2}": v for k2, v in vev.items()
                               if k2 not in ("number_of_trees", "timestamp")})
                history.append(ev)
                if stopper is not None:
                    # ScoreKeeper watches validation when present (hex.ScoreKeeper)
                    key_name = (
                        f"validation_{stopper.metric}"
                        if valid_state is not None else stopper.metric
                    )
                    val = ev.get(key_name)
                    if val is None:
                        val = ev.get(
                            "validation_training_deviance"
                            if valid_state is not None else "training_deviance",
                            np.nan,
                        )
                    if stopper.record(val):
                        _discard_spec()
                        break
            if max_runtime:
                # clock consensus: every rank must take the same branch or
                # the next chunk's collectives deadlock
                if distdata.global_any(time.time() - t0 > max_runtime):
                    _discard_spec()
                    break
            if self.job:
                self.job.update(built / max(ntrees_target, 1))
            if (ckpt_fp is not None and m < ntrees_target
                    and built - ckpt_last_m >= ckpt_every):
                # cadenced snapshot — never after a stopper break (resume
                # replays the final chunk deterministically instead)
                _save_fit_ckpt()
                ckpt_last_m = built

        _sup.fit_finished("tree")
        _ph.stage("iterate.forest")
        if dart:
            # bake the per-round DART scales into the stored leaf values so
            # scoring / MOJO / TreeSHAP see ordinary trees (xgboost keeps a
            # parallel weight_drop vector; baking is equivalent and keeps
            # every downstream surface unchanged)
            for i, s in enumerate(dart_scales[: len(packed_chunks)]):
                if s != 1.0:
                    packed_chunks[i] = _dart_scale_jit(packed_chunks[i],
                                                       jnp.float32(s))

        # ---- forest stays ON DEVICE; host materialization is lazy --------
        # Deep heaps are big (depth-18 ⇒ 12.6 MB/tree) and D2H is the slow
        # path — an eager D2H of a deep DRF forest can dominate training
        # (device cost not measured on today's code). The packed array is kept in HBM;
        # `.forest` (mojo/save/tree-API consumers) pulls it to host on
        # first access. Fallbacks to the eager host path: checkpoint
        # continuation (needs host concat with the prior forest), multi-host
        # meshes, and over-budget runs that already flushed chunks.
        packed_dev = None
        if packed_chunks and not packed_host and not prior_stacked \
                and not multiproc and ndev == 1:
            # single-device only: on a multi-device mesh the pack becomes a
            # multi-device array whose later (scoring/eviction) executions
            # can interleave with the next model's COLLECTIVE tree programs —
            # XLA:CPU runs concurrent executions on one thunk pool and the
            # all-reduce rendezvous deadlocks (observed: 7/8 participants).
            # Multi-device hosts also have fast local D2H, so the eager host
            # path costs little there; the pack exists for the single
            # device whose D2H is the slow path.
            _ph.mark("train_loop_dispatch")
            packed_dev = (packed_chunks[0] if len(packed_chunks) == 1
                          else _concat_args(*packed_chunks))
            packed_chunks.clear()
            all_packed = None
            _ph.mark("forest_devkeep")
            gain_total += np.asarray(sum(gains_chunks), np.float64)
        elif packed_chunks or packed_host:
            _ph.mark("train_loop_dispatch")
            # remaining device chunks: single device-side concat + ONE D2H
            # (per-chunk sync transfers only happen on over-budget flushes)
            if packed_chunks:
                if multiproc:
                    # eager concat of process-spanning arrays needs jit;
                    # chunks are replicated, so host concat is equivalent
                    packed_host.extend(np.asarray(pk) for pk in packed_chunks)
                else:
                    rest = (packed_chunks[0] if len(packed_chunks) == 1
                            else jnp.concatenate(packed_chunks, axis=0))
                    packed_host.append(np.asarray(rest))
                packed_chunks.clear()
            all_packed = (packed_host[0] if len(packed_host) == 1
                          else np.concatenate(packed_host, axis=0))
            _ph.mark("forest_D2H")
            if multiproc:
                # replicated chunks pull to host per-chunk (eager device sum
                # would need jit for process-spanning arrays), but the fold
                # stays f32 left-to-right like the single-process
                # `sum(gains_chunks)` so pod varimp is bit-identical to the
                # forced-shard comparator
                acc = None
                for g in gains_chunks:
                    gh = np.asarray(g, np.float32)
                    acc = gh if acc is None else acc + gh
                if acc is not None:
                    gain_total += np.asarray(acc, np.float64)
            else:
                gain_total += np.asarray(sum(gains_chunks), np.float64)
            _ph.mark("gains_D2H")
        else:
            all_packed = np.zeros((0, K, treelib.heap_size(tp["max_depth"]), 6),
                                  np.float32)
        _ph.stage("iterate.model")
        forest = None
        covers_by_class = None
        if packed_dev is None:
            # stacked forests sliced straight off the bulk array — no
            # per-tree host Trees, no 6×ntrees tiny H2D transfers
            forest = []
            covers_by_class = []
            prior_covers = getattr(pm, "covers", None) if prior_stacked else None
            for k in range(K):
                new = treelib.Tree(
                    np.ascontiguousarray(all_packed[:, k, :, 0]).astype(np.int32),
                    np.ascontiguousarray(all_packed[:, k, :, 1]).astype(np.int32),
                    np.ascontiguousarray(all_packed[:, k, :, 2]),
                    all_packed[:, k, :, 3] > 0.5,
                    np.ascontiguousarray(all_packed[:, k, :, 4]),
                )
                cov_k = np.ascontiguousarray(all_packed[:, k, :, 5])
                if prior_stacked:
                    prior = prior_stacked[k]
                    new = treelib.Tree(*[
                        np.concatenate([np.asarray(getattr(prior, f)),
                                        getattr(new, f)], axis=0)
                        for f in treelib.Tree._fields
                    ])
                    if prior_covers is not None and k < len(prior_covers):
                        cov_k = np.concatenate(
                            [np.asarray(prior_covers[k], np.float32), cov_k], axis=0)
                forest.append(new)
                covers_by_class.append(cov_k)
            if prior_stacked and prior_covers is None:
                # continued from a pre-TreeSHAP checkpoint: the prior trees
                # have no covers, so a partial covers array would misalign
                # with the forest — disable contributions for this model
                covers_by_class = None
        model = SharedTreeModel(
            self, x, y, bm, problem, nclass, domain, dist,
            np.asarray(f0) if K > 1 else float(f0[0]),
            forest, tp["max_depth"], mode=self._mode,
            packed_dev=packed_dev, nclasses_packed=K,
        )
        model._npad = npad  # CV passes this to folds as _npad_floor
        if packed_dev is None:
            model.covers = covers_by_class
        else:
            _register_dev_pack(model, _PACK_BUDGET)
        model.requested_max_depth = requested_depth  # pre-clamp user value
        model.balance_dists = balance_dists
        model.calibrator = None
        if self._parms.get("calibrate_model"):
            if problem != "binomial":
                raise ValueError("calibrate_model is only supported for "
                                 "binomial models")
            model.calibrator = self._fit_calibrator(model)
        model.scoring_history = ScoringHistory(history)
        if gain_total.sum() > 0:
            order = np.argsort(-gain_total)
            model.varimp_table = [
                (list(x)[i], float(gain_total[i]),
                 float(gain_total[i] / gain_total.max()),
                 float(gain_total[i] / gain_total.sum()))
                for i in order
            ]
        # training metrics straight from the final margins (already on device)
        # instead of a fresh forest re-predict — saves transfers + a compile
        _ph.mark("forest_unpack", then="fit.metrics")
        # sharded fits take the host metrics path: the binned reduction's
        # quantile sort and float32 sums are not bit-stable across device
        # counts, and the margins D2H is local on a CPU mesh
        device_auc = (not multiproc and problem == "binomial"
                      and dist == "bernoulli" and self._mode == "gbm"
                      and cfg.shard_mode not in ("mesh", "blocks"))
        if device_auc:
            # binomial GBM/XGB: the whole training-metric reduction runs on
            # device (AUC2 binned design) — no margin D2H, no host rank sort.
            # The stage holds the dispatch and the reads that wait for it;
            # its attrs say which form of the reduction ran
            _ph.stage("metrics.binned")
            _ph.stage_span.annotate(
                device=True, counts="edges",
                block_rows=_edge_count_block(margins.shape[0]))
            qs_b, npos_b, nneg_b, nll_b, sq_b = _binom_binned_stats(
                margins, y_d, jnp.int32(n))
            binned = (np.asarray(qs_b), np.asarray(npos_b),
                      np.asarray(nneg_b), float(nll_b), float(sq_b))
            _ph.mark("training_metrics")
        _ph.stage("metrics.margins")
        if multiproc:
            # this process's real rows in INGEST order (training metrics
            # are local-shard on a multi-host cloud; the forest itself is
            # identical everywhere; pods undo the canonical relayout first)
            margins_np = unpadr(
                distdata.local_shard(margins)).astype(np.float64)
        elif not device_auc or custom_obj is not None:
            margins_np = np.asarray(margins[:n]).astype(np.float64)
        _ph.mark("margins_D2H")
        if custom_obj is not None:
            # a custom objective's caller reads its own closing metric off
            # the same final margins (XGBoost ranking: NDCG), as the
            # training metrics below do, with no second matrix and no
            # re-predict; the caller takes the attribute and clears it
            self._final_margins = margins_np
        _ph.stage("metrics.make")
        if device_auc:
            model.training_metrics = ModelMetricsBinomial.from_binned(*binned)
        if self._mode == "drf" and row_sampled and n_prior > 0:
            # checkpoint continuation: the prior forest's per-tree sample
            # masks are gone, so OOB accounting cannot be reconstructed —
            # metrics fall back to in-bag; make the semantics change loud
            from ..runtime.log import Log

            Log.warn("DRF checkpoint continuation: training metrics are "
                     "in-bag (OOB state is not carried across checkpoints)")
        if self._mode == "drf" and row_sampled and n_prior == 0:
            # DRF training metrics are OUT-OF-BAG (DRF OOB scoring): each
            # row is scored only by trees that did not sample it; in-bag
            # margins back-fill rows every tree happened to include
            if multiproc:
                osum = unpadr(
                    distdata.local_shard(oob_sum)).astype(np.float64)
                ocnt = unpadr(
                    distdata.local_shard(oob_cnt)).astype(np.float64)
            else:
                osum = np.asarray(oob_sum[:n], np.float64)
                ocnt = np.asarray(oob_cnt[:n], np.float64)
            have = ocnt > 0
            oob_mean = np.where(
                have[:, None], osum / np.maximum(ocnt[:, None], 1.0),
                margins_np / max(model.ntrees_built, 1))
            # feed as "margins × ntrees" so probs_from_margins' ÷ntrees
            # reproduces the OOB mean
            probs_tr = self._probs_from_margins(
                problem, dist, oob_mean * max(model.ntrees_built, 1),
                model.ntrees_built)
        elif not device_auc:
            probs_tr = self._probs_from_margins(problem, dist, margins_np,
                                                model.ntrees_built)
        if not device_auc:
            model.training_metrics = _metrics_for(problem, train.vec(y),
                                                  probs_tr)
        _ph.mark("training_metrics")
        if valid is not None:
            _ph.stage("metrics.valid")
            if valid_state is not None and self._mode != "drf":
                # multiproc: local-shard validation metrics, matching the
                # local-shard training metrics above (forest is identical
                # on every rank; scoring history carried the global numbers)
                mv = (distdata.local_shard(valid_state[2])
                      if multiproc else np.asarray(valid_state[2]))
                mv = mv[:valid_state[3]].astype(np.float64)
                probs_v = self._probs_from_margins(problem, dist, mv,
                                                   model.ntrees_built)
                model.validation_metrics = _metrics_for(problem, valid.vec(y), probs_v)
            else:
                model.validation_metrics = model._make_metrics(valid)
        # per-fit stream summary (ISSUE 14): blocks uploaded/evicted/reused
        # and bytes streamed per tree land on the recorded kernel plan
        # (/3/Profiler `tree` fold) and on the model, so "how many bytes
        # did this fit move" is a read, not a rerun
        if ooc_blocks and stream0 is not None:
            delta = {k2: ooc_store.counters[k2] - stream0.get(k2, 0)
                     for k2 in ooc_store.counters}
            stream_stats = dict(
                blocks=int(ooc_blocks),
                blocks_uploaded=delta["uploaded"],
                blocks_evicted=delta["evicted"],
                blocks_reused=delta["reused"],
                streamed_bytes=delta["bytes_streamed"],
                bytes_per_tree=int(delta["bytes_streamed"]
                                   / max(model.ntrees_built, 1)),
                resident_block_peak=int(ooc_store.peak_window_bytes()),
                spilled_blocks=delta.get("spilled", 0),
                restored_blocks=delta.get("restored", 0),
                spilled_bytes=delta.get("bytes_spilled", 0),
                restored_bytes=delta.get("bytes_restored", 0),
                disk_bytes=int(ooc_store.disk_bytes()),
                resident_host_peak=int(ooc_store.host_peak_window_bytes()),
                goss=bool(goss_cfg))
            from ..ops.histogram import attach_fit_stream

            attach_fit_stream(plan_tag, stream_stats)
            model._stream_stats = stream_stats
        # per-fit collective-skew summary (ISSUE 13): fold the fences this
        # fit recorded into the plan ring (/3/Profiler `tree`) and the fit
        # trace, so a dashboard sees which lane a sharded fit waited on
        if cfg.shard_mode == "mesh" and ndev_eff > 1:
            try:
                skew = cloudlib.lane_summary(lane_seq0)
                if skew.get("fences"):
                    from ..ops.histogram import attach_fit_skew

                    attach_fit_skew(plan_tag, skew)
                    _tracing.event(
                        "collective_skew", fences=skew["fences"],
                        skew_p50_ms=skew["skew_p50_ms"],
                        skew_max_ms=skew["skew_max_ms"],
                        worst_lane=skew["worst_lane"])
            except Exception:
                pass
        return model

    def _probs_from_margins(self, problem, dist, m: np.ndarray, ntrees: int) -> np.ndarray:
        return probs_from_margins(self._mode, problem, dist, m, ntrees)

    def _fit_calibrator(self, model: SharedTreeModel):
        """calibrate_model: fit Platt scaling (default) or isotonic
        regression of the true labels on predicted p1 over the
        calibration_frame (hex/tree CalibrationHelper)."""
        calib = self._parms.get("calibration_frame")
        if calib is None:
            raise ValueError("calibrate_model=True requires calibration_frame")
        # score EXACTLY as predict will (incl. offsets) so the map composes
        p1 = model._score_probs(model._matrix(calib),
                                model._offset_of(calib))[:, 1]
        ycal = np.asarray(calib.vec(model.y).data, np.float64)
        method = str(self._parms.get("calibration_method", "AUTO"))
        multiproc = distdata.multiprocess()
        if method in ("AUTO", "PlattScaling"):
            # 1-D logistic regression y ~ a·logit(p) + b via Newton. On a
            # multi-process cloud each rank holds its calibration shard;
            # gradient and Hessian are row sums, so one global_sum per
            # Newton step makes every rank converge to the SAME (a, b)
            z = np.log(np.clip(p1, 1e-12, 1 - 1e-12)
                       / np.clip(1 - p1, 1e-12, 1 - 1e-12))
            X = np.column_stack([z, np.ones_like(z)])
            ab = np.zeros(2)
            for _ in range(25):
                mu = 1 / (1 + np.exp(-(X @ ab)))
                Wd = np.clip(mu * (1 - mu), 1e-10, None)
                grad = X.T @ (ycal - mu)
                Hm = (X * Wd[:, None]).T @ X
                if multiproc:
                    packed = distdata.global_sum(
                        np.concatenate([grad, Hm.ravel()]))
                    grad, Hm = packed[:2], packed[2:].reshape(2, 2)
                step = np.linalg.solve(Hm + 1e-9 * np.eye(2), grad)
                ab = ab + step
                if np.max(np.abs(step)) < 1e-10:
                    break
            a, b = float(ab[0]), float(ab[1])

            def platt(p):
                zz = np.log(np.clip(p, 1e-12, 1 - 1e-12)
                            / np.clip(1 - p, 1e-12, 1 - 1e-12))
                return 1 / (1 + np.exp(-(a * zz + b)))

            return platt
        if method == "IsotonicRegression":
            from .isotonic import pav

            if multiproc:
                # PAV needs the globally sorted sequence — allgather the
                # (p, y) pairs as raw bytes (per-rank lengths differ;
                # calibration frames are holdout-sized, and the reference's
                # Isotonic calibration also centralizes them)
                p1 = np.concatenate([
                    np.frombuffer(b, np.float64) for b in
                    distdata.allgather_bytes(
                        np.ascontiguousarray(p1, np.float64).tobytes())])
                ycal = np.concatenate([
                    np.frombuffer(b, np.float64) for b in
                    distdata.allgather_bytes(
                        np.ascontiguousarray(ycal, np.float64).tobytes())])
            tx, ty = pav(p1, ycal, np.ones_like(ycal))
            return lambda p: np.interp(p, tx, ty)
        raise ValueError(f"unknown calibration_method {method!r}")

    def _default_stopping_metric(self, problem):
        sm = self._parms.get("stopping_metric", "AUTO")
        if sm and sm != "AUTO":
            return sm.lower()
        return "logloss" if problem in ("binomial", "multinomial") else "deviance"

    def _score_event_async(self, problem, dist, margins, y_d, w_d, n,
                           ntrees, row_mask=None, loss_fn=None):
        """Dispatch a scoring-history event and return a FINALIZER.

        Device path: the loss-reduction program is enqueued immediately
        and the returned callable blocks on its scalar only when invoked —
        the overlapped-chunk-scoring hook (ISSUE 7): the driver enqueues
        chunk m+1's tree programs between dispatch and finalize, so the
        device crunches the next chunk while the host waits on chunk m's
        metric and runs the early-stopping decision. Host paths compute
        eagerly and return a constant finalizer.

        `loss_fn` (sharded fits) is the blocked deterministic loss program
        (`_sharded_event_loss_fn`) replacing the whole-array reduction. It
        is the only loss program containing collectives, so it alone is
        fenced after dispatch (at most one collective executable in flight
        on a CPU mesh; no-op elsewhere) — the collective-free events
        (validation frames, the escape hatch) stay fully async so the
        overlapped speculative chunk keeps the device busy behind them."""
        if row_mask is not None and not isinstance(margins, np.ndarray):
            # QoS chunk-fence yield: the loss program is a training-class
            # dispatch — hold it back while serving is in flight
            _qos.yield_point("score_event")
            if loss_fn is not None:
                val_dev = loss_fn(margins, y_d, row_mask,
                                  jnp.float32(1.0 / max(ntrees, 1)))
                cloudlib.collective_fence(val_dev)
            else:
                val_dev = _event_loss_device(
                    margins, y_d, row_mask,
                    jnp.float32(1.0 / max(ntrees, 1)),
                    self._mode, problem, dist)

            def _fin() -> Dict:
                val = float(val_dev)
                ev: Dict = {"number_of_trees": ntrees,
                            "timestamp": time.time()}
                if problem in ("binomial", "multinomial"):
                    ev["logloss"] = val
                    ev["training_deviance"] = val
                    if problem == "binomial":
                        ev["auc"] = float("nan")  # full AUC at final scoring
                else:
                    ev["deviance"] = val
                    ev["rmse"] = float(np.sqrt(val))
                    ev["training_deviance"] = val
                return ev

            return _fin
        ev = self._score_event(problem, dist, margins, y_d, w_d, n,
                               ntrees, row_mask=row_mask)
        return lambda: ev

    def _score_event(self, problem, dist, margins, y_d, w_d, n, ntrees,
                     row_mask=None) -> Dict:
        """One scoring-history event. With `row_mask` (device real-row
        mask), the loss sums are computed ON DEVICE and only two scalars
        cross the wire — at 1M rows the host path's full-margin pull is
        4·n·K bytes to the host per event. On a multi-process cloud
        the device inputs are global, so the sums come back global and
        identical on every rank (the early-stopping decisions that read
        them therefore agree); the host fallback (OOB means arrive as numpy)
        reduces with ONE `global_sum` instead."""
        if row_mask is not None and not isinstance(margins, np.ndarray):
            return self._score_event_async(problem, dist, margins, y_d,
                                           w_d, n, ntrees,
                                           row_mask=row_mask)()
        multiproc = distdata.multiprocess()
        m = distdata.to_local(margins)[:n].astype(np.float64)
        y = distdata.to_local(y_d)[:n].astype(np.float64)
        probs = self._probs_from_margins(problem, dist, m, ntrees)

        def _gmean(local_sum: float, local_cnt: float) -> float:
            if multiproc:
                tot = distdata.global_sum(
                    np.asarray([local_sum, local_cnt], np.float64))
                return float(tot[0] / max(tot[1], 1e-12))
            return float(local_sum / max(local_cnt, 1e-12))

        ev: Dict = {"number_of_trees": ntrees, "timestamp": time.time()}
        if problem == "binomial":
            p = np.clip(probs[:, 1], 1e-15, 1 - 1e-15)
            nll = -np.log(np.where(y[:, 0] > 0.5, p, 1 - p))
            ev["logloss"] = _gmean(float(nll.sum()), float(len(nll)))
            ev["auc"] = float("nan")  # full AUC computed at final scoring
            ev["training_deviance"] = ev["logloss"]
        elif problem == "multinomial":
            p = np.clip(probs, 1e-15, 1)
            nll = -np.log(p[y.astype(bool)])
            ev["logloss"] = _gmean(float(nll.sum()), float(len(nll)))
            ev["training_deviance"] = ev["logloss"]
        else:
            sq = (probs[:, 0] - y[:, 0]) ** 2
            ev["deviance"] = _gmean(float(sq.sum()), float(len(sq)))
            ev["rmse"] = float(np.sqrt(ev["deviance"]))
            ev["training_deviance"] = ev["deviance"]
        return ev

    def _cv_predict(self, model: SharedTreeModel, frame: Frame) -> np.ndarray:
        out = model._score_probs(model._matrix(frame))
        if model.problem == "binomial":
            return out[:, 1]
        if model.problem == "multinomial":
            return out
        return out[:, 0]
