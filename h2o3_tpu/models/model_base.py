"""Model framework — estimator lifecycle, jobs, CV, early stopping, DataInfo.

Reference parity:
* `h2o-core/src/main/java/hex/Model.java` / `hex/ModelBuilder.java` — the
  train/score lifecycle, n-fold CV orchestration (`computeCrossValidation`),
  parameter validation.
* `water/Job.java` — async job tracking (here: synchronous with progress).
* `hex/ScoreKeeper.java` — early stopping on a moving average of the
  stopping metric.
* `hex/DataInfo.java` — the numeric adapter reused by GLM/DeepLearning/PCA/
  KMeans: categorical one-hot expansion, standardization, NA mean-imputation.
* `h2o-py/h2o/estimators/estimator_base.py` — the Python estimator facade
  whose signatures (`train(x, y, training_frame, validation_frame)`,
  `predict`, `model_performance`) are the compatibility contract.

TPU note: builders prepare host-side numpy, then hand dense arrays to jitted
training programs; the padded/sharded device placement happens inside each
algorithm (see `tree.py`, `glm.py`, `deeplearning.py`).
"""

from __future__ import annotations

import functools
import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..frame.frame import Frame
from ..frame.vec import Vec
from .metrics import (
    ModelMetricsBase,
    ModelMetricsBinomial,
    ModelMetricsMultinomial,
    ModelMetricsRegression,
)

_model_counter = itertools.count()


@functools.lru_cache(maxsize=64)
def _device_expand_fn(sig):
    """Jitted design-matrix expansion, cached per DataInfo signature
    (column kinds/cardinalities + transfer dtype per numeric column,
    use_all, standardize, intercept) so every same-shaped frame reuses one
    compiled program. Numeric columns arrive in up to three transfer
    groups — uint8 / int16 / f32 — the analog of the reference's columnar
    chunk compression (water/fvec C1Chunk, C2Chunk): small-range integer
    columns cross the host↔device link at 1–2 bytes/value, LOSSLESSLY,
    and widen to f32 on device."""
    import jax
    import jax.numpy as jnp

    spec, use_all, standardized, add_intercept = sig

    def expand(nums8, nums16, nums32, cats, means, stds):
        parts = []
        idx = [0, 0, 0]
        groups = (nums8, nums16, nums32)
        ci = 0
        for kind, K in spec:
            if kind == "num":
                g = K  # for num entries, K carries the transfer group id
                parts.append(groups[g][:, idx[g]].astype(jnp.float32)[:, None])
                idx[g] += 1
            else:
                codes = cats[:, ci]
                ci += 1
                oh = (codes[:, None] == jnp.arange(K, dtype=jnp.int32)[None, :]
                      ).astype(jnp.float32)
                if not use_all and K > 0:
                    oh = oh[:, 1:]
                parts.append(oh)
        X = jnp.concatenate(parts, axis=1)
        if standardized:
            X = (X - means[None, :]) / stds[None, :]
        # trailing NaN cleanup, mirroring fit_transform/transform
        X = jnp.nan_to_num(X, nan=0.0)
        if add_intercept:
            X = jnp.concatenate(
                [X, jnp.ones((X.shape[0], 1), jnp.float32)], axis=1)
        return X

    return jax.jit(expand)


class JobCancelled(RuntimeError):
    """Raised inside a training driver when its Job was cancelled
    (`water.Job.JobCancelledException` — cancellation takes effect at the
    driver's next safe point, a scoring boundary)."""


class ScoringHistory(list):
    """Scoring-history rows, list-compatible AND callable: h2o-py's
    `model.scoring_history()` returns a table, while this framework's
    drivers (and earlier rounds' tests) index the rows directly — one
    object serves both surfaces."""

    def __call__(self, use_pandas: bool = False):
        cols = {}
        for k in (list(self[0]) if self else []):
            vals = [r.get(k) for r in self]
            if isinstance(vals[0], str):
                cols[k] = np.asarray(vals, dtype=object)
            else:
                cols[k] = np.asarray(
                    [np.nan if v is None else float(v) for v in vals])
        fr = Frame.from_dict(cols) if cols else Frame({})
        if use_pandas:
            return fr.as_data_frame(use_pandas=True)
        return fr


# scoring-program row bucket: jitted scorer inputs (tree _margins, GLM
# scoring design) quantize their row dimension to this multiple so nearby
# frame sizes share one compiled program (each extra program is a cold
# compile). ONE constant — tree and GLM must bucket alike.
SCORE_ROW_BUCKET = 512


@dataclass
class Job:
    """`water.Job` — progress/cancel tracking for a training run."""

    dest: str
    description: str = ""
    start_time: float = 0.0
    end_time: float = 0.0
    progress: float = 0.0
    status: str = "CREATED"  # CREATED/RUNNING/DONE/FAILED/CANCELLED
    warnings: List[str] = field(default_factory=list)
    cancel_requested: bool = False
    # observability spine: the REST request (or client call) that created
    # this job stamps its trace id here, so the job's worker thread — and
    # every trainpool candidate under it — records spans in the same trace
    trace_id: Optional[str] = None

    def start(self):
        self.start_time = time.time()
        self.status = "RUNNING"
        return self

    def update(self, progress: float):
        self.progress = float(progress)

    def cancel(self):
        """Request cancellation (`DELETE /3/Jobs/{id}` / Job.stop): takes
        effect at the driver's next safe point."""
        if self.status in ("CREATED", "RUNNING"):
            self.cancel_requested = True

    def check_cancelled(self):
        """Driver-side safe point: finalize + raise if a cancel is pending."""
        if self.cancel_requested and self.status == "RUNNING":
            self.status = "CANCELLED"
            self.end_time = time.time()
            raise JobCancelled(self.dest)

    def done(self):
        self.end_time = time.time()
        self.progress = 1.0
        self.status = "DONE"

    @property
    def run_time(self) -> float:
        return (self.end_time or time.time()) - self.start_time


class ScoreKeeper:
    """`hex.ScoreKeeper.stopEarly` — moving-average early stopping."""

    def __init__(self, stopping_rounds: int, stopping_metric: str, tolerance: float,
                 larger_is_better: Optional[bool] = None):
        self.k = stopping_rounds
        self.metric = stopping_metric
        self.tol = tolerance
        if larger_is_better is None:
            larger_is_better = stopping_metric.lower() in ("auc", "pr_auc", "accuracy", "r2")
        self.more = larger_is_better
        self.history: List[float] = []

    def record(self, value: float) -> bool:
        """Record a scoring event; True ⇒ stop now (moving average of the
        last k events is not better than the best before them by > tol)."""
        self.history.append(float(value))
        k = self.k
        if k <= 0 or len(self.history) < 2 * k:
            return False
        hist = np.asarray(self.history)
        recent = hist[-k:].mean()
        prior = hist[:-k]
        best_prior = prior.max() if self.more else prior.min()
        margin = self.tol * max(abs(best_prior), 1e-12)
        if self.more:
            return recent <= best_prior + margin
        return recent >= best_prior - margin


def _column_moments(v: "Vec", fit: bool):
    """`(c, isna, has_nan, n_ok, mean, std)` of a numeric column: the values
    as float64, where they are NaN, and for a fit the count, `np.nanmean` and
    `np.nanstd` of the rest (0.0 where there is none or the result is not
    finite). The arithmetic is numpy's own, operation for operation (the
    zero-filled sum over the count; the zero-filled squared deviations'
    sum over the count), in two of this thread's work buffers
    (`runtime/workspace.py`) where those two calls make six row-sized
    temporaries. `c` and `isna` are good until the next call."""
    from ..runtime import workspace

    src = v.numeric_np() if v.type == "enum" else np.asarray(v.data)
    n = len(src)
    c = workspace.take("design.column", n, np.float64)
    np.copyto(c, src, casting="unsafe")
    isna = workspace.take("design.isna", n, np.bool_)
    np.isnan(c, out=isna)
    n_ok = n - int(np.count_nonzero(isna))
    has_nan = n_ok < n
    if not fit or not n_ok:
        return c, isna, has_nan, n_ok if fit else 0, 0.0, 0.0
    dev = workspace.take("design.deviation", n, np.float64)
    np.copyto(dev, c)
    with np.errstate(all="ignore"):
        if has_nan:
            np.copyto(dev, 0, where=isna)
        mean = np.add.reduce(dev) / n_ok
        dev -= mean
        if has_nan:
            np.copyto(dev, 0, where=isna)
        dev *= dev
        std = np.sqrt(np.add.reduce(dev) / n_ok)
    return (c, isna, has_nan, n_ok,
            float(mean) if np.isfinite(mean) else 0.0,
            float(std) if np.isfinite(std) else 0.0)


def _filled(c: np.ndarray, isna: Optional[np.ndarray], value: float):
    """`c` with `value` where it is NaN: written into `c` where `isna`
    comes with it (`_column_moments`' work buffer), a new array where not."""
    if isna is None:
        return np.where(np.isnan(c), value, c)
    np.copyto(c, value, where=isna)
    return c


def _level_counts(codes: np.ndarray, K: int) -> np.ndarray:
    """Rows at each of the first `K` levels, NAs (negative codes) left out:
    `np.bincount` a block at a time (it widens its whole input to int64)."""
    from ..runtime import workspace

    cnt = np.zeros(K, np.intp)
    for b in workspace.blocks(len(codes)):
        blk = codes[b]
        cnt += np.bincount(blk[blk >= 0], minlength=K)[:K]
    return cnt


class DataInfo:
    """`hex.DataInfo` — Frame → dense numeric design matrix.

    use_all_factor_levels / standardize / imputeMissing mirror the reference
    flags; categorical expansion is one-hot (the reference's default enum
    encoding for GLM/DL)."""

    def __init__(
        self,
        frame: Frame,
        x: Sequence[str],
        standardize: bool = True,
        use_all_factor_levels: bool = False,
        impute_missing: bool = True,
        max_categorical_levels: int = 1000,
    ):
        self.x = list(x)
        self.standardize = standardize
        self.use_all = use_all_factor_levels
        self.coef_names: List[str] = []
        self._spec = []  # per input col: ("num", name) | ("cat", name, domain)
        for n in self.x:
            v = frame.vec(n)
            if v.type == "enum":
                dom = (v.domain or [])[:max_categorical_levels]
                self._spec.append(("cat", n, dom))
                levels = dom if use_all_factor_levels else dom[1:]
                self.coef_names += [f"{n}.{d}" for d in levels]
            else:
                self._spec.append(("num", n, None))
                self.coef_names.append(n)
        self.means: Optional[np.ndarray] = None
        self.stds: Optional[np.ndarray] = None
        self.impute_missing = impute_missing
        self.col_means: Dict[str, float] = {}

    def fit_transform(self, frame: Frame) -> np.ndarray:
        X = self._expand(frame, fit=True)
        if self.standardize:
            from ..parallel import distdata

            if distdata.multiprocess():
                # global moments across the multi-host cloud (the MRTask
                # mean/σ reduce) — local stats would skew each shard.
                # Two-pass: mean first, then Σ(x−μ)² — the one-pass
                # E[x²]−E[x]² form cancels catastrophically for columns
                # with large mean and small spread
                finite = ~np.isnan(X)
                s = distdata.global_sum(np.nansum(X, axis=0))
                c = np.maximum(distdata.global_sum(finite.sum(axis=0)), 1.0)
                self.means = s / c
                dev2 = distdata.global_sum(
                    np.nansum((X - self.means) ** 2, axis=0))
                self.stds = np.sqrt(dev2 / c)
            else:
                self.means = np.nanmean(X, axis=0)
                self.stds = np.nanstd(X, axis=0)
            self.stds = np.where(self.stds < 1e-10, 1.0, self.stds)
            X = (X - self.means) / self.stds
        return np.nan_to_num(X, nan=0.0).astype(np.float32)

    def transform(self, frame: Frame) -> np.ndarray:
        X = self._expand(frame, fit=False)
        if self.standardize and self.means is not None:
            X = (X - self.means) / self.stds
        return np.nan_to_num(X, nan=0.0).astype(np.float32)

    def device_design(self, frame: Frame, fit: bool,
                      add_intercept: bool = False, cloud=None,
                      quota: Optional[int] = None,
                      row_bucket: int = 0):
        """Expanded design matrix built ON DEVICE from compact columns.

        Semantically identical to fit_transform/transform (same one-hot
        layout, imputation, standardization — the stats are derived
        analytically from the codes), but the host→device transfer is the
        compact representation (numeric f32 + categorical int32 codes,
        ~P_cat× smaller than the dense one-hot), and the expansion runs as
        one compiled program. This is what makes wide-categorical GLM
        viable over the host↔device link.

        With `cloud` (a mesh of >1 devices, possibly multi-process) the
        compact packs are assembled as ROW-SHARDED global arrays (padded to
        `quota` rows per process) and expanded in place, so multi-device
        meshes get the same byte-compressed transfer as a single chip —
        no dense f32 upload and no unsharded intermediate on device 0.
        In ONE process `fit=True` fits the stats from the compact columns
        on every cloud size (the host holds all rows, so they are the
        one-device lane's bit for bit). A multi-PROCESS cloud holds only
        its ingest shard: there the stats come from `fit_transform`'s
        global-moment collectives first, then fit=False."""
        import jax.numpy as jnp

        from ..parallel import distdata
        from ..runtime import tracing, workspace

        multiproc = cloud is not None and distdata.multiprocess()
        if fit and multiproc:
            raise ValueError(
                "device_design(fit=True) on a multi-process cloud would fit "
                "this process's shard alone: fit_transform first, then "
                "fit=False")

        # children of the fit's `fit.design` span (also opened when a
        # frame is scored): column statistics and imputation from the
        # codes, stacking the categorical codes, the transfer dtype of each
        # numeric column, packing, the upload with the expand program's
        # dispatch
        with tracing.span("design.stats", kind="fit"):
            n = frame.nrow
            nums, cats = [], []
            means, stds = [], []
            # wide-numeric fast pre-pass: per-column nanmean/nanstd/isnan calls
            # cost ~2 s of host time at MNIST width (784 × 60k); batching them
            # as axis-0 reductions over one stacked matrix is ~10× cheaper and
            # numerically identical
            _num_cols = [nm for k, nm, _ in self._spec if k == "num"]
            _pre = {}
            if len(_num_cols) > 8:
                mat = np.stack([frame.vec(nm).numeric_np()
                                for nm in _num_cols], axis=1)
                nan_mask = np.isnan(mat)
                has_nan_vec = nan_mask.any(axis=0)
                if fit:
                    has_valid = ~nan_mask.all(axis=0)
                    with np.errstate(all="ignore"):
                        mvec = np.where(has_valid, np.nanmean(mat, axis=0), 0.0)
                        svec = np.where(has_valid, np.nanstd(mat, axis=0), 0.0)
                    nvalid = (~nan_mask).sum(axis=0)
                    # isfinite-else-0.0, matching the narrow per-column path:
                    # nan_to_num would map an infinite column mean to ±1.8e308
                    # and diverge the standardization stats by frame width
                    _pre = {nm: (mat[:, j], bool(has_nan_vec[j]),
                                 float(mvec[j]) if np.isfinite(mvec[j]) else 0.0,
                                 float(svec[j]) if np.isfinite(svec[j]) else 0.0,
                                 int(nvalid[j]))
                            for j, nm in enumerate(_num_cols)}
                else:
                    # scoring path: stats come from the stored fit-time values;
                    # only the column data + NaN flags are needed
                    _pre = {nm: (mat[:, j], bool(has_nan_vec[j]), 0.0, 0.0, 0)
                            for j, nm in enumerate(_num_cols)}
            pos = 0  # expanded-column position (for stored-stat lookups)
            for kind, name, dom in self._spec:
                v = frame.vec(name)
                if kind == "num":
                    if name in _pre:
                        c, has_nan, pre_m, pre_s, n_ok = _pre[name]
                        isna = None
                    else:
                        # float64 in this thread's work buffer, good until
                        # the next column: `nums` keeps a float32 copy
                        c, isna, has_nan, n_ok, pre_m, pre_s = \
                            _column_moments(v, fit)
                    if self.impute_missing:
                        if fit:
                            self.col_means[name] = pre_m
                        if has_nan:
                            c = _filled(c, isna,
                                        self.col_means.get(name, 0.0))
                            # post-impute plain std: mean-filling leaves the
                            # mean unchanged and shrinks the variance by the
                            # valid-row fraction (exactly, analytically)
                            pre_s = pre_s * float(np.sqrt(n_ok / max(n, 1)))
                    if fit and self.standardize:
                        # stats over valid rows only (nanmean/nanstd), exactly
                        # like fit_transform. All-NaN columns get (0, 1) so
                        # they standardize to the zeros fit_transform's
                        # trailing nan_to_num produces.
                        means.append([pre_m])
                        stds.append([pre_s if pre_s >= 1e-10 else 1.0])
                    if not self.impute_missing and has_nan:
                        if self.standardize:
                            # fit_transform zeroes missing AFTER scaling, so the
                            # raw fill that standardizes to 0 is the column mean
                            mm = (means[-1][0] if fit
                                  else float(self.means[pos])
                                  if self.means is not None else 0.0)
                            c = _filled(c, isna, mm)
                        else:
                            c = np.nan_to_num(c, nan=0.0)
                    nums.append(c.astype(np.float32))
                    pos += 1
                else:
                    codes = np.asarray(v.data)
                    if v.domain != dom and v.domain:
                        remap = np.asarray(
                            [dom.index(d) if d in dom else -1 for d in v.domain],
                            np.int64)
                        codes = np.where(codes >= 0, remap[np.maximum(codes, 0)], -1)
                    cats.append(codes.astype(np.int32, copy=False))
                    if fit and self.standardize:
                        K = len(dom)
                        cnt = _level_counts(codes, K)
                        p_lvl = cnt / max(n, 1)
                        lv = p_lvl if self.use_all else p_lvl[1:]
                        means.append(lv.tolist())
                        stds.append([float(s) if (s := np.sqrt(pl * (1 - pl))) >= 1e-10
                                     else 1.0 for pl in lv])
                    pos += len(dom) if self.use_all else max(len(dom) - 1, 0)
            if fit and self.standardize:
                self.means = np.asarray(
                    [m for grp in means for m in grp], np.float64)
                self.stds = np.asarray(
                    [s for grp in stds for s in grp], np.float64)

        with tracing.span("design.codes", kind="fit"):
            # np.stack's rows, written a block of rows at a time: a column
            # at a time walks the whole (n, k) array once a column
            cats_a = np.empty((n, len(cats)), np.int32)
            for b in workspace.blocks(n, 1 << 15):
                for j, c in enumerate(cats):
                    cats_a[b, j] = c[b]
        with tracing.span("design.groups", kind="fit"):
            # per-column transfer dtype: integer-valued small-range columns
            # ship as 1–2 bytes/value (LOSSLESS — C1Chunk/C2Chunk parity);
            # everything else as f32. Group id rides the spec signature, so the
            # layout is FROZEN at fit: scoring frames reuse the training
            # program when their values still fit the stored dtypes, and fall
            # back to ONE stable all-f32 program otherwise (per-frame
            # re-derivation would churn fresh XLA compiles on every frame
            # whose integrality/range differs).
            def _fits_group(c, g):
                if g == 2:
                    return True
                if not c.size:
                    return False
                lo, hi = (0.0, 255.0) if g == 0 else (-32768.0, 32767.0)
                if not (lo <= c.min() and c.max() <= hi):
                    return False
                # in range, so finite: whole-valued where truncation changes
                # nothing. A block at a time: a fractional column is known
                # by its first block and nothing row-sized is made (six
                # passes of np.mod(c, 1.0) were 1.7 s of a 2.6 s design
                # build at 7,250,000 rows; PERF.md section 5)
                return all(np.array_equal(c[b], np.trunc(c[b]))
                           for b in workspace.blocks(c.size, 1 << 16))

            def _local_groups():
                out = []
                for c in nums:
                    out.append(0 if _fits_group(c, 0)
                               else 1 if _fits_group(c, 1) else 2)
                return out

            if fit:
                num_group = _local_groups()
                self._transfer_groups = list(num_group)
            else:
                stored = getattr(self, "_transfer_groups", None)
                ok = bool(stored is not None and len(stored) == len(nums) and all(
                    _fits_group(c, g) for c, g in zip(nums, stored)))
                if multiproc:
                    # pack layout is part of the compiled program: every rank
                    # must make the SAME stored-vs-fallback decision
                    ok = bool(distdata.allgather_host(
                        np.asarray([ok], np.int32)).all())
                if ok:
                    num_group = stored
                elif cloud is not None:
                    # sharded ingest with no (usable) fit-time decision: decide
                    # now, globally — per-rank data ranges differ, so take the
                    # widest group each column needs anywhere
                    num_group = _local_groups()
                    if multiproc:
                        num_group = list(distdata.allgather_host(
                            np.asarray(num_group, np.int32)
                        ).reshape(-1, len(num_group)).max(axis=0)) if nums else []
                        num_group = [int(g) for g in num_group]
                    if stored is None:
                        self._transfer_groups = list(num_group)
                else:
                    num_group = [2] * len(nums)
        with tracing.span("design.pack", kind="fit"):
            groups = ([], [], [])                 # uint8, int16, f32
            for c, g in zip(nums, num_group):
                groups[g].append(c)
            dts = (np.uint8, np.int16, np.float32)
            packs = [np.empty((n, len(g)), dt) for g, dt in zip(groups, dts)]
            for b in workspace.blocks(n, 1 << 15):
                for pk, g in zip(packs, groups):
                    for j, c in enumerate(g):
                        pk[b, j] = c[b]
            gi = iter(num_group)
            sig = (tuple((k, next(gi) if k == "num" else (len(d) if d else 0))
                         for k, _, d in self._spec),
                   self.use_all, self.standardize and self.means is not None,
                   add_intercept)
            fn = _device_expand_fn(sig)
            m_h = (np.asarray(self.means, np.float32)
                   if self.standardize and self.means is not None
                   else np.zeros(0, np.float32))
            s_h = (np.asarray(self.stds, np.float32)
                   if self.standardize and self.stds is not None
                   else np.ones(0, np.float32))
            if row_bucket and cloud is None:
                from ..parallel.mesh import pad_to_multiple

                # quantize the expand program's row dimension: nearby scoring
                # frame sizes (CV folds, pages) reuse ONE compiled program; the
                # zero-filled pad rows expand to garbage the CALLER slices off
                npad_b = pad_to_multiple(n, row_bucket)
                if npad_b != n:
                    packs = [np.concatenate(
                        [p, np.zeros((npad_b - n,) + p.shape[1:], p.dtype)])
                        for p in packs]
                    cats_a = np.concatenate(
                        [cats_a, np.zeros((npad_b - n, cats_a.shape[1]),
                                          cats_a.dtype)])

        from ..runtime import phases as _phases

        nbytes = sum(p.nbytes for p in packs) + cats_a.nbytes
        if cloud is not None and (cloud.size > 1 or multiproc):
            from ..parallel import mesh as cloudlib

            if quota is None:
                # every rank must agree on the padded per-process rows
                quota = (distdata.local_quota(n) if multiproc
                         else cloudlib.pad_to_multiple(n, cloud.size))
            m_r = distdata.replicated_array(m_h, cloud)
            s_r = distdata.replicated_array(s_h, cloud)

            def _sharded():
                gp = [distdata.global_row_array(pk, quota, cloud)
                      for pk in packs]
                gc = distdata.global_row_array(cats_a, quota, cloud)
                return fn(gp[0], gp[1], gp[2], gc, m_r, s_r)

            with tracing.span("design.upload", kind="fit",
                              devices=cloud.size, bytes_h2d=nbytes):
                return _phases.accounted_h2d(_sharded, nbytes)
        with tracing.span("design.upload", kind="fit", devices=1,
                          bytes_h2d=nbytes):
            return _phases.accounted_h2d(
                lambda: fn(jnp.asarray(packs[0]), jnp.asarray(packs[1]),
                           jnp.asarray(packs[2]), jnp.asarray(cats_a),
                           jnp.asarray(m_h), jnp.asarray(s_h)),
                nbytes)

    def _expand(self, frame: Frame, fit: bool) -> np.ndarray:
        cols = []
        for kind, n, dom in self._spec:
            v = frame.vec(n)
            if kind == "num":
                c = v.numeric_np()
                if self.impute_missing:
                    if fit:
                        from ..parallel import distdata

                        if distdata.multiprocess():
                            # global imputation mean — a local shard mean
                            # would bake different values into each
                            # process's design matrix (and into the saved
                            # model's col_means)
                            sc = distdata.global_sum(np.asarray(
                                [np.nansum(c), float((~np.isnan(c)).sum())],
                                np.float64))
                            self.col_means[n] = float(sc[0] / max(sc[1], 1.0))
                        else:
                            self.col_means[n] = float(np.nanmean(c))
                    c = np.where(np.isnan(c), self.col_means.get(n, 0.0), c)
                cols.append(c[:, None])
            else:
                codes = np.asarray(v.data)
                if v.domain != dom and v.domain:
                    remap = np.asarray(
                        [dom.index(d) if d in dom else -1 for d in v.domain], np.int64
                    )
                    codes = np.where(codes >= 0, remap[np.maximum(codes, 0)], -1)
                K = len(dom)
                oh = np.zeros((len(codes), K))
                valid = codes >= 0
                oh[np.nonzero(valid)[0], codes[valid]] = 1.0
                if not self.use_all and K > 0:
                    oh = oh[:, 1:]
                cols.append(oh)
        return np.concatenate(cols, axis=1) if cols else np.zeros((frame.nrow, 0))


class H2OModel:
    """Trained-model half of `hex.Model` + the `h2o-py` ModelBase surface."""

    algo = "base"

    def __init__(self, params: "H2OEstimator"):
        self.parms = params
        # honour a user-chosen model_id (estimator parameter), else generate
        user_id = None
        if hasattr(params, "_parms"):
            user_id = params._parms.get("model_id")
        self.model_id = user_id or f"{self.algo}_{next(_model_counter)}"
        self.training_metrics: Optional[ModelMetricsBase] = None
        self.validation_metrics: Optional[ModelMetricsBase] = None
        self.cross_validation_metrics: Optional[ModelMetricsBase] = None
        self.scoring_history: ScoringHistory = ScoringHistory()
        self.varimp_table: Optional[List] = None
        self.run_time: float = 0.0
        self._cv_holdout_pred: Optional[np.ndarray] = None
        self.cross_validation_models: Optional[List] = None

    # -- metric accessors (h2o-py ModelBase) --------------------------------
    def _m(self, valid=False, xval=False):
        if xval and self.cross_validation_metrics:
            return self.cross_validation_metrics
        if valid and self.validation_metrics:
            return self.validation_metrics
        return self.training_metrics

    def auc(self, valid=False, xval=False):
        return getattr(self._m(valid, xval), "auc", float("nan"))

    def logloss(self, valid=False, xval=False):
        return getattr(self._m(valid, xval), "logloss", float("nan"))

    def rmse(self, valid=False, xval=False):
        return self._m(valid, xval).rmse

    def mse(self, valid=False, xval=False):
        return self._m(valid, xval).mse

    def mae(self, valid=False, xval=False):
        return getattr(self._m(valid, xval), "mae", float("nan"))

    def r2(self, valid=False, xval=False):
        return getattr(self._m(valid, xval), "r2", float("nan"))

    def mean_per_class_error(self, valid=False, xval=False):
        return getattr(self._m(valid, xval), "mean_per_class_error", float("nan"))

    def varimp(self, use_pandas=False):
        return self.varimp_table

    def summary(self):
        """Model summary table (h2o-py ModelBase.summary) — generic form;
        concrete models override with their architecture specifics."""
        return dict(model_id=self.model_id, algo=self.algo,
                    run_time_s=round(self.run_time, 3))

    def show(self):
        print(f"Model: {self.model_id} ({self.algo})")
        for k, v in self.summary().items():
            print(f"  {k}: {v}")
        if self.training_metrics is not None:
            print(f"  training: {self.training_metrics._ser()}")

    def gains_lift(self, valid=False, xval=False):
        m = self._m(valid, xval)
        return m.gains_lift() if hasattr(m, "gains_lift") else None

    def roc(self, valid=False, xval=False):
        m = self._m(valid, xval)
        return m.roc() if hasattr(m, "roc") else None

    def predict(self, test_data: Frame) -> Frame:
        raise NotImplementedError

    def scoring_signature(self) -> tuple:
        """(n_features, dtype) identifying this model's compiled
        scoring-program family — the shape-bearing parts of the serving
        cache key (serving/model_cache.py). Two models under the same DKV
        key with different signatures can never share an executable."""
        x = getattr(self, "x", None)
        nf = len(x) if isinstance(x, (list, tuple)) else (1 if x else 0)
        return (nf, "float32")

    def model_performance(self, test_data: Optional[Frame] = None, **kw):
        if test_data is None:
            return self.training_metrics
        return self._make_metrics(test_data)

    def _make_metrics(self, frame: Frame):
        raise NotImplementedError

    # -- model understanding (h2o-py ModelBase surface) ---------------------
    @staticmethod
    def _response_stats(p: np.ndarray, weights: Optional[np.ndarray]):
        """(mean, sd, sem) of one response column, optionally weighted."""
        if weights is None:
            mean = float(np.mean(p))
            sd = float(np.std(p, ddof=1)) if len(p) > 1 else 0.0
        else:
            wsum = max(float(weights.sum()), 1e-12)
            mean = float((p * weights).sum() / wsum)
            sd = float(np.sqrt(((p - mean) ** 2 * weights).sum() / wsum))
        return mean, sd, sd / max(np.sqrt(len(p)), 1.0)

    def _response_column(self, pred: Frame, target: Optional[str]) -> np.ndarray:
        """Pick the response column of a prediction frame — a chosen class
        probability, binomial p1, or the raw (regression) prediction."""
        if target is not None:
            return pred.vec(str(target)).numeric_np().astype(np.float64)
        domain = getattr(self, "domain", None)
        if domain is not None and len(domain) == 2 and str(domain[1]) in pred.names:
            return pred.vec(str(domain[1])).numeric_np().astype(np.float64)
        if domain is not None and len(domain) > 2:
            raise ValueError(
                "multinomial models need `targets=[<class label>, ...]` "
                "(averaging the predicted class labels is meaningless — "
                "hex/PartialDependence requires targets too)")
        return pred.vec("predict").numeric_np().astype(np.float64)

    def partial_plot(self, data: Frame, cols=None, nbins: int = 20,
                     plot: bool = False, include_na: bool = False,
                     user_splits=None, targets=None, row_index=None,
                     weight_column: Optional[str] = None, **_kw):
        """Partial-dependence tables, one Frame per column (× target for
        multinomial): columns [<col>, mean_response, stddev_response,
        std_error_mean_response]. 1-D PDP over nbins grid points (numeric) or
        the categorical levels — `h2o-py ModelBase.partial_plot` /
        `hex/PartialDependence.java`. `row_index` gives a single-row ICE
        curve instead of the dataset mean."""
        if cols is None:
            raise ValueError("cols is required")
        if isinstance(cols, str):
            cols = [cols]
        if row_index is not None:
            data = Frame({n: v.take(np.asarray([row_index]))
                          for n, v in data._vecs.items()})
        weights = None
        if weight_column is not None:
            weights = data.vec(weight_column).numeric_np().astype(np.float64)
        tlist = list(targets) if targets else [None]
        out = []
        for col in cols:
            v = data.vec(col)
            if v.type == "enum":
                values = list(range(len(v.domain or [])))
                labels = list(v.domain or [])
            else:
                raw = v.numeric_np()
                raw = raw[~np.isnan(raw)]
                if user_splits and col in user_splits:
                    values = list(user_splits[col])
                else:
                    lo, hi = (float(raw.min()), float(raw.max())) if len(raw) else (0.0, 1.0)
                    values = list(np.linspace(lo, hi, nbins))
                labels = values
            if include_na:
                values = values + [np.nan]
                labels = labels + [float("nan") if v.type != "enum" else ".missing(NA)"]
            # ONE predict per grid value; every target reads its own column
            rows = {tgt: [] for tgt in tlist}
            for val in values:
                n = data.nrow
                if v.type == "enum":
                    is_na = isinstance(val, float) and np.isnan(val)
                    code = -1 if is_na else int(val)
                    const = Vec(np.full(n, code, np.int32), "enum",
                                domain=v.domain)
                else:
                    const = Vec(np.full(n, val, np.float64), "real")
                pred = self.predict(Frame({**data._vecs, col: const}))
                for tgt in tlist:
                    p = self._response_column(pred, tgt)
                    rows[tgt].append(self._response_stats(p, weights))
            for tgt in tlist:
                d = {
                    col: (np.asarray(labels, dtype=object) if v.type == "enum"
                          else np.asarray(labels, np.float64)),
                    "mean_response": np.asarray([r[0] for r in rows[tgt]]),
                    "stddev_response": np.asarray([r[1] for r in rows[tgt]]),
                    "std_error_mean_response": np.asarray(
                        [r[2] for r in rows[tgt]]),
                }
                fr_out = Frame.from_dict(
                    d, column_types={col: "enum"} if v.type == "enum" else None)
                if tgt is not None:
                    fr_out.target = tgt
                out.append(fr_out)
        return out

    def permutation_importance(self, frame: Frame, metric: str = "AUTO",
                               n_samples: int = -1, n_repeats: int = 1,
                               features=None, seed: int = -1,
                               use_pandas: bool = False) -> Frame:
        """Permutation variable importance (`h2o-py permutation_varimp` /
        `hex/PermutationVarImp.java`): |metric(baseline) − metric(feature
        shuffled)|, averaged over n_repeats."""
        problem = getattr(self, "problem", None)
        if metric in ("AUTO", "auto", None):
            metric = {"binomial": "auc", "multinomial": "logloss"}.get(
                problem, "rmse")
        metric = metric.lower()
        rng = np.random.default_rng(None if seed in (-1, None) else seed)
        if 0 < n_samples < frame.nrow:
            idx = rng.choice(frame.nrow, n_samples, replace=False)
            frame = Frame({n: v.take(idx) for n, v in frame._vecs.items()})
        base = getattr(self._make_metrics(frame), metric)
        feats = list(features) if features else list(self.x)
        rel = []
        for f in feats:
            deltas = []
            v = frame.vec(f)
            for _ in range(max(n_repeats, 1)):
                perm = rng.permutation(frame.nrow)
                shuf = Vec(np.asarray(v.data)[perm] if v.data is not None else None,
                           v.type, domain=v.domain)
                m = getattr(self._make_metrics(Frame({**frame._vecs, f: shuf})),
                            metric)
                deltas.append(abs(base - m))
            rel.append(float(np.mean(deltas)))
        rel_a = np.asarray(rel, np.float64)
        mx = rel_a.max() if rel_a.size and rel_a.max() > 0 else 1.0
        tot = rel_a.sum() if rel_a.sum() > 0 else 1.0
        order = np.argsort(-rel_a)
        return Frame.from_dict({
            "Variable": np.asarray(feats, dtype=object)[order],
            "Relative Importance": rel_a[order],
            "Scaled Importance": rel_a[order] / mx,
            "Percentage": rel_a[order] / tot,
        })


class H2OEstimator:
    """Parameter-holder + builder — `hex.ModelBuilder` merged with the
    generated `h2o-py` estimator classes (h2o-bindings/bin/gen_python.py).

    Subclasses define `_param_defaults` and `_fit`; unknown kwargs raise like
    the reference's schema validation does."""

    algo = "base"
    supervised = True  # class-level default; see _is_supervised()
    _param_defaults: Dict[str, Any] = {}
    _common_defaults: Dict[str, Any] = dict(
        model_id=None,
        seed=-1,
        max_runtime_secs=0.0,
        ignored_columns=None,
        ignore_const_cols=True,
        weights_column=None,
        offset_column=None,
        fold_column=None,
        nfolds=0,
        fold_assignment="AUTO",
        keep_cross_validation_predictions=False,
        keep_cross_validation_models=True,
        stopping_rounds=0,
        stopping_metric="AUTO",
        stopping_tolerance=0.001,
        score_each_iteration=False,
        categorical_encoding="AUTO",
        export_checkpoints_dir=None,
        checkpoint=None,
    )

    def __init__(self, **kwargs):
        self._parms: Dict[str, Any] = dict(self._common_defaults)
        self._parms.update(self._param_defaults)
        for k, v in kwargs.items():
            if k not in self._parms:
                raise TypeError(f"{type(self).__name__}: unknown parameter {k!r}")
            self._parms[k] = v
        self._model: Optional[H2OModel] = None
        self.job: Optional[Job] = None

    def __getattr__(self, name):
        parms = object.__getattribute__(self, "_parms")
        if name in parms:
            return parms[name]
        raise AttributeError(name)

    def __setattr__(self, name, value):
        if name.startswith("_") or name in ("job",):
            object.__setattr__(self, name, value)
        elif name in self._parms:
            self._parms[name] = value
        else:
            object.__setattr__(self, name, value)

    def _is_supervised(self) -> bool:
        """Instance-level supervision check — overridable where a parameter
        flips it (e.g. DeepLearning autoencoder=True)."""
        return type(self).supervised

    @property
    def actual_params(self) -> Dict[str, Any]:
        return dict(self._parms)

    # -- training entrypoint (estimator_base.train) -------------------------
    def train(
        self,
        x: Optional[Sequence[str]] = None,
        y: Optional[str] = None,
        training_frame: Optional[Frame] = None,
        validation_frame: Optional[Frame] = None,
        **kw,
    ) -> "H2OEstimator":
        if training_frame is None:
            raise ValueError("training_frame is required")
        if self._is_supervised() and y is None:
            raise ValueError(f"{self.algo}: response column y is required")
        if getattr(training_frame, "_is_remote", False):
            # the frame lives on an attached server: train over REST and
            # bind a RemoteModel — the delegation surface below then works
            # unchanged (h2o-py estimator_base semantics). Dispatch AFTER
            # the client-side arg validation so bad calls raise locally.
            from ..client import remote_train

            return remote_train(self, x, y, training_frame, validation_frame)
        from ..runtime import phases, tracing

        # every fit begins here, so this is where the compile pipeline's
        # listener is installed (idempotent): whatever a fit traces,
        # compiles or loads is counted, and lands on its span tree
        phases.install_listener()
        # the fit's span tree (docs/observability.md): the same names for
        # every estimator, under the trace id of the REST request / job /
        # candidate that called, or one minted here
        with tracing.span("train", kind="fit", algo=self.algo,
                          rows=int(training_frame.nrow)) as sp:
            # the Vec rollups the screen reads tally themselves on this span
            with tracing.span("train.resolve", kind="fit",
                              rollups_computed=0, rollups_reused=0):
                x, training_frame, validation_frame, nfolds = self._resolve(
                    x, y, training_frame, validation_frame)
            sp.annotate(predictors=len(x))
            t0 = time.time()
            with tracing.span("train.fit", kind="fit"):
                model = self._fit(x, y, training_frame, validation_frame)
            # a fold_column triggers CV by itself (its folds are the
            # column's distinct values) — but only for estimators that CAN
            # cross-validate: TargetEncoder-style builders consume
            # fold_column for their own leakage handling inside _fit and
            # define no _cv_predict
            supports_cv = (type(self)._cv_predict
                           is not H2OEstimator._cv_predict)
            if ((nfolds >= 2
                 or (self._parms.get("fold_column") and supports_cv))
                    and self._is_supervised()):
                with tracing.span("train.cv", kind="fit"):
                    self._run_cv(model, x, y, training_frame, nfolds)
            model.run_time = time.time() - t0
            with tracing.span("train.publish", kind="fit"):
                self._publish(model)
        return self

    def _resolve(self, x, y, training_frame: Frame,
                 validation_frame: Optional[Frame]):
        """What `train` settles before the fit: the predictor list (the
        constant-column screen reads every column), the rows with a
        response, the Job, the seed and the fold plan. Returns
        (x, training_frame, validation_frame, nfolds)."""
        ignored = set(self._parms.get("ignored_columns") or [])
        if x is None:
            x = [
                n for n in training_frame.names
                if n != y and n not in ignored
                and n not in (self._parms.get("weights_column"),
                              self._parms.get("offset_column"),
                              self._parms.get("fold_column"))
            ]
        else:
            x = [training_frame.names[i] if isinstance(i, int) else i for i in x]
            x = [n for n in x if n != y and n not in ignored]
        if self._parms.get("ignore_const_cols", True):
            x = [n for n in x if not _is_const(training_frame.vec(n))]

        if self._is_supervised() and y is not None:
            # rows with a missing response are dropped before training —
            # ModelBuilder.init response filtering (hex/ModelBuilder.java)
            training_frame = _with_response(training_frame, y)
            if validation_frame is not None:
                validation_frame = _with_response(validation_frame, y)

        # a REST-created Job (h_train) rides through so /3/Jobs progress and
        # cancellation act on THE job driving this estimator
        ext = getattr(self, "_external_job", None)
        self.job = ext if ext is not None else Job(
            dest=f"{self.algo}_{next(_model_counter)}",
            description=f"{self.algo} train")
        if self.job.status == "CREATED":
            self.job.start()
        seed = int(self._parms.get("seed", -1))
        if seed in (-1, None):
            self._parms["_actual_seed"] = 1234
        else:
            self._parms["_actual_seed"] = seed

        nfolds = int(self._parms.get("nfolds") or 0)
        if nfolds < 0 or nfolds == 1:
            raise ValueError(
                f"nfolds must be 0 (no CV) or >= 2, got {nfolds}")
        fold_col = self._parms.get("fold_column")
        if fold_col and nfolds:
            raise ValueError(
                "specify EITHER nfolds OR fold_column, not both "
                "(hex/ModelBuilder cv_init)")
        if fold_col and fold_col not in training_frame.names:
            raise ValueError(f"fold_column {fold_col!r} not in frame")
        return x, training_frame, validation_frame, nfolds

    def _publish(self, model: "H2OModel") -> None:
        """Hand the fitted model over: the DKV, the Job's result, the
        checkpoint export."""
        self._model = model
        from ..runtime.dkv import DKV

        DKV.put(model.model_id, model)  # h2o.get_model / h2o.models surface
        # result before done(): a REST poller that sees DONE must be able to
        # fetch the model that instant (h_train's thread sets result later,
        # which would leave a 404 window)
        self.job.result = model.model_id
        self.job.done()
        ckpt_dir = self._parms.get("export_checkpoints_dir")
        if ckpt_dir:
            # auto-export the finished model (Model export_checkpoints_dir)
            try:
                from ..mojo import save_model

                save_model(model, ckpt_dir, force=True)
            except TypeError:
                pass  # artifact format doesn't cover this algo yet

    # -- n-fold CV (ModelBuilder.computeCrossValidation) --------------------
    def _run_cv(self, model: H2OModel, x, y, train: Frame, nfolds: int):
        n = train.nrow
        rng = np.random.default_rng(self._parms["_actual_seed"])
        fold_col = self._parms.get("fold_column")
        if fold_col:
            assign = train.vec(fold_col).numeric_np().astype(np.int64)
            folds = np.unique(assign)
        else:
            mode = self._parms.get("fold_assignment", "AUTO")
            if mode in ("AUTO", "Random"):
                assign = rng.integers(0, nfolds, n)
            elif mode == "Modulo":
                assign = np.arange(n) % nfolds
            else:  # Stratified — approximate by per-class modulo
                yv = train.vec(y).numeric_np()
                order = np.argsort(yv, kind="mergesort")
                assign = np.empty(n, np.int64)
                assign[order] = np.arange(n) % nfolds
            folds = np.arange(nfolds)
        # -- CV fold reuse ---------------------------------------------------
        # Tree builders expose the parent fit's BinnedMatrix; folds then
        # reuse its codes via row-index slicing instead of two full
        # `Frame.take` copies + a per-fold re-bin/re-pack (LightGBM/XGBoost-
        # style CV over one quantized matrix). The fold frame shrinks to the
        # response + any *_column parameters. H2O3_CV_REBIN=1 (or the bench
        # comparator H2O3_TRAIN_LEGACY=1) restores the seed per-fold path,
        # which stays bit-exact with earlier rounds.
        import os as _os

        from ..parallel import distdata
        from ..runtime import trainpool as _trainpool

        reuse_bm = None
        if (_os.environ.get("H2O3_CV_REBIN", "") in ("", "0")
                and not _trainpool.legacy()
                and not distdata.multiprocess()
                and self._cv_can_reuse()):
            reuse_bm = self._cv_reuse_source(model, train)
        keep_cols = [y] + sorted(
            v for k, v in self._parms.items()
            if k.endswith("_column") and isinstance(v, str)
            and v in train.names and v != y)

        holdout = None
        cv_models = []
        for f in folds:
            idx_tr = np.nonzero(assign != f)[0]
            idx_ho = np.nonzero(assign == f)[0]
            sub = type(self)()
            sub._parms.update(
                {k: v for k, v in self._parms.items() if not k.startswith("_")}
            )
            sub._parms["nfolds"] = 0
            sub._parms["model_id"] = None  # fold models get their own ids
            sub._parms["_actual_seed"] = self._parms["_actual_seed"]
            _trainpool.record_cv_fold(reused=reuse_bm is not None)
            if reuse_bm is not None:
                # reuse folds take their NATURAL row bucket instead of the
                # parent's padded shape: pad rows are zero-weight no-ops
                # (results are padded-shape invariant), every fold of every
                # sweep candidate lands on the same ~((k-1)/k)-size bucket,
                # and the one extra compile amortizes across all of them —
                # while the parent shape would tax each fold ~k/(k-1)×
                # extra histogram compute forever.
                tr = Frame({nm: train.vec(nm).take(idx_tr)
                            for nm in keep_cols})
                sub._parms["_cv_reuse"] = dict(bm=reuse_bm, rows=idx_tr)
                cvm = sub._fit(x, y, tr, None)
                pred = sub._cv_predict_codes(cvm, reuse_bm.codes[idx_ho])
            else:
                # seed path: pad fold fits up to the parent's padded row
                # shape so every fold reuses the parent's compiled tree
                # program (a second program load is not free)
                sub._parms["_npad_floor"] = getattr(model, "_npad", 0)
                tr = train.take(idx_tr)
                ho = train.take(idx_ho)
                cvm = sub._fit(x, y, tr, None)
                pred = sub._cv_predict(cvm, ho)
            if holdout is None:
                holdout = np.zeros((n,) + pred.shape[1:], dtype=np.float64)
            holdout[assign == f] = pred
            if self._parms.get("keep_cross_validation_models", True):
                if reuse_bm is not None:
                    # fold validation metrics straight from the holdout
                    # prediction (same probabilities _make_metrics would
                    # score — the codes path IS the scoring path here)
                    cvm.validation_metrics = self._metrics_from_cv(
                        train.vec(y).take(idx_ho), None, pred)
                else:
                    cvm.validation_metrics = cvm._make_metrics(ho)
                cv_models.append(cvm)
        model._cv_holdout_pred = holdout
        model.cross_validation_models = cv_models or None
        model.cross_validation_metrics = self._metrics_from_cv(train.vec(y), assign, holdout)

    def _metrics_from_cv(self, yvec: Vec, assign, holdout):
        if yvec.type == "enum" and yvec.nlevels == 2:
            return ModelMetricsBinomial.make(np.asarray(yvec.data), holdout[:, -1] if holdout.ndim > 1 else holdout)
        if yvec.type == "enum":
            return ModelMetricsMultinomial.make(np.asarray(yvec.data), holdout)
        return ModelMetricsRegression.make(yvec.numeric_np(), holdout if holdout.ndim == 1 else holdout[:, 0])

    def _cv_predict(self, model: H2OModel, frame: Frame) -> np.ndarray:
        """Holdout prediction as probabilities (classif) or values (regr)."""
        raise NotImplementedError

    # -- CV fold-reuse hooks (overridden by builders that can slice a
    # parent-fit artifact per fold — see shared_tree.py) --------------------
    def _cv_can_reuse(self) -> bool:
        return False

    def _cv_reuse_source(self, model: H2OModel, train: Frame):
        return None

    def _cv_predict_codes(self, model: H2OModel, codes) -> np.ndarray:
        raise NotImplementedError

    def _fit(self, x, y, train: Frame, valid: Optional[Frame]) -> H2OModel:
        raise NotImplementedError

    # -- model delegation ---------------------------------------------------
    @property
    def model(self) -> H2OModel:
        if self._model is None:
            raise ValueError("model not trained; call train() first")
        return self._model

    def predict(self, test_data: Frame) -> Frame:
        return self.model.predict(test_data)

    def model_performance(self, test_data=None, valid=False, xval=False):
        if test_data is not None:
            return self.model.model_performance(test_data)
        return self.model._m(valid=valid, xval=xval)

    # metric passthroughs
    def auc(self, **kw):
        return self.model.auc(**kw)

    def logloss(self, **kw):
        return self.model.logloss(**kw)

    def rmse(self, **kw):
        return self.model.rmse(**kw)

    def mse(self, **kw):
        return self.model.mse(**kw)

    def varimp(self, **kw):
        return self.model.varimp(**kw)

    # model-understanding passthroughs (h2o-py keeps these on the estimator)
    def partial_plot(self, *a, **kw):
        return self.model.partial_plot(*a, **kw)

    def permutation_importance(self, *a, **kw):
        return self.model.permutation_importance(*a, **kw)

    def predict_contributions(self, *a, **kw):
        return self.model.predict_contributions(*a, **kw)

    def predict_leaf_node_assignment(self, *a, **kw):
        return self.model.predict_leaf_node_assignment(*a, **kw)

    def staged_predict_proba(self, *a, **kw):
        return self.model.staged_predict_proba(*a, **kw)

    def feature_frequencies(self, *a, **kw):
        return self.model.feature_frequencies(*a, **kw)

    @property
    def scoring_history(self):
        return self.model.scoring_history

    @property
    def model_id(self):
        return self.model.model_id


def warn_host_solver(algo: str, n_rows: int, bound: int = 500_000) -> None:
    """Long-tail algorithms solve host-side in numpy (documented in
    docs/architecture.md §"Host-side solvers"): correct at their usual
    scale, but a big frame deserves a loud heads-up rather than a silent
    slow fit."""
    if n_rows > bound:
        from ..runtime.log import Log

        Log.warn(
            f"{algo}: {n_rows} rows exceed the ~{bound} row envelope of "
            "this host-side (numpy) solver; expect host memory/time to "
            "scale accordingly (docs/architecture.md)")


def _is_const(v: Vec) -> bool:
    r = v.rollup()
    return r.nacnt < len(v) and r.min == r.max


def _with_response(fr: Frame, y: str) -> Frame:
    """`fr` without the rows whose response is NA; `fr` itself when the
    response's rollup says there are none."""
    v = fr.vec(y)
    if v.nacnt() == 0:
        return fr
    return fr.take(np.nonzero(~v.isna_np())[0])


def response_info(yvec: Vec):
    """(problem_kind, nclass, domain) from the response Vec — mirrors
    ModelBuilder's distribution inference from response type."""
    if yvec.type == "enum":
        k = yvec.nlevels
        if k < 2:
            raise ValueError(
                "categorical response has fewer than two classes "
                "(ModelBuilder rejects constant responses)")
        return ("binomial" if k == 2 else "multinomial"), k, yvec.domain
    return "regression", 1, None
