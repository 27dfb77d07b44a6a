"""H2OXGBoostEstimator — tree_method=tpu_hist.

Reference parity: `h2o-ext-xgboost/src/main/java/hex/tree/xgboost/`
(`XGBoost.java`, `XGBoostModel.java` parameter mapping, `remote/` Rabit
workers) wrapping the native `libxgboost4j` `hist`/`gpu_hist`/`approx`
updaters; estimator surface `h2o-py/h2o/estimators/xgboost.py`. The
BASELINE north star: `tree_method=hist → tpu_hist` (MSLR-WEB30K lambdarank).

Rebuild: there is no JNI/DMatrix layer — frame columns are already bin codes
in HBM, and the `gpu_hist` CUDA updater's job is done by the same
`ops/histogram.py` kernels GBM uses (`tpu_hist`); Rabit allreduce ≡ the
`lax.psum` the tree builder already does under shard_map. This class maps
XGBoost parameter names onto the shared-tree driver and adds:
* XGBoost-exact leaf regularization: reg_lambda shrinks the Newton step and
  reg_alpha soft-thresholds G (xgboost CalcWeight), both applied inside
  `tree.build_tree`,
* `rank:ndcg` lambdarank objective with query groups — pairwise ΔNDCG
  weighted gradients (the xgboost `rank:ndcg` objective).
"""

from __future__ import annotations

from typing import Optional

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..frame.frame import Frame
from ..runtime import phases as _phases
from ..runtime import tracing as _tracing
from .glm import GLMModel as _GLMModelBase
from .metrics import ndcg_at_k
from .shared_tree import H2OSharedTreeEstimator, SharedTreeModel


class _GBLinearModel(_GLMModelBase):
    """gblinear's fitted model: a GLMModel (it IS a generalized linear
    model — same scoring, coef tables, metrics) under the xgboost algo
    identity, so model ids and summaries say what trained it."""

    algo = "xgboost"


class H2OXGBoostEstimator(H2OSharedTreeEstimator):
    algo = "xgboost"
    _mode = "gbm"
    _param_defaults = dict(
        ntrees=50,
        max_depth=6,
        min_rows=1.0,                 # = min_child_weight
        min_child_weight=None,
        learn_rate=0.3,               # = eta
        eta=None,
        sample_rate=1.0,              # = subsample
        subsample=None,
        col_sample_rate=1.0,          # = colsample_bylevel
        colsample_bylevel=None,
        col_sample_rate_per_tree=1.0,  # = colsample_bytree
        colsample_bytree=None,
        max_abs_leafnode_pred=0.0,
        max_delta_step=0.0,
        score_tree_interval=0,
        min_split_improvement=0.0,    # = gamma
        gamma=None,
        nthread=-1,
        max_bins=256,
        max_leaves=0,
        tree_method="auto",           # auto/exact/approx/hist → all tpu_hist
        grow_policy="depthwise",
        booster="gbtree",
        reg_lambda=1.0,
        reg_alpha=0.0,
        quiet_mode=True,
        distribution="AUTO",
        tweedie_power=1.5,
        normalize_type="tree",
        rate_drop=0.0,
        one_drop=False,
        skip_drop=0.0,
        dmatrix_type="auto",
        backend="auto",
        gpu_id=None,
        objective=None,               # e.g. "rank:ndcg" (+ group_column)
        group_column=None,
        ndcg_k=10,
    )

    def _tree_params(self):
        p = self._parms
        def pick(a, b, default):
            va = p.get(a)
            return float(va) if va is not None else float(p.get(b, default) or default)

        return dict(
            ntrees=int(p.get("ntrees", 50)),
            max_depth=int(p.get("max_depth", 6)),
            min_rows=pick("min_child_weight", "min_rows", 1.0),
            nbins=int(p.get("max_bins", 256)) - 1,  # +1 NA bin added downstream
            learn_rate=pick("eta", "learn_rate", 0.3),
            learn_rate_annealing=1.0,
            sample_rate=pick("subsample", "sample_rate", 1.0),
            col_sample_rate=pick("colsample_bylevel", "col_sample_rate", 1.0),
            col_sample_rate_per_tree=pick("colsample_bytree", "col_sample_rate_per_tree", 1.0),
            min_split_improvement=pick("gamma", "min_split_improvement", 0.0),
            histogram_type="QuantilesGlobal",  # xgboost hist = sketch quantiles
            mtries=0,
            reg_lambda=float(p.get("reg_lambda", 1.0)),
            reg_alpha=float(p.get("reg_alpha", 0.0)),
            grow_policy=str(p.get("grow_policy", "depthwise")),
            max_leaves=int(p.get("max_leaves", 0) or 0),
            # xgboost: 0 = no cap, for both knobs
            max_abs_leaf=min(
                float(p.get("max_abs_leafnode_pred", 0) or 0) or np.inf,
                float(p.get("max_delta_step", 0) or 0) or np.inf),
            # DART dropout boosting (h2o-ext-xgboost booster=dart
            # passthrough; xgboost dart.cc). Dropout granularity here is a
            # boosting ROUND (all K class trees of the round together).
            dart=(dict(rate_drop=float(p.get("rate_drop", 0) or 0),
                       one_drop=bool(p.get("one_drop", False)),
                       skip_drop=float(p.get("skip_drop", 0) or 0),
                       normalize_type=str(p.get("normalize_type", "tree")))
                  if str(p.get("booster", "gbtree")) == "dart" else None),
        )

    def _check_params(self):
        """Reject accepted-but-unimplemented combinations LOUDLY — upstream
        either honors or errors on these (`hex/tree/xgboost/XGBoostModel.java`
        createParamsMap); training something silently different is worse
        than failing."""
        p = self._parms
        booster = str(p.get("booster", "gbtree"))
        if booster not in ("gbtree", "dart", "gblinear"):
            raise ValueError(f"booster={booster!r}: expected 'gbtree', "
                             "'dart', or 'gblinear'")
        if booster == "gblinear":
            obj = p.get("objective")
            if obj and str(obj).startswith("rank"):
                raise ValueError(
                    f"objective={obj!r} is not supported with "
                    "booster='gblinear' (lambdarank needs trees)")
            dist = str(p.get("distribution", "AUTO"))
            if dist not in ("AUTO", "gaussian", "bernoulli", "multinomial"):
                raise ValueError(
                    f"distribution={dist!r} with booster='gblinear': only "
                    "AUTO/gaussian/bernoulli/multinomial links are "
                    "implemented for the linear booster")
        for k in ("rate_drop", "skip_drop"):
            v = float(p.get(k, 0) or 0)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{k}={v}: must be in [0, 1]")
            if v != 0.0 and booster != "dart":
                raise ValueError(f"{k} is a DART parameter; set "
                                 "booster='dart' to use it")
        if bool(p.get("one_drop", False)) and booster != "dart":
            raise ValueError("one_drop is a DART parameter; set "
                             "booster='dart' to use it")
        if str(p.get("normalize_type", "tree")) not in ("tree", "forest"):
            raise ValueError("normalize_type must be 'tree' or 'forest'")
        gp = str(p.get("grow_policy", "depthwise"))
        if gp not in ("depthwise", "lossguide"):
            raise ValueError(f"grow_policy={gp!r}: expected 'depthwise' or "
                             "'lossguide'")
        if gp == "lossguide":
            ml = int(p.get("max_leaves", 0) or 0)
            if ml == 1 or ml < 0:
                raise ValueError(f"max_leaves={ml}: a tree has at least 2 "
                                 "leaves (0 = bounded by max_depth only)")
            if int(p.get("max_depth", 6)) < 1:
                raise ValueError(
                    "grow_policy='lossguide' needs max_depth >= 1: the heap "
                    "tree layout is depth-capped (leaf-wise growth stops at "
                    "max_leaves OR max_depth, whichever binds first)")
            if p.get("monotone_constraints"):
                raise ValueError("monotone_constraints are not yet supported "
                                 "with grow_policy='lossguide'")
        elif int(p.get("max_leaves", 0) or 0) > 0:
            raise ValueError("max_leaves needs grow_policy='lossguide' "
                             "(depthwise growth is bounded by max_depth)")

    def _cv_can_reuse(self) -> bool:
        """gblinear folds fit a DataInfo design from the fold frame's raw x
        columns, and ranking folds rebuild lambdarank state (and NDCG) from
        the fold frame — both need full fold frames, not sliced codes."""
        if str(self._parms.get("booster", "gbtree")) == "gblinear":
            return False
        obj = self._parms.get("objective")
        if obj and str(obj).startswith("rank"):
            return False
        return super()._cv_can_reuse()

    def _fit(self, x, y, train: Frame, valid: Optional[Frame]):
        self._check_params()
        if str(self._parms.get("booster", "gbtree")) == "gblinear":
            return self._fit_gblinear(x, y, train, valid)
        obj = self._parms.get("objective")
        if obj and str(obj).startswith("rank"):
            gcol = self._parms.get("group_column") or "qid"
            if gcol not in train.names:
                raise ValueError(
                    f"objective={obj!r} needs group_column (qid); {gcol!r} not in frame"
                )
            from ..parallel import distdata

            k = int(self._parms.get("ndcg_k", 10))
            with _tracing.span("fit.objective", kind="fit") as sp:
                # the objective contract is GLOBAL rows in global order: on
                # a multi-process cloud, gather qid/rel once so query groups
                # that span ingest-shard boundaries stay whole (upstream
                # rabit gets this for free from its single DMatrix; here the
                # gather is the equivalent one-time cost)
                qid = distdata.allgather_rows(
                    train.vec(gcol).numeric_np().astype(np.int64))
                rel = distdata.allgather_rows(
                    train.vec(y).numeric_np().astype(np.float64))
                x = [n for n in x if n != gcol]
                self._objective_fn = _make_lambdarank(qid, rel, k)
                plan = dict(self._objective_fn.rank_plan)
                sp.annotate(n_classes=len(plan.pop("classes")), **plan)
            try:
                model = super()._fit(x, y, train, valid)
                margins = self._final_margins
            finally:
                self._objective_fn = self._final_margins = None
            # NDCG as the headline metric for ranking models (global rows),
            # from the fit's own final training margins, as every other
            # training metric is: the forest is not scored a second time
            with _tracing.span("fit.ndcg", kind="fit"):
                scores = distdata.allgather_rows(margins[:, 0])
                model.training_metrics.ndcg = ndcg_at_k(rel, scores, qid, k)
            model.training_metrics.description = (
                f"NDCG@{k}={model.training_metrics.ndcg:.5f}")
            return model
        return super()._fit(x, y, train, valid)

    def _fit_gblinear(self, x, y, train: Frame, valid: Optional[Frame]):
        """`booster="gblinear"` — the linear booster (upstream
        h2o-ext-xgboost passes it through to xgboost's `gblinear` with the
        shotgun/coordinate updater; `xgboost/src/linear/updater_shotgun.cc`
        CoordinateDelta).

        TPU-first: instead of per-coordinate sequential updates, each
        boosting round is ONE Jacobi ("shotgun") pass — two MXU matmuls
        (Xᵀg and (X∘X)ᵀh) produce every coordinate's gradient/hessian sums
        against the current margin, the elastic-net delta (reg_lambda L2,
        reg_alpha soft-threshold, xgboost's CoordinateDelta formula) is
        applied to all weights at once, damped by eta. All rounds run in a
        single jitted lax.scan. The learned coefficients are wrapped in a
        GLMModel, which reuses the GLM scoring/metrics/coef surface — a
        gblinear model IS a (boosted) generalized linear model."""
        from ..parallel import distdata
        from ..parallel import mesh as cloudlib
        from .glm import attach_linear_artifacts
        from .model_base import DataInfo, response_info

        p = self._parms
        yvec = train.vec(y)
        problem, nclass, domain = response_info(yvec)
        family = {"binomial": "binomial",
                  "multinomial": "multinomial"}.get(problem, "gaussian")
        dist = str(p.get("distribution", "AUTO"))
        if dist != "AUTO":
            # an explicitly requested link must MATCH the response type —
            # silently training a different family is worse than failing
            want = {"bernoulli": "binomial", "multinomial": "multinomial",
                    "gaussian": "gaussian"}[dist]
            if want != family:
                raise ValueError(
                    f"distribution={dist!r} is inconsistent with the "
                    f"response ({problem}, which implies {family}); drop "
                    "the distribution parameter or fix the response type")
        rounds = int(p.get("ntrees", 50))
        eta = float(p.get("eta") if p.get("eta") is not None
                    else p.get("learn_rate", 0.3) or 0.3)
        lam = float(p.get("reg_lambda", 1.0))
        alpha = float(p.get("reg_alpha", 0.0))

        dinfo = DataInfo(train, x, standardize=False)
        n = train.nrow
        w = (train.vec(p["weights_column"]).numeric_np()
             if p.get("weights_column") else np.ones(n)).astype(np.float32)
        if family == "binomial":
            yarr = (np.asarray(yvec.data, np.float32)
                    if yvec.type == "enum"
                    else yvec.numeric_np().astype(np.float32))
        elif family == "multinomial":
            yarr = np.asarray(yvec.data, np.float32)
        else:
            yarr = yvec.numeric_np().astype(np.float32)

        cloud = cloudlib.cloud()
        if distdata.multiprocess():
            # same global-row ingest as GLM: every rank contributes its
            # shard; the jitted scan over global sharded arrays makes XLA
            # insert the cross-host reductions
            X = dinfo.fit_transform(train)
            Xi = np.concatenate([X, np.ones((n, 1), np.float32)], axis=1)
            quota = distdata.local_quota(n)
            Xd = distdata.global_row_array(Xi.astype(np.float32), quota, cloud)
            yd = distdata.global_row_array(yarr, quota, cloud)
            wd = distdata.global_row_array(w, quota, cloud)
        elif cloud.size > 1 and n >= cloud.size:
            X = dinfo.fit_transform(train)
            Xi = np.concatenate([X, np.ones((n, 1), np.float32)], axis=1)
            npad = cloudlib.pad_to_multiple(n, cloud.size)
            padn = npad - n
            rs = cloud.row_sharding()
            Xd = jax.device_put(jnp.asarray(np.concatenate(
                [Xi, np.zeros((padn, Xi.shape[1]), np.float32)])), rs)
            yd = jax.device_put(jnp.asarray(np.concatenate(
                [yarr, np.zeros(padn, np.float32)])), rs)
            wd = jax.device_put(jnp.asarray(np.concatenate(
                [w, np.zeros(padn, np.float32)])), rs)
        else:
            Xd = dinfo.device_design(train, fit=True, add_intercept=True)
            yd, wd = jnp.asarray(yarr), jnp.asarray(w)

        K = nclass if family == "multinomial" else 1
        W = _gblinear_train(Xd, yd, wd, family=family, n_class=K,
                            rounds=rounds, eta=eta, lam=lam, alpha=alpha)
        beta = (np.asarray(W, np.float64) if family == "multinomial"
                else np.asarray(W[0], np.float64))

        model = _GBLinearModel(self, x, y, dinfo, family, beta, domain,
                               lambda_best=lam)
        return attach_linear_artifacts(model, train, valid, Xd, cloud.size, n)

    def _cv_predict(self, model, frame: Frame) -> np.ndarray:
        if isinstance(model, _GLMModelBase):  # gblinear fold models
            return model._score(frame)
        return super()._cv_predict(model, frame)

    def ndcg(self, frame: Optional[Frame] = None,
             k: Optional[int] = None) -> float:
        """NDCG@k of `frame` by query group; with no frame, the training
        NDCG@ndcg_k the fit reported (`training_metrics.ndcg`)."""
        from ..parallel import distdata

        if frame is None:
            return float(self.model.training_metrics.ndcg)
        gcol = self._parms.get("group_column") or "qid"
        qid = distdata.allgather_rows(
            frame.vec(gcol).numeric_np().astype(np.int64))
        rel = distdata.allgather_rows(
            frame.vec(self.model.y).numeric_np().astype(np.float64))
        scores = distdata.allgather_rows(
            self.model._margins(self.model._matrix(frame))[:, 0])
        return ndcg_at_k(rel, scores, qid,
                         k or int(self._parms.get("ndcg_k", 10)))


@functools.partial(jax.jit, static_argnames=("family", "n_class", "rounds"))
def _gblinear_train(Xd, yd, wd, *, family: str, n_class: int, rounds: int,
                    eta: float, lam: float, alpha: float):
    """All gblinear boosting rounds as one jitted lax.scan.

    Per round: margins via one (n,p)×(p,K) matmul, per-row (g, h) from the
    family's link, coordinate gradient/hessian sums via Xᵀg and (X∘X)ᵀh,
    then xgboost's CoordinateDelta (elastic net + clamp-at-zero crossing)
    applied Jacobi-style to every weight, damped by eta. The intercept
    (last design column) is unregularized, like xgboost's bias updater.
    HIGHEST precision keeps the f32 sums exact (TPU matmuls default to
    bf16 operands)."""
    pdim = Xd.shape[1]
    hi = jax.lax.Precision.HIGHEST
    X2 = Xd * Xd
    is_bias = jnp.zeros(pdim, jnp.float32).at[pdim - 1].set(1.0)
    lam_v = lam * (1.0 - is_bias)[:, None]          # (p, 1) broadcast over K
    alpha_v = alpha * (1.0 - is_bias)[:, None]
    onehot = (jax.nn.one_hot(yd.astype(jnp.int32), n_class, dtype=jnp.float32)
              if family == "multinomial" else None)

    def one_round(Wt, _):
        # Wt: (p, K) — transposed so the coord axis is leading
        margin = jnp.matmul(Xd, Wt, precision=hi)   # (n, K)
        if family == "binomial":
            mu = jax.nn.sigmoid(margin[:, 0])
            g = ((mu - yd) * wd)[:, None]
            h = (mu * (1 - mu) * wd)[:, None]
        elif family == "multinomial":
            pr = jax.nn.softmax(margin, axis=1)
            g = (pr - onehot) * wd[:, None]
            # xgboost multiclass_obj: h = 2·p·(1−p)
            h = 2.0 * pr * (1 - pr) * wd[:, None]
        else:
            g = ((margin[:, 0] - yd) * wd)[:, None]
            h = wd[:, None]
        G = jnp.matmul(Xd.T, g, precision=hi)       # (p, K)
        H = jnp.matmul(X2.T, h, precision=hi)
        gl2 = G + lam_v * Wt
        denom = H + lam_v
        tmp = Wt - gl2 / denom
        dw = jnp.where(tmp >= 0,
                       jnp.maximum(-(gl2 + alpha_v) / denom, -Wt),
                       jnp.minimum(-(gl2 - alpha_v) / denom, -Wt))
        dw = jnp.where(H < 1e-5, 0.0, dw)           # xgboost's hess guard
        return Wt + eta * dw, None

    W0 = jnp.zeros((pdim, n_class), jnp.float32)
    Wt, _ = jax.lax.scan(one_round, W0, None, length=rounds)
    return Wt.T                                     # (K, p)


# One lax.map chunk of the pairwise pass holds at most this many pair slots
# (a `(q_chunk, width, width)` block), so one huge query (MSLR has ~1250-doc
# queries) cannot inflate memory to its class's whole queries x width².
_PAIR_BLOCK = 1 << 27
_LANES = 128                # widths are multiples of the chip's lane tile
_MAX_CLASSES = 8            # programs a pass compiles, whatever the frame


def _class_widths(sizes: np.ndarray) -> np.ndarray:
    """The widths queries are padded to, from the query sizes alone.

    Multiples of the chip's 128-lane tile, doubling from 128 (128, 256, 512,
    ...) with the top width at the largest query rounded up to a multiple of
    128; of more than `_MAX_CLASSES` the smallest go (their queries join the
    smallest width kept), and a width no query falls under is dropped. A
    query belongs to the smallest width that holds it, so queries of one
    size (fixed candidate lists) make ONE class: the (Q, G, G) program of a
    frame padded to its largest query, G rounded up to the tile (250
    documents a query run as 256: at most 5 % more slots)."""
    top = -(-max(int(sizes.max()), 1) // _LANES) * _LANES
    widths = [_LANES]
    while widths[-1] * 2 < top:
        widths.append(widths[-1] * 2)
    if widths[-1] < top:
        widths.append(top)
    widths = np.asarray(widths[-_MAX_CLASSES:], np.int64)
    return widths[np.unique(np.searchsorted(widths, sizes))]


def _make_lambdarank(qid: np.ndarray, rel: np.ndarray, k: int):
    """Pairwise lambdarank (g, h) — xgboost `rank:ndcg`.

    For each query, pairs (i, j) with rel_i > rel_j contribute
    λ = -σ(-(s_i - s_j)) · |ΔNDCG_ij| to g_i (and +λ to g_j); h gets
    σ(1-σ)|ΔNDCG|.

    TPU-first: queries are grouped into a few size classes
    (`_class_widths`), each padded to its class's width and not to the
    largest query of the frame, and the whole pairwise pass runs as ONE
    jitted program per boosting round — per class a (Q_c, W_c, W_c) batched
    pairwise block in lax.map chunks, read back to rows through each row's
    one slot. (A per-query host loop costs ~1 s per tree on MSLR-sized data;
    this is a single device dispatch.) Ranks use pairwise comparison counts
    with an index tiebreak — equivalent to a stable sort rank. Rows may come
    in any order, a query's need not be contiguous."""
    N = len(qid)
    with _tracing.span("objective.groups", kind="fit"):
        order = np.argsort(qid, kind="mergesort")
        qs = qid[order]
        starts = np.flatnonzero(np.r_[True, qs[1:] != qs[:-1]])
        sizes = np.diff(np.r_[starts, N])
        Q = len(starts)
        of_query = np.repeat(np.arange(Q), sizes)
        widths = _class_widths(sizes)
        cls = np.searchsorted(widths, sizes)
        # a class's queries keep their frame order, padded to a whole number
        # of chunks by choosing the chunk from the count (12,500 queries in
        # two chunks are 2 x 6,250, not 2 x 8,192)
        classes, layout, first_slot = [], [], np.zeros(Q, np.int64)
        n_slots = 0
        for c, W in enumerate(widths):
            mem = np.flatnonzero(cls == c)
            n_chunks = -(-len(mem) // max(1, _PAIR_BLOCK // int(W * W)))
            q_chunk = -(-len(mem) // n_chunks)
            classes.append(dict(width=int(W), queries=len(mem),
                                padded_queries=n_chunks * q_chunk,
                                q_chunk=q_chunk))
            layout.append((mem, n_slots, (n_chunks, q_chunk, int(W))))
            first_slot[mem] = n_slots + np.arange(len(mem)) * W
            n_slots += n_chunks * q_chunk * int(W)
        # every row owns exactly one slot: its query's first + its place
        slot = np.empty(N, np.int64)
        slot[order] = first_slot[of_query] + np.arange(N) - starts[of_query]
        idx = np.full(n_slots, N, np.int64)         # N = pad slot
        idx[slot] = np.arange(N)
        gains = (2.0 ** rel - 1.0).astype(np.float64)
        rflat = np.concatenate([rel.astype(np.float64), [0.0]])[idx]
        gflat = np.concatenate([gains, [0.0]])[idx]
        # the ordered pairs the objective is a sum over (r_i > r_j): half of
        # what a query's n² leaves once its equal-relevance pairs are out
        levels, lvl = np.unique(rel, return_inverse=True)
        _, ties = np.unique(of_query * len(levels) + lvl[order],
                            return_counts=True)
        pairs = (int((sizes.astype(np.int64) ** 2).sum())
                 - int((ties.astype(np.int64) ** 2).sum())) // 2
    with _tracing.span("objective.idcg", kind="fit"):
        # per-query ideal DCG@k (static — relevance doesn't change per round)
        idcg = np.zeros(Q)
        for qi, (s, n) in enumerate(zip(starts, sizes)):
            ideal = np.sort(rel[order[s:s + n]])[::-1]
            idcg[qi] = ((2.0 ** ideal - 1)
                        / np.log2(np.arange(2, len(ideal) + 2)))[:k].sum()
        inv_idcg = np.where(idcg > 0, 1.0 / np.maximum(idcg, 1e-12), 0.0)

    rank_plan = dict(
        queries=int(Q), group_max=int(sizes.max()),
        group_mean=float(N / Q), pairs=int(pairs),
        pair_slots=int(sum(c["padded_queries"] * c["width"] ** 2
                           for c in classes)),
        classes=classes)
    with _tracing.span("objective.upload", kind="fit") as sp:
        def up(a, dtype):
            return _phases.accounted_h2d(
                lambda: jnp.asarray(a, dtype),
                a.size * np.dtype(dtype).itemsize)

        class_d = []
        for mem, lo, shape in layout:
            def part(a):
                return a[lo:lo + int(np.prod(shape))].reshape(shape)

            inv = np.zeros(shape[:2])
            inv.reshape(-1)[: len(mem)] = inv_idcg[mem]
            class_d.append((up(part(idx), jnp.int32),
                            up(part(rflat), jnp.float32),
                            up(part(gflat), jnp.float32),
                            up(inv, jnp.float32)))
        class_d = tuple(class_d)
        slot_d = up(slot, jnp.int32)
        sp.annotate(bytes_h2d=int(sum(
            a.nbytes for a in jax.tree_util.tree_leaves((class_d, slot_d)))))

    def objective(margin_dev, y_dev):
        return _lambdarank_pass(margin_dev, class_d, slot_d)

    objective.rank_plan = rank_plan
    return objective


@jax.jit
def _lambdarank_pass(margin, classes, slot):
    """One lambdarank (g, h) pass over all (padded) query groups.

    `classes` holds, per size class, the group tensors `(idx, rel, gain)` of
    shape (n_chunks, q_chunk, width) and `inv_idcg` of (n_chunks, q_chunk);
    `slot` is each row's place in the classes' slots laid end to end. They
    arrive as ARGUMENTS (not closure captures) so the HLO carries no data
    literals and the persistent compilation cache keys on shapes only — the
    same convention as the tree builder's _one_tree. Returns g/h padded
    with zeros to len(margin) (the tree build's padded row count)."""
    n_rows = slot.shape[0]
    # pad slots (idx == n_rows) read the sentinel; real pad rows of the
    # margin vector are never referenced by idx (idx < n_rows)
    s_pad = jnp.concatenate(
        [margin[:n_rows].astype(jnp.float32), jnp.zeros(1, jnp.float32)])

    def chunk(args):
        ii, rr, gg, inv = args
        G = ii.shape[1]
        vv = ii < n_rows
        with jax.named_scope("rank.scores"):
            sc = s_pad[ii]                                  # (qb, G)
            sc = jnp.where(vv, sc, -jnp.inf)
        with jax.named_scope("rank.pairs"):
            # rank = #better-scored + #equal-scored-earlier (stable-sort rank)
            gt = (sc[:, :, None] < sc[:, None, :]) & vv[:, None, :]
            eq = (sc[:, :, None] == sc[:, None, :]) & vv[:, None, :]
            earlier = jnp.arange(G)[None, :] < jnp.arange(G)[:, None]  # j<i
            rk = gt.sum(axis=2) + (eq & earlier[None, :, :]).sum(axis=2)
            disc = jnp.where(
                vv, 1.0 / jnp.log2(rk.astype(jnp.float32) + 2.0), 0.0)
            dG = gg[:, :, None] - gg[:, None, :]
            dD = disc[:, :, None] - disc[:, None, :]
            delta = jnp.abs(dG * dD) * inv[:, None, None]
            sij = jnp.where(vv, sc, 0.0)
            sij = sij[:, :, None] - sij[:, None, :]
            rho = jax.nn.sigmoid(-jnp.clip(sij, -35, 35))
            pair_ok = (rr[:, :, None] > rr[:, None, :]) \
                & vv[:, :, None] & vv[:, None, :]
            lam = jnp.where(pair_ok, rho * delta, 0.0)
            hess = jnp.where(pair_ok, rho * (1 - rho) * delta, 0.0)
            g_q = -(lam.sum(axis=2) - lam.sum(axis=1))      # (qb, G)
            h_q = hess.sum(axis=2) + hess.sum(axis=1)
        return g_q, h_q

    per_class = [jax.lax.map(chunk, group) for group in classes]
    with jax.named_scope("rank.rows"):
        g = jnp.concatenate([g_c.reshape(-1) for g_c, _ in per_class])[slot]
        h = jnp.concatenate([h_c.reshape(-1) for _, h_c in per_class])[slot]
    M = margin.shape[0]
    g_full = jnp.zeros(M, jnp.float32).at[:n_rows].set(g)
    h_full = jnp.full(M, 1e-6, jnp.float32).at[:n_rows].set(
        jnp.maximum(h, 1e-6))
    return g_full, h_full


XGBoost = H2OXGBoostEstimator
