"""ModelMetrics — per-problem metric hierarchy.

Reference parity: `h2o-core/src/main/java/hex/ModelMetrics*.java`
(`ModelMetricsBinomial`, `ModelMetricsMultinomial`, `ModelMetricsRegression`,
`ModelMetricsClustering`), `hex/AUC2.java` (threshold-binned ROC: 400-bin
score histogram → AUC / pr-AUC / max-F1 and friends), `hex/ConfusionMatrix.java`.

The reference computes these inside scoring MRTasks via
`ModelMetrics.MetricBuilder` map/reduce; here the reductions are numpy on
gathered predictions, on one host core, with the same binned-AUC design
available for the distributed path. Gini = 2·AUC−1 as in AUC2.

That is not cheap relative to training: at 1,812,500 rows the binomial
metrics were 0.98 s of a 2.1 s GLM fit on a v5e host while they sorted the
scores three times (ledger and `PERF.md` §5, PR 24). So
`ModelMetricsBinomial.make` orders the scores ONCE (`order_scores`) and the
exact AUC, the threshold sweep and the gains/lift table all read that one
ordering; what it costs now is in `PERF.md` §5.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from ..runtime import tracing, workspace

MAX_AUC_BINS = 400  # AUC2.NBINS


class ScoreOrder(NamedTuple):
    """The scores of one `(y, p)` in ascending order, ties in row order (the
    permutation of `np.argsort(p, kind="stable")`), and what every order
    statistic below reads of `y` in that order."""
    ps: np.ndarray   # p[order], float64
    cum: np.ndarray  # n + 1 running sums: cum[i] = y[order][:i].sum()
    path: str        # how the permutation was made: "packed32" | "argsort"


def _stable_order(p: np.ndarray, take=workspace.fresh):
    """`(order, p[order], path)` with `order` the stable ascending argsort
    of float64 `p`. Scores that float32 holds exactly and that carry no sign
    bit order like their bit patterns, so there the permutation comes from
    one sort of the uint64 keys `bits << 32 | row` (the row breaks ties in
    row order): the same permutation, several times sooner. `take` makes
    the row-sized arrays (`runtime/workspace.py`); what is n-sized besides
    is made a block at a time."""
    n = len(p)
    p32 = take("order.p32", n, np.float32)
    with np.errstate(over="ignore"):
        np.copyto(p32, p, casting="same_kind")
    if (0 < n < 2 ** 32 and p32.view(np.int32).min() >= 0
            and np.array_equal(p32, p)):
        key = take("order.key", n, np.uint64)
        np.copyto(key, p32.view(np.uint32))
        key <<= np.uint64(32)
        for b in workspace.blocks(n):
            key[b] |= np.arange(b.start, b.stop, dtype=np.uint64)
        key.sort()
        ps = take("order.ps", n, np.float64)
        for b in workspace.blocks(n):
            ps[b] = (key[b] >> np.uint64(32)).astype(np.uint32).view(
                np.float32)
        key &= np.uint64(0xFFFFFFFF)
        return key.view(np.int64), ps, "packed32"
    order = np.argsort(p, kind="stable")
    return order, p[order], "argsort"


def order_scores(y: np.ndarray, p: np.ndarray,
                 take=workspace.fresh) -> ScoreOrder:
    """The one O(n log n) step of the binomial metrics."""
    order, ps, path = _stable_order(np.asarray(p, np.float64), take)
    cum = take("order.cum", len(ps) + 1, np.float64)
    cum[0] = 0.0
    np.take(np.asarray(y, np.float64), order, out=cum[1:], mode="clip")
    np.cumsum(cum[1:], out=cum[1:])
    return ScoreOrder(ps, cum, path)


def roc_curve_binned(y: np.ndarray, p: np.ndarray, nbins: int = MAX_AUC_BINS,
                     ordering: Optional[ScoreOrder] = None,
                     take=workspace.fresh):
    """AUC2's design: histogram scores into <=400 threshold bins, then sweep.
    `ordering`, where the caller has one, is `order_scores(y, p)`."""
    ps, cum, _ = ordering or order_scores(y, p)
    n = len(ps)
    # np.quantile partitions a copy of its input: the copy is made here,
    # where `take` decides whose memory it is
    work = take("roc.work", n, np.float64)
    np.copyto(work, ps)
    qs = np.unique(np.quantile(work, np.linspace(0, 1, nbins),
                               overwrite_input=True))
    # bin b holds the scores in (qs[b-1], qs[b]] — searchsorted(qs, p,
    # "left") — and is counted between the places of its two edges in the
    # sorted scores; the last bin, above the maximum, stays empty
    at = np.append(np.searchsorted(ps, qs, side="right"), n)
    npos = np.diff(cum[at], prepend=0.0)
    nneg = np.diff(at, prepend=0) - npos
    # descending threshold sweep
    tp = np.cumsum(npos[::-1])[::-1]
    fp = np.cumsum(nneg[::-1])[::-1]
    P, Ntot = cum[-1], n - cum[-1]
    tpr = tp / max(P, 1e-12)
    fpr = fp / max(Ntot, 1e-12)
    return qs, tpr, fpr, tp, fp, P, Ntot


def auc_exact(y: np.ndarray, p: np.ndarray,
              ordering: Optional[ScoreOrder] = None,
              take=workspace.fresh) -> float:
    """Exact rank AUC (ties handled) — matches AUC2 in the limit of one bin
    per distinct score. `ordering` as for `roc_curve_binned`."""
    ps, cum, _ = ordering or order_scores(y, p)
    n = len(ps)
    npos = cum[-1]
    nneg = n - npos
    if npos == 0 or nneg == 0:
        return float("nan")
    # a run of tied scores [start, end) shares the average of its ranks
    # start+1 .. end, so the positives' rank sum is a sum over runs, not
    # over rows: `edge` holds every run's start and, one on, its end
    ne = take("auc.ne", n + 1, np.bool_)
    ne[0] = ne[n] = True
    np.not_equal(ps[1:], ps[:-1], out=ne[1:n])
    m = int(np.count_nonzero(ne))
    edge = take("auc.edge", m, np.intp)
    k = 0
    for b in workspace.blocks(n + 1):
        at = np.flatnonzero(ne[b])
        at += b.start
        edge[k:k + len(at)] = at
        k += len(at)
    ranks = take("auc.ranks", m - 1, np.float64)
    for b in workspace.blocks(m - 1):
        lo, hi = edge[b], edge[b.start + 1:b.stop + 1]
        np.subtract(cum[hi], cum[lo], out=ranks[b])  # a run's positives ...
        ranks[b] *= lo + hi         # ... times twice its average rank, less 1
    rank_sum = (ranks.sum() + npos) / 2
    return float((rank_sum - npos * (npos + 1) / 2) / (npos * nneg))


class MetricValue(float):
    """Float that is also callable with h2o-py's method signature.

    h2o-py exposes metrics as methods (`perf.auc()`, `perf.rmse()`) while the
    internal code reads attributes (`m.auc`); wrapping plain-float fields in
    this keeps both call styles working.
    """

    __slots__ = ()

    def __call__(self, *_a, **_kw) -> float:
        return float(self)


@dataclass
class ModelMetricsBase:
    mse: float = float("nan")
    rmse: float = float("nan")
    nobs: int = 0
    description: str = ""

    def __setattr__(self, k, v):
        # dataclass __init__ assigns via setattr, so this wraps both
        # construction and later post-hoc assignments (e.g. KMeans metrics)
        if isinstance(v, (float, np.floating)) \
                and not isinstance(v, MetricValue):
            v = MetricValue(v)
        object.__setattr__(self, k, v)

    def _ser(self) -> Dict:
        return {k: float(v) if isinstance(v, MetricValue) else v
                for k, v in self.__dict__.items() if not k.startswith("_")}


@dataclass
class ModelMetricsRegression(ModelMetricsBase):
    mae: float = float("nan")
    rmsle: float = float("nan")
    r2: float = float("nan")
    mean_residual_deviance: float = float("nan")

    @staticmethod
    def make(y: np.ndarray, pred: np.ndarray) -> "ModelMetricsRegression":
        y = np.asarray(y, np.float64)
        pred = np.asarray(pred, np.float64)
        err = pred - y
        mse = float(np.mean(err**2))
        with np.errstate(invalid="ignore"):
            rmsle = (
                float(np.sqrt(np.mean((np.log1p(pred) - np.log1p(y)) ** 2)))
                if (pred > -1).all() and (y > -1).all()
                else float("nan")
            )
        var = float(np.var(y))
        return ModelMetricsRegression(
            mse=mse, rmse=float(np.sqrt(mse)), nobs=len(y),
            mae=float(np.mean(np.abs(err))), rmsle=rmsle,
            r2=1.0 - mse / var if var > 0 else float("nan"),
            mean_residual_deviance=mse,
        )


def gains_lift_table(y: np.ndarray, p: np.ndarray, groups: int = 16,
                     ordering: Optional[ScoreOrder] = None):
    """Quantile gains/lift table — `hex/GainsLift.java` (16 groups default):
    per group cumulative capture rate, lift, response rate. Rows go by
    descending score, ties in row order. `ordering` as for
    `roc_curve_binned`."""
    ps, cum, _ = ordering or order_scores(y, p)
    n = len(ps)
    total_pos = max(cum[-1], 1e-12)
    bounds = np.unique((np.arange(1, groups + 1) * n) // groups)
    bounds = bounds[bounds > 0]  # n < groups would emit an empty first group
    rows = []
    prev = 0
    cum_pos = 0.0
    overall_rate = total_pos / n
    for b in bounds:
        # the b highest scores are every score above v = ps[n - b] and, of
        # v's tie run [lo, hi), the first hi - (n - b) rows: the descending
        # order is the ascending one reversed run by run
        v = ps[n - b]
        lo = np.searchsorted(ps, v)
        hi = np.searchsorted(ps, v, side="right")
        s = cum[-1] - cum[hi] + cum[lo + hi - (n - b)] - cum[lo] - cum_pos
        cum_pos += s
        rate = s / max(b - prev, 1)
        rows.append(dict(
            group=len(rows) + 1,
            cumulative_data_fraction=b / n,
            lower_threshold=float(v),
            lift=float(rate / overall_rate),
            cumulative_lift=float((cum_pos / b) / overall_rate),
            response_rate=float(rate),
            cumulative_response_rate=float(cum_pos / b),
            capture_rate=float(s / total_pos),
            cumulative_capture_rate=float(cum_pos / total_pos),
            gain=100.0 * (rate / overall_rate - 1),
            cumulative_gain=100.0 * ((cum_pos / b) / overall_rate - 1),
        ))
        prev = b
    return rows


# `ModelMetricsBinomial.make`'s row-sized arrays by stage. Those of different
# stages are never alive together and share a buffer, so a thread that scores
# n rows keeps seven of 8n bytes: y, p, the sorted scores, the running sums
# and these three.
_MAKE_SHARED = {
    "order.key": "w0", "auc.edge": "w0", "logloss.a": "w0", "roc.work": "w0",
    "order.p32": "w1", "auc.ranks": "w1", "logloss.b": "w1",
    "auc.ne": "w2", "logloss.c": "w2", "confusion": "w2",
}


def _make_buffers(name: str, n: int, dtype) -> np.ndarray:
    return workspace.take("metrics." + _MAKE_SHARED.get(name, name), n, dtype)


@dataclass
class ModelMetricsBinomial(ModelMetricsBase):
    auc: float = float("nan")
    pr_auc: float = float("nan")
    logloss: float = float("nan")
    gini: float = float("nan")
    mean_per_class_error: float = float("nan")
    f1: float = float("nan")
    accuracy: float = float("nan")
    confusion_matrix: Optional[np.ndarray] = None
    threshold: float = 0.5
    gains_lift_table: Optional[List[Dict]] = None
    _roc: Optional[tuple] = None

    def gains_lift(self):
        return self.gains_lift_table

    def roc(self):
        """(fpr, tpr) arrays over the binned threshold sweep (AUC2)."""
        return self._roc

    @staticmethod
    def make(y: np.ndarray, p: np.ndarray) -> "ModelMetricsBinomial":
        # ONE ordering of the scores (`metrics.order`), then the three order
        # statistics read off it, each a child span of the fit's
        # `fit.metrics` (docs/observability.md): the exact AUC, the binned
        # threshold sweep, the gains/lift table. Every row-sized array of it
        # lives in this thread's work buffers (`runtime/workspace.py`), and
        # nothing row-sized leaves: the arithmetic is the plain formulas',
        # operation for operation, written into a buffer instead of a new
        # array
        take = _make_buffers
        n = len(y)
        y, y_in = take("make.y", n, np.float64), y
        np.copyto(y, y_in, casting="unsafe")
        p, p_in = take("make.p", n, np.float64), p
        np.copyto(p, p_in, casting="unsafe")
        np.clip(p, 1e-15, 1 - 1e-15, out=p)
        with tracing.span("metrics.order", kind="fit") as sp:
            ordering = order_scores(y, p, take)
            sp.annotate(path=ordering.path)
        with tracing.span("metrics.auc", kind="fit"):
            auc = auc_exact(y, p, ordering=ordering, take=take)
        # -mean(y log p + (1 - y) log(1 - p)), then mean((p - y)^2)
        a = take("logloss.a", n, np.float64)
        b = take("logloss.b", n, np.float64)
        c = take("logloss.c", n, np.float64)
        np.log(p, out=a)
        a *= y
        np.subtract(1, p, out=b)
        np.log(b, out=b)
        np.subtract(1, y, out=c)
        b *= c
        a += b
        logloss = float(-np.mean(a)) if n else float("nan")
        np.subtract(p, y, out=a)
        np.multiply(a, a, out=a)
        mse = float(np.mean(a)) if n else float("nan")
        # max-F1 threshold via the AUC2-style binned sweep
        with tracing.span("metrics.roc", kind="fit"):
            qs, tpr, fpr, tp, fp, P, Ntot = roc_curve_binned(
                y, p, ordering=ordering, take=take)
        fn = P - tp
        prec = tp / np.maximum(tp + fp, 1e-12)
        rec = tp / max(P, 1e-12)
        f1s = 2 * prec * rec / np.maximum(prec + rec, 1e-12)
        bi = int(np.argmax(f1s))
        thr = float(qs[min(bi, len(qs) - 1)]) if len(qs) else 0.5
        # the confusion matrix at that threshold: four counts of rows
        hat, pos, neg, both = take("confusion", 4 * n, np.bool_).reshape(4, n)
        np.greater_equal(p, thr, out=hat)
        np.equal(y, 1, out=pos)
        np.equal(y, 0, out=neg)
        tp_ = float(np.count_nonzero(np.logical_and(hat, pos, out=both)))
        fp_ = float(np.count_nonzero(np.logical_and(hat, neg, out=both)))
        tn_ = float(np.count_nonzero(neg)) - fp_
        fn_ = float(np.count_nonzero(pos)) - tp_
        cm = np.asarray([[tn_, fp_], [fn_, tp_]])
        err0 = fp_ / max(tn_ + fp_, 1e-12)
        err1 = fn_ / max(tp_ + fn_, 1e-12)
        # pr_auc by trapezoid over recall
        order = np.argsort(rec)
        pr_auc = float(np.trapezoid(prec[order], rec[order])) if len(rec) > 1 else float("nan")
        with tracing.span("metrics.gains", kind="fit"):
            gains = gains_lift_table(y, p, ordering=ordering)
        return ModelMetricsBinomial(
            mse=mse, rmse=float(np.sqrt(mse)), nobs=n,
            auc=auc, pr_auc=pr_auc, logloss=logloss, gini=2 * auc - 1,
            mean_per_class_error=(err0 + err1) / 2, f1=float(f1s[bi]),
            accuracy=(tp_ + tn_) / n if n else float("nan"),
            confusion_matrix=cm, threshold=thr,
            gains_lift_table=gains,
            _roc=(fpr, tpr),
        )

    @staticmethod
    def from_binned(qs: np.ndarray, npos: np.ndarray, nneg: np.ndarray,
                    nll_sum: float, sq_sum: float) -> "ModelMetricsBinomial":
        """Metrics from a 400-bin score histogram — `hex/AUC2.java`'s exact
        design: every statistic (AUC/pr-AUC/max-F1/CM/gains) derives from
        per-threshold-bin (pos, neg) counts, so only ~KBs ever leave the
        device. The AUC is the binned trapezoid, which IS the reference's
        reported AUC semantics (AUC2 sweeps its 400 bins the same way)."""
        qs = np.asarray(qs, np.float64)
        npos = np.asarray(npos, np.float64)
        nneg = np.asarray(nneg, np.float64)
        # merge bins with duplicate thresholds (host roc_curve_binned
        # np.unique semantics: ties collapse into one bin)
        uq = np.unique(qs)
        npos_m = np.zeros(len(uq) + 1)
        nneg_m = np.zeros(len(uq) + 1)
        # bin b of searchsorted(qs,...) maps to searchsorted(uq,...) bins
        edge_map = np.searchsorted(uq, qs, side="left")
        full_map = np.concatenate([edge_map, [len(uq)]])
        np.add.at(npos_m, full_map, npos)
        np.add.at(nneg_m, full_map, nneg)
        npos, nneg, qs = npos_m, nneg_m, uq
        P = float(npos.sum())
        Ntot = float(nneg.sum())
        n = P + Ntot
        tp = np.cumsum(npos[::-1])[::-1]
        fp = np.cumsum(nneg[::-1])[::-1]
        tpr = tp / max(P, 1e-12)
        fpr = fp / max(Ntot, 1e-12)
        order = np.argsort(fpr)
        auc = float(np.trapezoid(
            np.r_[0.0, tpr[order], 1.0], np.r_[0.0, fpr[order], 1.0]))
        prec = tp / np.maximum(tp + fp, 1e-12)
        rec = tpr
        f1s = 2 * prec * rec / np.maximum(prec + rec, 1e-12)
        bi = int(np.argmax(f1s))
        thr = float(qs[min(bi, len(qs) - 1)]) if len(qs) else 0.5
        # confusion at the max-F1 threshold straight from the sweep counts
        tp_, fp_ = float(tp[bi]), float(fp[bi])
        fn_, tn_ = P - tp_, Ntot - fp_
        cm = np.asarray([[tn_, fp_], [fn_, tp_]])
        err0 = fp_ / max(tn_ + fp_, 1e-12)
        err1 = fn_ / max(tp_ + fn_, 1e-12)
        oi = np.argsort(rec)
        pr_auc = (float(np.trapezoid(prec[oi], rec[oi]))
                  if len(rec) > 1 else float("nan"))
        # gains/lift from the bin counts (16 cumulative-count groups)
        glt = []
        tot = npos + nneg
        cum_rows = np.cumsum(tot[::-1])[::-1]          # rows scored >= bin
        cum_pos = tp
        prev_rows = prev_pos = 0.0
        for gidx in range(1, 17):
            target = n * gidx / 16.0
            # the group boundary may fall INSIDE a tied-score block (bins
            # cannot split ties); split the block fractionally, assuming a
            # uniform positive rate within it — the expectation of the
            # exact-sort table's arbitrary tie ordering
            sel = int(np.searchsorted(-cum_rows, -target, side="left"))
            b = min(max(sel - 1, 0), len(tot) - 1)
            if cum_rows[b] < target and b > 0:
                b -= 1
            rows_above = float(cum_rows[b + 1]) if b + 1 < len(tot) else 0.0
            pos_above = float(cum_pos[b + 1]) if b + 1 < len(tot) else 0.0
            blk_rows = max(float(cum_rows[b]) - rows_above, 1e-12)
            blk_pos = float(cum_pos[b]) - pos_above
            f = min(max((target - rows_above) / blk_rows, 0.0), 1.0)
            rows = target
            pos = pos_above + f * blk_pos
            frac = rows / max(n, 1e-12)
            capture = pos / max(P, 1e-12)
            g_rows = max(rows - prev_rows, 0.0)
            g_pos = max(pos - prev_pos, 0.0)
            g_cap = g_pos / max(P, 1e-12)
            g_frac = g_rows / max(n, 1e-12)
            cum_lift = capture / max(frac, 1e-12)
            lift = g_cap / max(g_frac, 1e-12)
            glt.append(dict(
                group=gidx, cumulative_data_fraction=frac,
                lower_threshold=float(qs[min(b, len(qs) - 1)]) if len(qs)
                else 0.0,
                lift=lift, cumulative_lift=cum_lift,
                response_rate=g_pos / max(g_rows, 1e-12),
                cumulative_response_rate=pos / max(rows, 1e-12),
                capture_rate=g_cap, cumulative_capture_rate=capture,
                gain=100.0 * (lift - 1.0),
                cumulative_gain=100.0 * (cum_lift - 1.0),
            ))
            prev_rows, prev_pos = rows, pos
        mse = sq_sum / max(n, 1e-12)
        return ModelMetricsBinomial(
            mse=mse, rmse=float(np.sqrt(mse)), nobs=int(n),
            auc=auc, pr_auc=pr_auc, logloss=nll_sum / max(n, 1e-12),
            gini=2 * auc - 1,
            mean_per_class_error=(err0 + err1) / 2, f1=float(f1s[bi]),
            accuracy=(tp_ + tn_) / max(n, 1e-12),
            confusion_matrix=cm, threshold=thr,
            gains_lift_table=glt,
            _roc=(fpr, tpr),
        )


@dataclass
class ModelMetricsMultinomial(ModelMetricsBase):
    logloss: float = float("nan")
    mean_per_class_error: float = float("nan")
    accuracy: float = float("nan")
    confusion_matrix: Optional[np.ndarray] = None

    @staticmethod
    def make(y: np.ndarray, probs: np.ndarray) -> "ModelMetricsMultinomial":
        y = np.asarray(y).astype(np.int64)
        probs = np.clip(np.asarray(probs, np.float64), 1e-15, 1.0)
        probs = probs / probs.sum(axis=1, keepdims=True)
        K = probs.shape[1]
        n = len(y)
        logloss = float(-np.mean(np.log(probs[np.arange(n), y])))
        yhat = probs.argmax(axis=1)
        cm = np.zeros((K, K))
        np.add.at(cm, (y, yhat), 1)
        with np.errstate(invalid="ignore"):
            per_class_err = 1 - np.diag(cm) / np.maximum(cm.sum(axis=1), 1e-12)
        onehot = np.zeros((n, K))
        onehot[np.arange(n), y] = 1
        mse = float(np.mean((probs - onehot) ** 2))
        return ModelMetricsMultinomial(
            mse=mse, rmse=float(np.sqrt(mse)), nobs=n, logloss=logloss,
            mean_per_class_error=float(np.nanmean(per_class_err)),
            accuracy=float((yhat == y).mean()), confusion_matrix=cm,
        )


@dataclass
class ModelMetricsClustering(ModelMetricsBase):
    tot_withinss: float = float("nan")
    betweenss: float = float("nan")
    totss: float = float("nan")


def ndcg_at_k(y: np.ndarray, score: np.ndarray, qid: np.ndarray, k: int = 10) -> float:
    """NDCG@k grouped by query — the lambdarank objective's eval metric
    (XGBoost `rank:ndcg`, used by the MSLR-WEB30K baseline config)."""
    total, nq = 0.0, 0
    y, score, qid = np.asarray(y), np.asarray(score), np.asarray(qid)
    # one stable sort by query, then a slice a query (queries in ascending
    # id, rows in frame order): a mask a query reads all N rows Q times
    by_q = np.argsort(qid, kind="mergesort")
    cuts = np.flatnonzero(np.diff(qid[by_q])) + 1
    for m in np.split(by_q, cuts):
        rel = y[m]
        s = score[m]
        if len(rel) < 2:
            continue
        order = np.argsort(-s, kind="mergesort")
        gains = (2 ** rel[order][:k] - 1) / np.log2(np.arange(2, min(k, len(rel)) + 2))
        ideal = np.sort(rel)[::-1]
        igains = (2 ** ideal[:k] - 1) / np.log2(np.arange(2, min(k, len(rel)) + 2))
        if igains.sum() > 0:
            total += gains.sum() / igains.sum()
            nq += 1
    return total / max(nq, 1)
