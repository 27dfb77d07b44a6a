"""`ModelMetricsBinomial.make` orders the scores once (`order_scores`) and
reads the exact AUC, the threshold sweep and the gains/lift table off that one
ordering. Held here against the three-sort versions it replaced, kept below
as the plain references, and against an O(n^2) pair count."""

import numpy as np
import pytest

from h2o3_tpu.models import metrics as M
from h2o3_tpu.models.metrics import (ModelMetricsBinomial, auc_exact,
                                     gains_lift_table, order_scores,
                                     roc_curve_binned)
from h2o3_tpu.runtime import tracing


# -- the references: one sort each, as the program had them -------------------

def _ref_auc(y, p):
    y = np.asarray(y).astype(np.float64)
    order = np.argsort(p, kind="mergesort")
    ps = np.asarray(p)[order]
    _, start = np.unique(ps, return_index=True)
    end = np.append(start[1:], len(ps))
    r = np.empty(len(ps))
    r[order] = np.repeat((start + 1 + end) / 2.0, end - start)
    npos = y.sum()
    nneg = len(y) - npos
    if npos == 0 or nneg == 0:
        return float("nan")
    return float((r[y == 1].sum() - npos * (npos + 1) / 2) / (npos * nneg))


def _ref_roc(y, p, nbins=400):
    y = np.asarray(y).astype(np.float64)
    p = np.asarray(p).astype(np.float64)
    qs = np.unique(np.quantile(p, np.linspace(0, 1, nbins)))
    bins = np.searchsorted(qs, p, side="left")
    npos = np.bincount(bins, weights=y, minlength=len(qs) + 1)
    nneg = np.bincount(bins, weights=1 - y, minlength=len(qs) + 1)
    tp = np.cumsum(npos[::-1])[::-1]
    fp = np.cumsum(nneg[::-1])[::-1]
    P, Ntot = y.sum(), (1 - y).sum()
    return qs, tp / max(P, 1e-12), fp / max(Ntot, 1e-12), tp, fp, P, Ntot


def _ref_gains(y, p, groups=16):
    y = np.asarray(y, np.float64)
    order = np.argsort(-np.asarray(p), kind="mergesort")
    ys, ps = y[order], np.asarray(p)[order]
    n = len(ys)
    total_pos = max(ys.sum(), 1e-12)
    bounds = np.unique((np.arange(1, groups + 1) * n) // groups)
    bounds = bounds[bounds > 0]
    rows, prev, cum_pos, overall = [], 0, 0.0, total_pos / n
    for b in bounds:
        s = ys[prev:b].sum()
        cum_pos += s
        rate = s / max(b - prev, 1)
        rows.append(dict(
            group=len(rows) + 1, cumulative_data_fraction=b / n,
            lower_threshold=float(ps[b - 1]), lift=float(rate / overall),
            cumulative_lift=float((cum_pos / b) / overall),
            response_rate=float(rate),
            cumulative_response_rate=float(cum_pos / b),
            capture_rate=float(s / total_pos),
            cumulative_capture_rate=float(cum_pos / total_pos),
            gain=100.0 * (rate / overall - 1),
            cumulative_gain=100.0 * ((cum_pos / b) / overall - 1)))
        prev = b
    return rows


def _pair_auc(y, p):
    pos, neg = p[y == 1], p[y == 0]
    if not len(pos) or not len(neg):
        return float("nan")
    d = pos[:, None] - neg[None, :]
    return float(((d > 0).sum() + 0.5 * (d == 0).sum())
                 / (len(pos) * len(neg)))


# -- the inputs ---------------------------------------------------------------

def _case(name):
    rng = np.random.default_rng(sum(map(ord, name)))
    n = {"fewer_than_groups": 11, "one_row": 1}.get(name, 1000)
    eta = rng.normal(0, 1.5, n)
    p = 1 / (1 + np.exp(-eta))
    y = (rng.random(n) < p).astype(np.float64)
    if name == "heavy_ties":
        # two decimals: ~100 distinct scores, so a sixteenth of the rows
        # (62) ends inside a tie run at every one of the 16 boundaries
        p = np.round(p, 2)
    elif name == "all_equal":
        p = np.full(n, 0.25)
    elif name == "clipped_both_ends":
        p[::7], p[3::11] = 0.0, 1.0
    elif name == "single_class":
        y = np.ones(n)
    elif name == "float32_exact":
        p = p.astype(np.float32).astype(np.float64)
    elif name == "float32_exact_ties":
        p = np.round(p, 2).astype(np.float32).astype(np.float64)
    return y, p


CASES = ["no_ties", "heavy_ties", "all_equal", "clipped_both_ends",
         "single_class", "fewer_than_groups", "one_row", "float32_exact",
         "float32_exact_ties", "float64_inexact"]
# the cases whose every clipped score is a float32 (0.25 is one)
PACKED = {"float32_exact", "float32_exact_ties", "all_equal"}


def _clip(p):
    return np.clip(np.asarray(p, np.float64), 1e-15, 1 - 1e-15)


def _same(a, b, tol=0.0):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    both_nan = np.isnan(a) & np.isnan(b)
    assert (both_nan | (np.abs(a - b) <= tol)).all(), (a, b)


def _order_path():
    (sp,) = [s for s in tracing.spans() if s["name"] == "metrics.order"]
    return sp["attrs"]["path"]


# -- make() against the references, the standalone functions and pairs --------

@pytest.mark.parametrize("case", CASES)
def test_make_equals_the_three_sort_references(case):
    y, p = _case(case)
    tracing.clear()
    m = ModelMetricsBinomial.make(y, p)
    pc = _clip(p)
    # exactly one ordering a call, made the way the input allows
    assert _order_path() == ("packed32" if case in PACKED else "argsort")
    # the rank AUC: same formula, same float64; and the pair count
    _same(m.auc, _ref_auc(y, pc), 1e-12)
    _same(m.auc, _pair_auc(y, pc), 1e-12)
    _same(m.gini, 2 * _ref_auc(y, pc) - 1, 1e-12)
    # the sweep: thresholds and counts equal exactly
    qs, tpr, fpr, tp, fp, P, Ntot = _ref_roc(y, pc)
    _same(m.roc()[0], fpr)
    _same(m.roc()[1], tpr)
    prec = tp / np.maximum(tp + fp, 1e-12)
    rec = tp / max(P, 1e-12)
    f1s = 2 * prec * rec / np.maximum(prec + rec, 1e-12)
    bi = int(np.argmax(f1s))
    assert m.threshold == float(qs[min(bi, len(qs) - 1)])
    assert m.f1 == float(f1s[bi])
    if len(rec) > 1:
        oi = np.argsort(rec)
        _same(m.pr_auc, np.trapezoid(prec[oi], rec[oi]), 1e-12)
    else:
        assert np.isnan(m.pr_auc)
    yhat = (pc >= m.threshold).astype(np.float64)
    cm = [[((yhat == 0) & (y == 0)).sum(), ((yhat == 1) & (y == 0)).sum()],
          [((yhat == 0) & (y == 1)).sum(), ((yhat == 1) & (y == 1)).sum()]]
    _same(m.confusion_matrix, cm)
    assert m.nobs == len(y)
    # gains/lift: group sizes, thresholds and counts exact, lifts to 1e-12
    ref = _ref_gains(y, pc)
    got = m.gains_lift()
    assert len(got) == len(ref) == min(16, len(y))
    for g, r in zip(got, ref):
        assert g.keys() == r.keys()
        for k in ("group", "cumulative_data_fraction", "lower_threshold"):
            assert g[k] == r[k], k
        for k in r:
            _same(g[k], r[k], 1e-12)


@pytest.mark.parametrize("case", CASES)
def test_standalone_functions_equal_the_references(case):
    """Called alone, each builds its own ordering and gives what it gave."""
    y, p = _case(case)
    pc = _clip(p)
    _same(auc_exact(y, pc), _ref_auc(y, pc), 1e-12)
    for got, ref in zip(roc_curve_binned(y, pc), _ref_roc(y, pc)):
        _same(got, ref)
    assert gains_lift_table(y, pc) == _ref_gains(y, pc)
    # and handed the shared ordering they give the same again
    o = order_scores(y, pc)
    _same(auc_exact(y, pc, o), auc_exact(y, pc))
    for got, ref in zip(roc_curve_binned(y, pc, ordering=o),
                        roc_curve_binned(y, pc)):
        _same(got, ref)
    assert gains_lift_table(y, pc, ordering=o) == gains_lift_table(y, pc)


def test_a_gains_boundary_inside_a_tie_run_counts_rows_in_row_order():
    """The descending order is the ascending one reversed run by run: of a
    tie run cut by a group boundary, the FIRST rows go to the upper group."""
    y, p = _case("heavy_ties")
    ps = np.sort(p)
    n = len(p)
    cut = [b for b in (np.arange(1, 17) * n) // 16
           if b < n and ps[n - b] == ps[n - b - 1]]
    assert len(cut) >= 8          # the case does what its name says
    # positives first in row order within every run, then last: the tables
    # differ, and each equals its reference
    first = np.lexsort((-y, p))
    for rows in (first, first[::-1]):
        yy, pp = y[rows], p[rows]
        assert gains_lift_table(yy, pp) == _ref_gains(yy, pp)
    a, b = (gains_lift_table(y[r], p[r]) for r in (first, first[::-1]))
    assert a[0]["capture_rate"] > b[0]["capture_rate"]
    assert a[-1]["cumulative_capture_rate"] == 1.0 \
        == b[-1]["cumulative_capture_rate"]


# -- how the ordering is made -------------------------------------------------

@pytest.mark.parametrize("case,path", [
    ("float32_exact", "packed32"), ("float32_exact_ties", "packed32"),
    ("all_equal", "packed32"),            # 0.25 is a float32
    ("no_ties", "argsort"), ("heavy_ties", "argsort"),
])
def test_both_paths_give_the_stable_permutation(case, path):
    _, p = _case(case)
    order, ps, took = M._stable_order(p)
    assert took == path
    want = np.argsort(p, kind="stable")
    assert order.dtype == want.dtype and (order == want).all()
    assert ps.dtype == np.float64 and (ps == p[want]).all()


@pytest.mark.parametrize("p", [
    np.array([0.5, -0.25, 0.5]),          # a sign bit: bits do not order
    np.array([0.0, -0.0, 0.0, -0.0]),     # equal as floats, not as bits
    np.array([0.5, np.nan, 0.25]),
    np.array([1e300, 0.5]),               # overflows float32
    np.array([]),
], ids=["negative", "minus_zero", "nan", "overflow", "empty"])
def test_the_packed_key_is_taken_only_where_bits_order_like_values(p):
    order, ps, took = M._stable_order(p)
    assert took == "argsort"
    assert (order == np.argsort(p, kind="stable")).all()
