"""The host stages of a fit that walk all rows compute in work buffers a
thread keeps (`runtime/workspace.py`) and make nothing row-sized besides: at
7,250,000 rows every float64 temporary is 58 MB of never-touched pages, and
the page faults, not the arithmetic, were what a fit waited for. Held here:
the buffers' contract, and that what is computed in them equals the plain
numpy formulas it replaced bit for bit — `np.nanmean` / `np.nanstd`, the
`np.mod` integrality rule of the transfer groups, `np.bincount`, and
`ModelMetricsBinomial.make` written with one new array an operation."""

import threading
import tracemalloc

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.vec import Vec
from h2o3_tpu.models import metrics as M
from h2o3_tpu.models.model_base import (DataInfo, _column_moments,
                                        _level_counts)
from h2o3_tpu.runtime import workspace


# -- the buffers ---------------------------------------------------------------

def test_a_name_is_the_same_memory_until_it_has_to_grow():
    workspace.release()
    a = workspace.take("t.a", 100, np.float64)
    a[:] = 7.0
    b = workspace.take("t.a", 50, np.float32)
    assert np.shares_memory(a, b) and b.dtype == np.float32 and b.shape == (50,)
    other = workspace.take("t.b", 100, np.float64)
    assert not np.shares_memory(a, other)
    big = workspace.take("t.a", 1000, np.float64)
    assert big.shape == (1000,) and not np.shares_memory(a, big)
    assert sorted(b.nbytes for b in workspace._local.pool.values()) == [
        800, 8000]
    workspace.release()
    assert not hasattr(workspace._local, "pool")


def test_a_thread_has_its_own_buffers():
    workspace.release()
    mine = workspace.take("t.shared", 64, np.float64)
    theirs = []
    t = threading.Thread(
        target=lambda: theirs.append(workspace.take("t.shared", 64,
                                                    np.float64)))
    t.start()
    t.join()
    assert not np.shares_memory(mine, theirs[0])
    workspace.release()


@pytest.mark.parametrize("n,size", [(0, 4), (1, 4), (8, 4), (9, 4), (3, 10)])
def test_blocks_cover_the_range_once(n, size):
    got = np.concatenate([np.arange(n)[b] for b in workspace.blocks(n, size)]
                         + [np.arange(0)])
    assert np.array_equal(got, np.arange(n))
    assert all(b.stop - b.start <= size for b in workspace.blocks(n, size))


# -- design statistics ---------------------------------------------------------

def _column(kind, n, rng):
    if kind == "float32":
        return np.abs(rng.normal(800, 500, n)).astype(np.float32)
    if kind == "float64_nan":
        c = rng.normal(0, 1e6, n)
        c[rng.random(n) < 0.2] = np.nan
        return c
    if kind == "int64":
        return rng.integers(-5, 2400, n).astype(np.int64)
    if kind == "all_nan":
        return np.full(n, np.nan)
    if kind == "one_value_left":
        c = np.full(n, np.nan)
        c[n // 2] = 3.25
        return c
    raise AssertionError(kind)


@pytest.mark.parametrize("n", [1, 9, 1000, 70001])
@pytest.mark.parametrize("kind", ["float32", "float64_nan", "int64",
                                  "all_nan", "one_value_left"])
def test_column_moments_are_numpys_nanmean_and_nanstd(kind, n):
    data = _column(kind, n, np.random.default_rng(n))
    v = Vec(data, "int" if kind == "int64" else "real")
    c, isna, has_nan, n_ok, mean, std = _column_moments(v, fit=True)
    ref = np.asarray(data, np.float64)
    assert c.dtype == np.float64 and np.array_equal(c, ref, equal_nan=True)
    assert np.array_equal(isna, np.isnan(ref))
    assert has_nan == bool(np.isnan(ref).any())
    assert n_ok == int((~np.isnan(ref)).sum())
    with np.errstate(all="ignore"):
        want_m = float(np.nanmean(ref)) if n_ok else 0.0
        want_s = float(np.nanstd(ref)) if n_ok else 0.0
    assert mean == (want_m if np.isfinite(want_m) else 0.0)
    assert std == (want_s if np.isfinite(want_s) else 0.0)
    # scoring reads the column and its NaN flag alone
    c2, _, has_nan2, n_ok2, m2, s2 = _column_moments(v, fit=False)
    assert np.array_equal(c2, ref, equal_nan=True)
    assert (has_nan2, n_ok2, m2, s2) == (has_nan, 0, 0.0, 0.0)


@pytest.mark.parametrize("n,K", [(0, 3), (5, 3), (300000, 7), (600001, 352)])
def test_level_counts_are_bincount_without_the_nas(n, K):
    rng = np.random.default_rng(K)
    codes = rng.integers(-1, K + 2, n).astype(np.int32)   # NAs and strays
    want = np.bincount(codes[codes >= 0], minlength=K)[:K]
    assert np.array_equal(_level_counts(codes, K), want)


def _mod_rule(c):
    """The transfer group as the np.mod rule it replaced decided it."""
    def fits(g):
        if not c.size:
            return False
        with np.errstate(invalid="ignore"):
            if not bool(np.all(np.mod(c, 1.0) == 0.0)):
                return False
        lo, hi = (0.0, 255.0) if g == 0 else (-32768.0, 32767.0)
        return bool(lo <= c.min() and c.max() <= hi)
    return 0 if fits(0) else 1 if fits(1) else 2


GROUP_COLUMNS = {
    "bytes": lambda n, r: r.integers(0, 256, n),
    "byte_edge_over": lambda n, r: np.r_[r.integers(0, 256, n - 1), 256],
    "shorts": lambda n, r: r.integers(-32768, 32768, n),
    "short_edge_over": lambda n, r: np.r_[r.integers(0, 9, n - 1), 32768],
    "fraction_in_the_last_block": lambda n, r: np.r_[
        r.integers(0, 200, n - 1), 7.5],
    "fraction_first": lambda n, r: np.r_[0.25, r.integers(0, 200, n - 1)],
    "negative_zero": lambda n, r: np.r_[-0.0, r.integers(0, 200, n - 1)],
    "tiny_fraction_float32_rounds_away": lambda n, r: np.full(n, 100.0000001),
    "infinite": lambda n, r: np.r_[np.inf, r.integers(0, 200, n - 1)],
    "large_whole": lambda n, r: r.integers(0, 2 ** 40, n),
}


@pytest.mark.parametrize("n", [3, 70001, 200003])
@pytest.mark.parametrize("name", sorted(GROUP_COLUMNS))
def test_transfer_groups_are_the_mod_rules(name, n):
    rng = np.random.default_rng(n)
    col = np.asarray(GROUP_COLUMNS[name](n, rng), np.float64)
    fr = Frame({"x": Vec(col, "real"),
                "k": Vec(rng.integers(0, 3, n).astype(np.int32), "enum",
                         domain=["a", "b", "c"])})
    di = DataInfo(fr, ["x", "k"], standardize=True)
    di.device_design(fr, fit=True)
    assert di._transfer_groups == [_mod_rule(col.astype(np.float32))]


# -- binomial metrics ----------------------------------------------------------

def _plain_make(y, p):
    """`ModelMetricsBinomial.make` as it was: a new array an operation."""
    y = np.asarray(y, np.float64)
    p = np.clip(np.asarray(p, np.float64), 1e-15, 1 - 1e-15)
    n = len(p)
    order = np.argsort(p, kind="stable")
    ps = p[order]
    cum = np.zeros(n + 1)
    np.cumsum(y[order], out=cum[1:])
    npos, nneg = cum[-1], n - cum[-1]
    if npos == 0 or nneg == 0:
        auc = float("nan")
    else:
        edge = np.flatnonzero(np.concatenate(([True], ps[1:] != ps[:-1],
                                              [True])))
        ranks = np.diff(cum[edge])
        ranks *= edge[:-1] + edge[1:]
        auc = float(((ranks.sum() + npos) / 2 - npos * (npos + 1) / 2)
                    / (npos * nneg))
    logloss = float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))
    mse = float(np.mean((p - y) ** 2))
    qs = np.unique(np.quantile(ps, np.linspace(0, 1, M.MAX_AUC_BINS)))
    at = np.append(np.searchsorted(ps, qs, side="right"), n)
    bpos = np.diff(cum[at], prepend=0.0)
    bneg = np.diff(at, prepend=0) - bpos
    tp = np.cumsum(bpos[::-1])[::-1]
    fp = np.cumsum(bneg[::-1])[::-1]
    prec = tp / np.maximum(tp + fp, 1e-12)
    rec = tp / max(npos, 1e-12)
    f1s = 2 * prec * rec / np.maximum(prec + rec, 1e-12)
    bi = int(np.argmax(f1s))
    thr = float(qs[min(bi, len(qs) - 1)])
    yhat = (p >= thr).astype(np.float64)
    cm = np.asarray([[float(((yhat == 0) & (y == 0)).sum()),
                      float(((yhat == 1) & (y == 0)).sum())],
                     [float(((yhat == 0) & (y == 1)).sum()),
                      float(((yhat == 1) & (y == 1)).sum())]])
    return dict(auc=auc, logloss=logloss, mse=mse, threshold=thr,
                f1=float(f1s[bi]), accuracy=float((yhat == y).mean()),
                confusion_matrix=cm, roc=(fp / max(nneg, 1e-12),
                                          tp / max(npos, 1e-12)))


def _scores(kind, n, rng):
    y = (rng.random(n) < 0.4).astype(np.int32)
    p = 1 / (1 + np.exp(-(rng.normal(0, 1.5, n) + y)))
    if kind == "float32":
        return y, p.astype(np.float32)
    if kind == "float32_ties":
        return y, np.round(p, 2).astype(np.float32)
    if kind == "float32_saturated":
        return y, np.clip(np.round(p * 1.3, 3), 0, 1).astype(np.float32)
    if kind == "float64":
        return y, p
    if kind == "one_class":
        return np.ones(n, np.int32), p.astype(np.float32)
    raise AssertionError(kind)


def _same(m, want):
    for k, v in want.items():
        got = m.roc() if k == "roc" else getattr(m, k)
        if k == "roc":
            assert all(np.array_equal(a, b) for a, b in zip(got, v))
        elif isinstance(v, np.ndarray):
            assert np.array_equal(got, v), k
        else:
            assert got == v or (got != got and v != v), (k, got, v)


@pytest.mark.parametrize("n", [2, 100, 4097, 300001])
@pytest.mark.parametrize("kind", ["float32", "float32_ties",
                                  "float32_saturated", "float64",
                                  "one_class"])
def test_make_in_buffers_equals_make_in_new_arrays(kind, n):
    y, p = _scores(kind, n, np.random.default_rng(n))
    y0, p0 = y.copy(), p.copy()
    _same(M.ModelMetricsBinomial.make(y, p), _plain_make(y, p))
    assert np.array_equal(y, y0) and np.array_equal(p, p0)   # inputs untouched


def test_nothing_of_one_make_is_changed_by_the_next():
    rng = np.random.default_rng(3)
    y1, p1 = _scores("float32", 5000, rng)
    y2, p2 = _scores("float32_ties", 5000, rng)
    first = M.ModelMetricsBinomial.make(y1, p1)
    kept = (first.roc()[0].copy(), first.roc()[1].copy(),
            first.confusion_matrix.copy(), list(first.gains_lift_table))
    M.ModelMetricsBinomial.make(y2, p2)
    assert np.array_equal(first.roc()[0], kept[0])
    assert np.array_equal(first.roc()[1], kept[1])
    assert np.array_equal(first.confusion_matrix, kept[2])
    assert first.gains_lift_table == kept[3]
    for arr in (*first.roc(), first.confusion_matrix):
        assert not any(np.shares_memory(arr, b) for b in
                       workspace._local.pool.values())


def test_the_standalone_helpers_keep_their_results_out_of_the_buffers():
    rng = np.random.default_rng(4)
    y, p = _scores("float32", 3000, rng)
    one = M.order_scores(y, p.astype(np.float64))
    ps, cum = one.ps.copy(), one.cum.copy()
    M.ModelMetricsBinomial.make(*_scores("float32_ties", 3000, rng))
    M.order_scores(*_scores("float32", 3000, rng))
    assert np.array_equal(one.ps, ps) and np.array_equal(one.cum, cum)


def test_a_second_make_allocates_nothing_row_sized():
    n = 1_500_000
    y, p = _scores("float32", n, np.random.default_rng(5))
    M.ModelMetricsBinomial.make(y, p)           # takes the buffers
    tracemalloc.start()
    try:
        M.ModelMetricsBinomial.make(y, p)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    # a few temporaries of one 2^18-row block and 400 bins: under ONE float64
    # column (8n bytes), where the plain formulas hold a dozen at once
    assert peak < 8 * n, peak


def test_a_second_design_build_allocates_what_it_uploads():
    n = 400_000
    rng = np.random.default_rng(6)
    fr = Frame({"a": Vec(np.abs(rng.normal(800, 500, n)).astype(np.float32),
                         "real"),
                "b": Vec(rng.integers(0, 2400, n).astype(np.float32), "real"),
                "k": Vec(rng.integers(0, 29, n).astype(np.int32), "enum",
                         domain=[f"l{i}" for i in range(29)]),
                "j": Vec(rng.integers(0, 7, n).astype(np.int32), "enum",
                         domain=[f"l{i}" for i in range(7)])})
    di = DataInfo(fr, ["a", "b", "k", "j"], standardize=True)
    di.device_design(fr, fit=True)
    tracemalloc.start()
    try:
        di.device_design(fr, fit=True)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    # what goes up: two float32 columns, their packs (int16, float32) and the
    # stacked codes (two int32): 4+4 + 2+4 + 8 = 22 bytes a row; the plain
    # statistics alone held 40 (five float64 temporaries of one column)
    assert peak < 30 * n, peak
