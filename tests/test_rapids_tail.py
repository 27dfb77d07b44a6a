"""Round-2 Rapids prim-tail parity (`water/rapids/ast/prims/**` long tail):
NA-propagating reducers, time construction, string metrics, reshapers, fold
columns, sequences, 2-column table."""

import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.frame.frame import Frame


def _fr(**cols):
    types = {k: "enum" for k, v in cols.items()
             if np.asarray(v).dtype.kind in "OUS"}
    return h2o.H2OFrame(dict(cols), column_types=types or None)


def _col(fr, i=0):
    return np.asarray(fr.vec(fr.names[i]).numeric_np())


def test_na_reducers(cloud1):
    fr = _fr(a=[1.0, np.nan, 3.0])
    assert np.isnan(h2o.rapids(f"(sumNA {fr.key})"))
    assert np.isnan(h2o.rapids(f"(maxNA {fr.key})"))
    assert h2o.rapids(f"(nacnt {fr.key})") == [1.0]
    m = _fr(a=[1.0, 2.0, 2.0, 3.0])
    assert h2o.rapids(f"(mode {m.key})") == 2.0


def test_math_tail(cloud1):
    fr = _fr(a=[0.5])
    np.testing.assert_allclose(
        _col(h2o.rapids(f"(asinh {fr.key})"))[0], np.arcsinh(0.5))
    np.testing.assert_allclose(
        _col(h2o.rapids(f"(cospi {fr.key})"))[0], np.cos(np.pi * 0.5),
        atol=1e-12)


def test_time_tail(cloud1):
    # 2020-03-04 05:06:07.250 UTC
    ts = 1583298367250.0
    fr = _fr(t=[ts])
    assert _col(h2o.rapids(f"(millis {fr.key})"))[0] == 250.0
    assert _col(h2o.rapids(f"(week {fr.key})"))[0] == 10.0  # ISO week 10
    mk = h2o.rapids("(mktime 2020 2 3 5 6 7 250)")  # 0-based month/day
    assert _col(mk)[0] == ts


def test_string_tail(cloud1):
    fr = _fr(s=["  ab", "cd  ", "aabb"])
    out = h2o.rapids(f"(lstrip {fr.key})")
    assert out.vec(out.names[0]).domain[0] == "ab"
    ent = _col(h2o.rapids(f'(entropy {fr.key})'))
    # row 2 is "aabb": two symbols equally likely -> 1 bit
    assert ent[2] == 1.0
    g = h2o.rapids(f'(grep {fr.key} "ab")')
    assert len(_col(g)) == 2  # "  ab" and "aabb" match


def test_frame_tail(cloud1):
    fr = _fr(a=[1.0, 2.0], b=[np.nan, np.nan], s=["x", "y"])
    names = h2o.rapids(f"(colnames {fr.key})")
    assert list(names.vec("names").domain) == ["a", "b", "s"]
    num = h2o.rapids(f'(columnsByType {fr.key} "numeric")')
    assert list(_col(num)) == [0.0, 1.0]
    keep = h2o.rapids(f"(filterNACols {fr.key} 0.5)")
    assert list(_col(keep)) == [0.0, 2.0]
    one = _fr(z=[7.0])
    assert h2o.rapids(f"(flatten {one.key})") == 7.0
    row = h2o.rapids(f"(getrow {one.key})")
    assert list(_col(row)) == [7.0]
    d = _fr(a=[1.0, 2.0, np.nan, np.nan, 5.0])
    filled = h2o.rapids(f'(h2o.fillna {d.key} "forward" 0 1)')
    np.testing.assert_array_equal(
        _col(filled), [1.0, 2.0, 2.0, np.nan, 5.0])
    df = h2o.rapids(f"(difflag1 {d.key})")
    assert _col(df)[1] == 1.0 and np.isnan(_col(df)[0])


def test_melt_pivot_roundtrip(cloud1):
    fr = _fr(id=["r1", "r2"], x=[1.0, 2.0], y=[3.0, 4.0])
    long = h2o.rapids(f'(melt {fr.key} [0] [1 2] "var" "val" FALSE)')
    assert long.shape == (4, 3)
    wide = h2o.rapids(
        f'(pivot (melt {fr.key} [0] [1 2] "var" "val" FALSE) "id" "var" "val")')
    assert wide.shape == (2, 3)
    assert list(np.asarray(wide.vec("x").numeric_np())) == [1.0, 2.0]
    assert list(np.asarray(wide.vec("y").numeric_np())) == [3.0, 4.0]


def test_levels_tail(cloud1):
    fr = _fr(c=["lo", "hi", "lo", "mid"])
    rel = h2o.rapids(f'(relevel {fr.key} "mid")')
    v = rel.vec(rel.names[0])
    assert v.domain[0] == "mid"
    # values preserved under the domain permutation
    labels = [v.domain[c] for c in np.asarray(v.data)]
    assert labels == ["lo", "hi", "lo", "mid"]
    dom = h2o.rapids(f'(setDomain {fr.key} ["H" "L" "M"])')
    v2 = dom.vec(dom.names[0])
    assert v2.domain == ["H", "L", "M"]  # hi,lo,mid sorted -> renamed


def test_fold_and_seq(cloud1):
    fr = _fr(y=["a", "b", "a", "b", "a", "b", "a", "b"])
    f1 = _col(h2o.rapids(f"(kfold_column {fr.key} 4 42)"))
    assert set(f1) <= {0.0, 1.0, 2.0, 3.0}
    f2 = _col(h2o.rapids(f"(modulo_kfold_column {fr.key} 4)"))
    assert list(f2[:4]) == [0.0, 1.0, 2.0, 3.0]
    f3 = _col(h2o.rapids(f"(stratified_kfold_column {fr.key} 2 7)"))
    y = np.asarray(fr.vec("y").data)
    for cls in (0, 1):  # each class split evenly across folds
        vals, cnt = np.unique(f3[y == cls], return_counts=True)
        assert list(cnt) == [2, 2]
    assert list(_col(h2o.rapids("(seq 2 6 2)"))) == [2.0, 4.0, 6.0]
    assert list(_col(h2o.rapids("(seq_len 3)"))) == [1.0, 2.0, 3.0]
    rl = _fr(a=[1.0, 2.0])
    assert list(_col(h2o.rapids(f"(rep_len {rl.key} 5)"))) == [
        1.0, 2.0, 1.0, 2.0, 1.0]


def test_topn_and_table2(cloud1):
    fr = _fr(v=[5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0, 4.0, 6.0, 0.0])
    top = h2o.rapids(f"(topn {fr.key} 0 20 TRUE)")
    assert list(_col(top, 1)) == [9.0, 8.0]
    bot = h2o.rapids(f"(topn {fr.key} 0 20 FALSE)")
    assert list(_col(bot, 1)) == [0.0, 1.0]
    t2 = _fr(a=["x", "x", "y"], b=["p", "p", "q"])
    tab = h2o.rapids(f"(table (cols {t2.key} [0 1]) )")
    assert tab.shape == (2, 3)
    counts = {(r0, r1): c for r0, r1, c in zip(
        [tab.vec("a").domain[i] for i in np.asarray(tab.vec("a").data)],
        [tab.vec("b").domain[i] for i in np.asarray(tab.vec("b").data)],
        np.asarray(tab.vec("Counts").numeric_np()))}
    assert counts == {("x", "p"): 2.0, ("y", "q"): 1.0}


def test_operator_tail(cloud1):
    fr = _fr(a=[5.0, 7.0])
    assert list(_col(h2o.rapids(f"(%% {fr.key} 3)"))) == [2.0, 1.0]
    assert list(_col(h2o.rapids(f"(%/% {fr.key} 3)"))) == [1.0, 2.0]
    assert list(_col(h2o.rapids(f"(^ {fr.key} 2)"))) == [25.0, 49.0]
    x = _fr(a=[1.0, 0.0, np.nan])
    y = _fr(b=[1.0, 1.0, 0.0])
    band = _col(h2o.rapids(f"(& {x.key} {y.key})"))
    np.testing.assert_array_equal(band, [1.0, 0.0, 0.0])  # NA & FALSE = FALSE
    bor = _col(h2o.rapids(f"(| {x.key} {y.key})"))
    np.testing.assert_array_equal(bor, [1.0, 1.0, np.nan])  # NA | FALSE = NA


def test_review_fixes_r02(cloud1):
    # scalar-first non-commutative binops must not swap operands
    fr = _fr(a=[1.0, 2.0])
    assert list(_col(h2o.rapids(f"(- 5 {fr.key})"))) == [4.0, 3.0]
    assert list(_col(h2o.rapids(f"(/ 6 {fr.key})"))) == [6.0, 3.0]
    # topn skips NAs
    nafr = _fr(v=[5.0, np.nan, 3.0, 9.0, np.nan, 1.0])
    top = h2o.rapids(f"(topn {nafr.key} 0 35 TRUE)")
    assert list(_col(top, 1)) == [9.0, 5.0]
    # pivot orders numeric keys numerically
    lng = _fr(idx=[1.0, 10.0, 2.0], c=["k", "k", "k"], v=[1.0, 2.0, 3.0])
    wide = h2o.rapids(f'(pivot {lng.key} "idx" "c" "v")')
    assert list(_col(wide, 0)) == [1.0, 2.0, 10.0]
    # fillna axis=1 fills across columns
    rowfr = _fr(a=[1.0, np.nan], b=[np.nan, np.nan], c=[7.0, 8.0])
    f = h2o.rapids(f'(h2o.fillna {rowfr.key} "forward" 1 1)')
    assert _col(f, 1)[0] == 1.0 and np.isnan(_col(f, 0)[1])
    # mktime with NA component yields NA, not a crash
    nfr = _fr(y=[2020.0, np.nan])
    mk = _col(h2o.rapids(f"(mktime {nfr.key} 0 0 0 0 0 0)"))
    assert not np.isnan(mk[0]) and np.isnan(mk[1])
    # vectorized week still correct across a year boundary (2021-01-01 -> 53)
    wfr = _fr(t=[1609459200000.0])
    assert _col(h2o.rapids(f"(week {wfr.key})"))[0] == 53.0


def test_scalar_first_multicolumn(cloud1):
    fr = _fr(a=[1.0, 2.0], b=[10.0, 20.0])
    out = h2o.rapids(f"(- 100 {fr.key})")
    assert out.ncol == 2
    assert list(_col(out, 0)) == [99.0, 98.0]
    assert list(_col(out, 1)) == [90.0, 80.0]


@pytest.mark.parametrize("expr", [
    "(append)", "(cut)", "(mean)", "(unique)", "(strDistance)",
    '(unique "x" "y" TRUE)', '(trim "x" TRUE [])',
    '(+ (hist 1 "x") (is.na -3.5 1 "x"))',
])
def test_malformed_rapids_raise_value_error(cloud1, expr):
    """Wrong arity / argument kinds are USER errors (ValueError → 400),
    never interpreter-internal 500s — found by fuzzing /99/Rapids."""
    import h2o3_tpu as h2o

    with pytest.raises((ValueError, TypeError, KeyError)):
        h2o.rapids(expr)
