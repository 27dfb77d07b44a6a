"""Round-3 Rapids final-tail parity (`water/rapids/ast/prims/**`):
digamma/trigamma, moment/asDate/timezones, string distance/title/
substring-count, rank_within_groupby, relevel.by.freq, distance, isax,
setproperty/setLevel/append."""

import datetime

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


def test_digamma_trigamma(cloud1):
    fr = _fr(a=[1.0, 0.5, 10.5])
    got = _col(h2o.rapids(f"(digamma {fr.key})"))
    np.testing.assert_allclose(
        got, [-0.5772156649, -1.9635100260, 2.3030010343], atol=1e-9)
    got = _col(h2o.rapids(f"(trigamma {fr.key})"))
    np.testing.assert_allclose(
        got, [np.pi ** 2 / 6, np.pi ** 2 / 2, 0.0999169561], atol=1e-8)


def test_moment_and_asdate(cloud1):
    out = h2o.rapids("(moment 2020 2 29 12 30 15 250)")
    want = datetime.datetime(2020, 2, 29, 12, 30, 15, 250000,
                             tzinfo=datetime.timezone.utc).timestamp() * 1000
    assert _col(out)[0] == want
    # column-valued year
    fr = _fr(y=[2019.0, 2021.0])
    out = _col(h2o.rapids(f"(moment {fr.key} 1 1 0 0 0 0)"))
    for i, yr in enumerate((2019, 2021)):
        want = datetime.datetime(yr, 1, 1,
                                 tzinfo=datetime.timezone.utc
                                 ).timestamp() * 1000
        assert out[i] == want
    # invalid date -> NA
    assert np.isnan(_col(h2o.rapids("(moment 2021 2 30 0 0 0 0)"))[0])

    sf = _fr(d=np.asarray(["2021-03-05", "1999-12-31"], dtype=object))
    got = _col(h2o.rapids(f'(asDate {sf.key} "yyyy-MM-dd")'))
    want0 = datetime.datetime(2021, 3, 5,
                              tzinfo=datetime.timezone.utc).timestamp() * 1000
    assert got[0] == want0


def test_timezones(cloud1):
    tz = h2o.rapids("(listTimeZones)")
    assert tz.nrow > 100
    h2o.rapids('(setTimeZone "America/New_York")')
    got = h2o.rapids("(getTimeZone)")
    assert got.vec(got.names[0]).to_numpy()[0] == "America/New_York"
    with pytest.raises(Exception):
        h2o.rapids('(setTimeZone "Not/AZone")')
    # moment honors the session zone: midnight in New York is 5h later
    # than midnight UTC (Jan = EST)
    ny = _col(h2o.rapids("(moment 2021 1 1 0 0 0 0)"))[0]
    h2o.rapids('(setTimeZone "UTC")')
    utc = _col(h2o.rapids("(moment 2021 1 1 0 0 0 0)"))[0]
    assert ny - utc == 5 * 3600 * 1000


def test_str_distance_and_title(cloud1):
    a = _fr(s=np.asarray(["kitten", "abc"], dtype=object))
    b = _fr(s=np.asarray(["sitting", "abc"], dtype=object))
    got = _col(h2o.rapids(f'(strDistance {a.key} {b.key} "lv" TRUE)'))
    np.testing.assert_array_equal(got, [3.0, 0.0])
    got = _col(h2o.rapids(f'(strDistance {a.key} {b.key} "jw" TRUE)'))
    assert got[1] == 1.0 and 0 < got[0] < 1
    t = h2o.rapids(f"(toTitle {a.key})")
    assert t.vec(t.names[0]).domain[0] in ("Kitten", "Abc") or \
        list(t.vec(t.names[0]).to_numpy())[0] == "Kitten"


def test_num_valid_substrings(cloud1, tmp_path):
    words = tmp_path / "words.txt"
    words.write_text("cat\nhat\nat\n")
    fr = _fr(s=np.asarray(["concatenate", "zzz"], dtype=object))
    got = _col(h2o.rapids(f'(num_valid_substrings {fr.key} "{words}")'))
    # substrings of "concatenate" include cat + at (hat absent)
    np.testing.assert_array_equal(got, [2.0, 0.0])


def test_rank_within_groupby(cloud1):
    fr = _fr(g=[1.0, 1.0, 1.0, 2.0, 2.0], v=[3.0, 1.0, 2.0, 5.0, 4.0])
    out = h2o.rapids(
        f'(rank_within_groupby {fr.key} [0] [1] [1] "rk" 0)')
    rk = np.asarray(out.vec("rk").numeric_np())
    # original row order preserved; rank follows ascending v within g
    np.testing.assert_array_equal(rk, [3.0, 1.0, 2.0, 2.0, 1.0])
    out2 = h2o.rapids(
        f'(rank_within_groupby {fr.key} [0] [1] [0] "rk" 0)')
    rk2 = np.asarray(out2.vec("rk").numeric_np())
    np.testing.assert_array_equal(rk2, [1.0, 3.0, 2.0, 1.0, 2.0])
    # NA group values form ONE group (NaN != NaN must not split them)
    fr2 = _fr(g=[1.0, np.nan, np.nan, np.nan], v=[1.0, 3.0, 1.0, 2.0])
    out3 = h2o.rapids(
        f'(rank_within_groupby {fr2.key} [0] [1] [1] "rk" 0)')
    rk3 = np.asarray(out3.vec("rk").numeric_np())
    np.testing.assert_array_equal(rk3, [1.0, 3.0, 1.0, 2.0])


def test_relevel_by_freq(cloud1):
    fr = _fr(c=np.asarray(["a", "b", "b", "c", "b", "c"], dtype=object))
    out = h2o.rapids(f"(relevel.by.freq {fr.key} -1)")
    v = out.vec(out.names[0])
    assert v.domain == ["b", "c", "a"]
    # values unchanged under the remap
    got = [v.domain[c] for c in np.asarray(v.data)]
    assert got == ["a", "b", "b", "c", "b", "c"]


def test_distance(cloud1):
    x = _fr(a=[0.0, 3.0], b=[0.0, 4.0])
    y = _fr(a=[0.0], b=[0.0])
    out = h2o.rapids(f'(distance {x.key} {y.key} "l2")')
    np.testing.assert_allclose(_col(out), [0.0, 5.0])
    out = h2o.rapids(f'(distance {x.key} {x.key} "l1")')
    assert _col(out, 0)[0] == 0.0 and _col(out, 1)[0] == 7.0
    out = h2o.rapids(f'(distance {x.key} {x.key} "cosine")')
    np.testing.assert_allclose(np.asarray(_col(out, 1)[1]), 1.0, atol=1e-12)


def test_isax(cloud1):
    rng = np.random.default_rng(0)
    data = {f"t{i}": rng.normal(size=4) for i in range(16)}
    fr = h2o.H2OFrame(data)
    out = h2o.rapids(f"(isax {fr.key} 4 8 0)")
    assert out.nrow == 4
    words = list(out.vec("iSax_index").to_numpy())
    assert all(len(w.split("^")) == 4 for w in words)
    syms = np.asarray(out.vec("iSax_word_0").numeric_np())
    assert ((syms >= 0) & (syms <= 7)).all()


def test_setproperty_setlevel_append(cloud1):
    h2o.rapids('(setproperty "h2o3.test.flag" "42")')
    from h2o3_tpu.frame.rapids_expr import _SYS_PROPS

    assert _SYS_PROPS["h2o3.test.flag"] == "42"

    fr = _fr(c=np.asarray(["x", "y", "x"], dtype=object))
    out = h2o.rapids(f'(setLevel {fr.key} "y")')
    v = out.vec(out.names[0])
    assert [v.domain[c] for c in np.asarray(v.data)] == ["y", "y", "y"]
    with pytest.raises(Exception):
        h2o.rapids(f'(setLevel {fr.key} "nope")')

    fr2 = _fr(a=[1.0, 2.0])
    out = h2o.rapids(f'(append {fr2.key} 7 "seven")')
    assert out.names == ["a", "seven"]
    np.testing.assert_array_equal(
        np.asarray(out.vec("seven").numeric_np()), [7.0, 7.0])


def test_str_distance_all_six_measures(cloud1):
    """strDistance 6/6 (AstStrDistance over the Apache measures) —
    round-4 completion of the r03 inventory gap."""
    a = _fr(s=np.asarray(["kitten", "robert", "night"], dtype=object))
    b = _fr(s=np.asarray(["sitting", "rupert", "nacht"], dtype=object))
    # lcs: |a|+|b| - 2*LCS ; LCS(kitten, sitting) = "ittn" (4)
    got = _col(h2o.rapids(f'(strDistance {a.key} {b.key} "lcs" TRUE)'))
    assert got[0] == 6.0 + 7.0 - 2 * 4.0
    # qgram: bigram profile L1 distance
    got = _col(h2o.rapids(f'(strDistance {a.key} {b.key} "qgram" TRUE)'))
    assert got[0] > 0 and np.isfinite(got).all()
    ident = _fr(s=np.asarray(["abc"], dtype=object))
    same = _col(h2o.rapids(
        f'(strDistance {ident.key} {ident.key} "qgram" TRUE)'))
    assert same[0] == 0.0
    # jaccard: 1 - |chars∩|/|chars∪|
    got = _col(h2o.rapids(f'(strDistance {a.key} {b.key} "jaccard" TRUE)'))
    assert 0.0 < got[0] < 1.0
    # soundex: robert/rupert encode identically (R163) -> 4 agreeing chars
    got = _col(h2o.rapids(f'(strDistance {a.key} {b.key} "soundex" TRUE)'))
    assert got[1] == 4.0
    with pytest.raises(Exception):
        h2o.rapids(f'(strDistance {a.key} {b.key} "bogus" TRUE)')
