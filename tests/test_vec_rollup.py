"""A Vec remembers its rollups (ISSUE 29): min, max and NA count are scanned
once a Vec, in the column's own dtype, and `train()`'s constant-column screen
reads them. The bodies this replaced are kept HERE as the plain reference."""

import pickle
import warnings

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.vec import Rollup, Vec
from h2o3_tpu.models.model_base import _is_const
from h2o3_tpu.runtime import metrics_registry, tracing

NAN = float("nan")


# -- the plain reference: the bodies of `_is_const` and `Vec.min/max/nacnt/
# mean/sd` as they stood before the rollup ---------------------------------

def _ref_is_const(v):
    if v.type == "string":
        return False
    a = v.numeric_np()
    fin = a[~np.isnan(a)]
    return fin.size > 0 and float(fin.min()) == float(fin.max())


def _ref_stats(v):
    """(min, max, nacnt, mean, sd) by the old formulas. The old `min`/`max`
    raised numpy's empty-reduction ValueError on a Vec of length 0; the
    rollup answers NaN there, as for an all-NA column."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        a = v.numeric_np()
        lo, hi = ((NAN, NAN) if a.size == 0
                  else (float(np.nanmin(a)), float(np.nanmax(a))))
        return (lo, hi, int(v.isna_np().sum()),
                float(np.nanmean(a)), float(np.nanstd(a, ddof=1)))


def _strings(vals):
    return Vec(None, "string", strings=np.asarray(vals, dtype=object))


CASES = {
    "const_real": lambda: Vec(np.full(50, 2.5, np.float32)),
    "const_with_nan": lambda: Vec(np.asarray([NAN, 7.0, 7.0, NAN, 7.0],
                                             np.float32)),
    "varying_with_nan": lambda: Vec(np.asarray([3.0, NAN, -1.5, 8.0],
                                               np.float32)),
    "all_nan": lambda: Vec(np.full(9, NAN, np.float32)),
    "single_finite": lambda: Vec(np.asarray([4.0], np.float32)),
    "single_finite_among_nan": lambda: Vec(np.asarray([NAN, NAN, 4.0, NAN])),
    "all_pos_inf": lambda: Vec(np.full(4, np.inf, np.float32)),
    "inf_and_finite": lambda: Vec(np.asarray([np.inf, 1.0, -np.inf])),
    "neg_inf_with_nan": lambda: Vec(np.asarray([-np.inf, NAN, -np.inf])),
    "zero_and_neg_zero": lambda: Vec(np.asarray([0.0, -0.0, 0.0],
                                                np.float32)),
    "time_f64": lambda: Vec(np.asarray([1.7e12, 1.7e12 + 1, NAN]), "time"),
    "time_f64_const": lambda: Vec(np.full(6, 1.7e12), "time"),
    "int": lambda: Vec(np.arange(-3, 40), "int"),
    "int_const": lambda: Vec(np.full(12, 5), "int"),
    "enum": lambda: Vec([0, 2, 1, 2, 0], "enum", domain=["a", "b", "c"]),
    "enum_with_na": lambda: Vec([2, -1, 1, -1, 1], "enum",
                                domain=["a", "b", "c"]),
    "enum_const_with_na": lambda: Vec([1, -1, 1, 1], "enum",
                                      domain=["a", "b"]),
    "enum_all_na": lambda: Vec([-1, -1, -1], "enum", domain=["a"]),
    "enum_one_level": lambda: Vec([0, 0, 0, 0], "enum", domain=["only"]),
    "string": lambda: _strings(["x", None, "y", None, None]),
    "string_const": lambda: _strings(["x", "x"]),
    "empty_real": lambda: Vec(np.empty(0, np.float32)),
    "empty_enum": lambda: Vec(np.empty(0, np.int32), "enum", domain=["a"]),
    "empty_string": lambda: _strings([]),
    "two_blocks": lambda: Vec(np.where(np.arange(2_500_000) % 700_001 == 0,
                                       NAN, 1.0).astype(np.float32)),
}


def _same(a, b):
    return (a != a and b != b) or a == b


@pytest.mark.parametrize("case", sorted(CASES))
def test_is_const_answers_as_the_old_body(case):
    v = CASES[case]()
    assert _is_const(v) == _ref_is_const(v)
    assert _is_const(v) == _ref_is_const(v)      # and again from the memo


@pytest.mark.parametrize("case", sorted(CASES))
def test_stats_equal_the_old_formulas(case):
    v = CASES[case]()
    lo, hi, nacnt, mean, sd = _ref_stats(v)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for _ in range(2):                       # scanned, then remembered
            assert _same(v.min(), lo) and _same(v.max(), hi)
            assert v.nacnt() == nacnt and isinstance(v.nacnt(), int)
            assert _same(v.mean(), mean) and _same(v.sd(), sd)


class _Counting(np.ndarray):
    """An array that counts the reductions run over it."""

    reductions = 0

    def __array_ufunc__(self, ufunc, method, *inputs, **kw):
        if method == "reduce":
            _Counting.reductions += 1
        inputs = tuple(np.asarray(i) if isinstance(i, _Counting) else i
                       for i in inputs)
        return getattr(ufunc, method)(*inputs, **kw)


def _rollup_counts():
    c = metrics_registry.counter("h2o3_vec_rollup", labelnames=("result",))
    return c.value("computed"), c.value("reused")


def test_second_request_scans_nothing():
    v = Vec(np.asarray([1.0, NAN, 3.0], np.float32))
    v.data = v.data.view(_Counting)    # the test's own rebinding, before any look
    _Counting.reductions = 0
    c0, r0 = _rollup_counts()
    first = v.rollup()
    scanned = _Counting.reductions
    assert scanned > 0 and _rollup_counts() == (c0 + 1, r0)
    assert (v.min(), v.max(), v.nacnt()) == (1.0, 3.0, 1)
    assert v.rollup() is first and _is_const(v) is False
    assert _Counting.reductions == scanned
    assert _rollup_counts() == (c0 + 1, r0 + 5)
    assert "h2o3_vec_rollup_total" in metrics_registry.prometheus_text()
    # mean and sd join the memo on their own first request
    m, s = v.mean(), v.sd()
    again = _Counting.reductions
    assert (v.mean(), v.sd()) == (m, s) and _Counting.reductions == again


def test_clean_column_takes_two_passes_and_no_float64_copy():
    v = Vec(np.arange(1000, dtype=np.float32))
    v.data = v.data.view(_Counting)
    _Counting.reductions = 0
    assert v.rollup() == Rollup(0.0, 999.0, 0)
    assert _Counting.reductions == 2             # min, max; no NaN, no count


def test_rollup_is_dropped_when_data_is_another_object():
    v = Vec(np.asarray([1.0, 2.0], np.float32))
    assert v.max() == 2.0 and v.mean() == 1.5
    v.data = np.asarray([5.0, 9.0], np.float32)
    assert (v.min(), v.max(), v.mean()) == (5.0, 9.0, 7.0)


def _filled(v):
    m = getattr(v, "_rollup", None)
    return bool(m and m[1])


def test_new_vecs_start_empty_and_shared_vecs_share():
    fr = Frame.from_dict({"a": np.arange(10.0), "b": np.ones(10),
                          "k": np.asarray(list("xyxyxyxyxy"), dtype=object)})
    for v in fr.vecs():
        assert not _filled(v)
        v.rollup()
        assert _filled(v)
    assert not any(_filled(v) for v in fr.take(np.arange(5)).vecs())
    assert not _filled(fr.vec("a").take(np.asarray([1, 2])))
    assert not _filled(Vec.from_numpy(np.arange(4.0)))
    fr["c"] = np.arange(10.0)
    assert not _filled(fr.vec("c")) and _filled(fr.vec("a"))
    # drop / cbind / a Frame over the same Vecs share the Vec: same bytes
    dropped = fr.drop("b")
    assert dropped.vec("a") is fr.vec("a") and _filled(dropped.vec("a"))
    bound = fr.drop("c").cbind(Frame({"z": fr.vec("c")}))
    assert bound.vec("k") is fr.vec("k") and _filled(bound.vec("k"))
    again = Frame(dict(zip(fr.names, fr.vecs())))
    c0, r0 = _rollup_counts()
    assert [_is_const(v) for v in again.vecs()] == [False, True, False, False]
    assert _rollup_counts() == (c0 + 1, r0 + 3)  # only "c" was a first look


@pytest.mark.parametrize("case", ["varying_with_nan", "enum_with_na",
                                  "string", "time_f64"])
def test_pickle_round_trip(case):
    v = CASES[case]()
    empty = pickle.loads(pickle.dumps(v))
    assert not _filled(empty)
    want = v.rollup()
    if v.type != "string":
        v.mean()
    full = pickle.loads(pickle.dumps(v))
    assert _filled(full)
    c0, r0 = _rollup_counts()
    for w in (empty, full):
        got = w.rollup()
        assert _same(got.min, want.min) and _same(got.max, want.max)
        assert got.nacnt == want.nacnt
    assert _rollup_counts() == (c0 + 1, r0 + 1)  # the filled one came filled
    # a Vec pickled before the slot existed: the slot is simply not there
    old = Vec.__new__(Vec)
    for slot in ("data", "type", "domain", "_strings"):
        setattr(old, slot, getattr(v, slot))
    assert not hasattr(old, "_rollup")
    assert old.nacnt() == want.nacnt and _is_const(old) == _ref_is_const(v)
    assert hasattr(old, "_rollup")


def _train_frame(n=4000, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 3)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] + 0.5 * rng.normal(size=n) > 0).astype(int)
    return Frame.from_dict(
        {"a": X[:, 0], "b": X[:, 1], "c": X[:, 2], "flat": np.full(n, 3.0),
         "y": np.asarray(["n", "p"], dtype=object)[y]},
        column_types={"y": "enum"})


def _estimator(algo):
    if algo == "glm":
        from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator

        return H2OGeneralizedLinearEstimator(family="binomial", lambda_=0)
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    return H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=1)


@pytest.mark.parametrize("algo", ["gbm", "glm"])
def test_second_train_on_one_frame_computes_no_rollup(cloud1, algo):
    fr = _train_frame()
    models, resolves = [], []
    for _ in range(2):
        tracing.clear()
        est = _estimator(algo)
        est.train(y="y", training_frame=fr)
        models.append(est._model)
        (r,) = [s for s in tracing.spans() if s["name"] == "train.resolve"]
        resolves.append(r["attrs"])
    # four predictors screened and the response's NA count: five first looks
    assert resolves[0] == dict(rollups_computed=5, rollups_reused=0)
    assert resolves[1] == dict(rollups_computed=0, rollups_reused=5)
    first, second = models
    assert first.x == second.x == ["a", "b", "c"]
    np.testing.assert_array_equal(first.predict(fr).vec("p").to_numpy(),
                                  second.predict(fr).vec("p").to_numpy())
    if algo == "glm":
        assert first.coef() == second.coef()


def test_response_nas_are_dropped_only_where_there_are_some(cloud1):
    fr = _train_frame(n=600)
    y = fr.vec("y").to_numpy().copy()
    y[::7] = -1
    holes = Frame({**{n: fr.vec(n) for n in "abc"},
                   "y": Vec(y, "enum", domain=fr.vec("y").domain)})
    seen = {}
    for name, frame in (("whole", fr), ("holes", holes)):
        est = _estimator("gbm")
        x, tr, va, _ = est._resolve(None, "y", frame, frame)
        seen[name] = (tr, va)
        assert x == ["a", "b", "c"]
    assert seen["whole"] == (fr, fr)             # the very frames, untaken
    kept = int((y >= 0).sum())
    assert [f.nrow for f in seen["holes"]] == [kept, kept]
    np.testing.assert_array_equal(seen["holes"][0].vec("a").to_numpy(),
                                  fr.vec("a").to_numpy()[y >= 0])
