"""Fused GBM hot path (ISSUE 7) — packed-code histograms, single-pass
split search, overlapped chunk scoring.

Pins: (1) the packed-resident build is BIT-EXACT against the full-width
one across the parity matrix — `build_tree` on packed · dense codes, whole
fits (GBM/DRF, mtries, CV fold reuse) packed · the full-width resident path
that DART, `checkpoint=`, lossguide and nbins > 256 fits run, overlap on ·
off; the split search itself is held to its plain reference in
tests/test_tree_split_sums.py; (2) a warm higgs-shaped fit re-traces ZERO
programs (the ROADMAP item 2 pin, via the PR 6 XLA tracker); (3) the
histogram kernel auto-dispatch is observable — per-fit plans, dispatch
counters, and the previously-silent VMEM-pressure fallback."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.models import tree as treelib
from h2o3_tpu.ops import histogram, packing

from conftest import make_classification


@pytest.fixture()
def _tree_env():
    """Isolate the overlap/kernel env knobs per test."""
    keys = ("H2O3_TREE_OVERLAP", "H2O3_HIST_METHOD")
    prior = {k: os.environ.pop(k, None) for k in keys}
    yield
    for k, v in prior.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _tree_data(seed=1, N=2048, F=9, B=21):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, B, (N, F)).astype(np.uint8)
    g = rng.normal(size=N).astype(np.float32)
    h = rng.random(N).astype(np.float32) + 0.1
    w = np.where(rng.random(N) > 0.05, 1.0, 0.0).astype(np.float32)
    fm = np.ones(F, np.float32)
    edges = np.sort(rng.normal(size=(F, B - 2)), axis=1).astype(np.float32)
    return codes, g, h, w, fm, edges, B


def _leaves_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# -- ops: packed consumption ------------------------------------------------

@pytest.mark.parametrize("F", [7, 130])
@pytest.mark.parametrize("bits,B", [(0, 21), (4, 16), (5, 21), (6, 33)])
def test_row_codes_exact(bits, B, F):
    """`_row_codes` — the one reader of a row's split-feature code — is
    exact on full-width and on every packed width, through the dense
    select (F = 7) and through the wide-frame gather (F = 130)."""
    rng = np.random.default_rng(bits * 1000 + F)
    N = 4096
    codes = rng.integers(0, B, (N, F)).astype(np.uint8)
    resident = packing.pack_host(codes, bits) if bits else codes
    rf = rng.integers(0, F, N).astype(np.int32)
    got = treelib._row_codes(jnp.asarray(resident), jnp.asarray(rf), bits)
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), codes[np.arange(N), rf])


@pytest.mark.parametrize("N", [8192, 8200, 24])
@pytest.mark.parametrize("bits,B", [(4, 16), (5, 21), (6, 33)])
def test_unpack_device_equals_unpack_host(bits, B, N):
    """The device widen (row groups read through a reshape, no strided
    slice) against the host's, at row counts that are and are not multiples
    of the histogram kernel's row chunk; and the widen lowers to no gather."""
    rng = np.random.default_rng(bits * 100 + N)
    codes = rng.integers(0, B, (N, 5)).astype(np.uint8)
    pk = packing.pack_host(codes, bits)
    got = np.asarray(packing.unpack_device(jnp.asarray(pk), bits))
    assert got.dtype == np.uint8
    assert np.array_equal(got, packing.unpack_host(pk, bits))
    assert np.array_equal(got, codes)
    text = packing.unpack_device.lower(jnp.asarray(pk), bits=bits).as_text()
    assert "gather" not in text


@pytest.mark.parametrize("F,form,read", [
    (1, "program", "select"), (28, "program", "select"),
    (128, "program", "select"), (129, "program", "gather"),
    (28, "fit", "select"), (129, "fit", "select"), (136, "fit", "select"),
])
def test_partition_read_rule(F, form, read):
    """The rule `_row_codes` and the per-fit plan share: a fit handed the
    code operand (`form` "fit") selects over it at every width; without
    it, dense select up to `_ONEHOT_LOOKUP_MAX` features, gather beyond."""
    assert treelib._ONEHOT_LOOKUP_MAX == 128
    assert treelib.partition_read(F, form) == read
    if form == "program":
        assert treelib.partition_read(F) == read


# -- build_tree: the parity matrix ------------------------------------------

@pytest.mark.parametrize("variant", [
    "packed_seed2", "packed", "packed_fused", "mtries", "monotone",
    "alpha_lambda0", "bits4", "bits5", "bits6", "f7", "f130", "compact",
])
def test_build_tree_fused_packed_parity(variant):
    # packed · dense codes through the same builder. The variants hold
    # `_row_codes` inside the level loop: every pack width, a narrow frame
    # (the dense select), a frame over `_ONEHOT_LOOKUP_MAX` (the gather
    # branch) and the compact levels
    shape = {"bits4": dict(B=16), "bits5": dict(B=21), "bits6": dict(B=33),
             "f7": dict(F=7), "f130": dict(F=130, N=1024),
             "compact": dict(B=16), "packed_seed2": dict(seed=2),
             }.get(variant, {})
    codes, g, h, w, fm, edges, B = _tree_data(**shape)
    bits = packing.pack_bits_for(B, codes.shape[0])
    if variant.startswith("bits"):
        assert bits == int(variant[4:])
    pk = packing.pack_host(codes, bits)
    key = jax.random.PRNGKey(3)
    kw = dict(max_depth=4, nbins=B, min_rows=5.0, key=key)
    if variant == "mtries":
        kw["mtries_rate"] = jnp.float32(0.5)
    if variant == "monotone":
        mono = np.zeros(codes.shape[1], np.float32)
        mono[0], mono[3] = 1.0, -1.0
        kw["monotone"] = jnp.asarray(mono)
    if variant == "alpha_lambda0":
        kw.update(reg_lambda=0.0, reg_alpha=0.5)   # NaN-prone gains
    if variant == "compact":
        # levels 6 and 7 partition in compact slots (`bf[row_slot]` reads)
        kw.update(max_depth=8, min_rows=1.0, compact_cap=32)
    base = treelib.build_tree(jnp.asarray(codes), g, h, w, fm, edges, **kw)
    got = treelib.build_tree(jnp.asarray(pk), g, h, w, fm, edges,
                             pack_bits=bits, **kw)
    assert _leaves_equal(base, got)


@pytest.mark.parametrize("F,N", [(28, 1024), (136, 520)])
@pytest.mark.parametrize("bits,B", [(0, 21), (4, 16), (5, 21), (6, 33)])
def test_build_tree_code_operand_parity(bits, B, F, N):
    """`build_tree` handed the fit-lifetime operand against `build_tree`
    handed the resident codes alone, the Pallas kernel in interpret mode:
    the tree, the leaf indices, the gains and the covers are bit for bit
    the same — at F = 28 the select over the operand against the select
    over the codes widened in the program, at F = 136 the select over the
    operand against the gather read from the row-major codes; packed at
    every width and full width, at a row count the operand pads (520 is
    no multiple of 8 x 128)."""
    from jax.experimental.pallas import tpu as pltpu

    codes, g, h, w, fm, edges, _ = _tree_data(N=N, F=F, B=B)
    resident = jnp.asarray(packing.pack_host(codes, bits) if bits else codes)
    depth = 3
    form = histogram.code_operand_form(
        treelib.histogram_level_plan(depth), B, "pallas_factored")
    assert form["form"] == "fit" and form["row_chunk"] >= 512
    kw = dict(max_depth=depth, nbins=B, min_rows=5.0, pack_bits=bits,
              key=jax.random.PRNGKey(3), hist_method="pallas_factored")
    with pltpu.force_tpu_interpret_mode():
        operand = histogram.build_code_operand(resident, bits,
                                               form["row_chunk"])
        base = treelib.build_tree(resident, g, h, w, fm, edges, **kw)
        got = treelib.build_tree(resident, g, h, w, fm, edges,
                                 operand=operand, **kw)
    assert operand.dtype == jnp.float32
    assert operand.shape == (-(-F // 8) * 8,
                             -(-N // form["row_chunk"]) * form["row_chunk"])
    assert np.array_equal(np.asarray(operand[:F, :N]), codes.T)
    assert np.all(np.asarray(operand[F:]) == -1)
    assert np.all(np.asarray(operand[:, N:]) == -1)
    assert treelib.partition_read(F) == ("gather" if F > 128 else "select")
    assert treelib.partition_read(F, form["form"]) == "select"
    assert _leaves_equal(base, got)


@pytest.mark.parametrize("bits,B", [(0, 21), (4, 16), (5, 21), (6, 33),
                                    (0, 300)])
def test_code_operand_is_built_block_by_block(bits, B, monkeypatch):
    """`build_code_operand` widens `_OPERAND_BLOCK_ROWS` rows a step and
    a shorter last block: whole blocks, a tail and a matrix under one
    block all give the widened codes feature-major, -1 beyond them."""
    monkeypatch.setattr(histogram, "_OPERAND_BLOCK_ROWS", 64)
    rng = np.random.default_rng(bits + B)
    for N in (40, 192, 1000):      # shapes no other test traces
        codes = rng.integers(0, B, (N, 7)).astype(
            np.uint16 if B > 256 else np.uint8)
        resident = jnp.asarray(packing.pack_host(codes, bits) if bits
                               else codes)
        got = np.asarray(histogram.build_code_operand(resident, bits, 128))
        assert got.shape == (8, -(-N // 128) * 128)
        assert np.array_equal(got[:7, :N], codes.T)
        assert np.all(got[7:] == -1) and np.all(got[:, N:] == -1)


def test_code_operand_rule():
    """`code_operand_form`, the one rule: a one-device fit any of whose
    levels the Pallas kernel runs gets the operand (at the largest of
    those levels' row chunks); a CPU fit, a blocked or mesh lane and a
    plan without levels widen inside the program."""
    levels = treelib.histogram_level_plan(6)
    fit = histogram.code_operand_form(levels, 21, "auto", platform="tpu")
    assert fit == {"form": "fit", "row_chunk": 8192}
    wide = histogram.code_operand_form(levels, 256, "auto", platform="tpu")
    assert wide == {"form": "fit", "row_chunk": 2048}
    program = {"form": "program", "row_chunk": 0}
    assert histogram.code_operand_form(levels, 21, "auto",
                                       platform="cpu") == program
    for lane in ("blocks", "mesh", "mesh_psum"):
        assert histogram.code_operand_form(
            levels, 21, "pallas_factored", shard_mode=lane) == program
    assert histogram.code_operand_form([], 21, "pallas_factored") == program
    # a deep level that falls back to `segment` does not take the operand
    # from the levels that run the kernel
    deep = [("d0", 1), ("d16", 1 << 16)]
    assert histogram.code_operand_form(
        deep, 64, "pallas_factored")["form"] == "fit"
    assert histogram.code_operand_form(
        deep[1:], 64, "pallas_factored") == program


def _row_gathers_over(text, numels):
    """The `stablehlo.gather`s of a lowered program that read ONE element
    per index from an operand of `numels` elements — a per-row read of the
    code matrix (packed words or widened codes). The packed widen's own
    strided row slices are gathers too, of whole F-wide rows: not these."""
    import re

    hits = []
    for line in text.splitlines():
        if "stablehlo.gather" not in line:
            continue
        sizes = re.search(r"slice_sizes = array<i64: ([0-9, ]+)>", line)
        operand = re.search(r": \(tensor<([0-9x]+)x[a-z]+[0-9]+>", line)
        numel = int(np.prod([int(d) for d in operand.group(1).split("x")]))
        per_index = int(np.prod([int(d) for d in sizes.group(1).split(",")]))
        if numel in numels and per_index == 1:
            hits.append(line.strip()[:160])
    return hits


@pytest.mark.parametrize("F,gathers", [(28, False), (130, True)])
def test_packed_partition_issues_no_gather_into_the_code_matrix(F, gathers):
    """The guard against the gather's quiet return: the tree program at
    the flagship's structure (5-bit packed codes, F = 28, depth 6) reads a
    row's code by the dense select — no gather whose operand is the code
    matrix, packed or widened — while a frame over `_ONEHOT_LOOKUP_MAX`
    still gathers."""
    N, B, depth = 4096, 21, 6
    codes, g, h, w, fm, edges, _ = _tree_data(N=N, F=F, B=B)
    pk = packing.pack_host(codes, 5)
    text = treelib.build_tree.lower(
        jnp.asarray(pk), g, h, w, fm, edges, max_depth=depth, nbins=B,
        pack_bits=5, hist_method="segment").as_text()
    hits = _row_gathers_over(text, {pk.size, N * F})
    assert bool(hits) == gathers, hits


def test_build_tree_compact_cap_parity_and_overflow_flag():
    """Compact-phase split search + partition on packed codes match the
    dense-code build, including the overflow flag the driver's
    dense-rebuild guard consumes."""
    codes, g, h, w, fm, edges, B = _tree_data(N=2048, F=9)
    bits = packing.pack_bits_for(B, codes.shape[0])
    pk = packing.pack_host(codes, bits)
    key = jax.random.PRNGKey(5)
    kw = dict(max_depth=8, nbins=B, min_rows=1.0, key=key)
    base = treelib.build_tree(jnp.asarray(codes), g, h, w, fm, edges,
                              compact_cap=64, **kw)
    got = treelib.build_tree(jnp.asarray(pk), g, h, w, fm, edges,
                             compact_cap=64, pack_bits=bits, **kw)
    assert _leaves_equal(base, got)
    assert int(np.asarray(base[-1])) == int(np.asarray(got[-1]))
    # a cap too small for the live frontier must raise the flag on BOTH
    # paths (the driver then rebuilds densely — exactness never traded)
    *_, ov_l = treelib.build_tree(jnp.asarray(codes), g, h, w, fm, edges,
                                  compact_cap=4, **kw)
    *_, ov_f = treelib.build_tree(jnp.asarray(pk), g, h, w, fm, edges,
                                  compact_cap=4, pack_bits=bits, **kw)
    assert int(np.asarray(ov_l)) > 0
    assert int(np.asarray(ov_l)) == int(np.asarray(ov_f))


# -- whole-fit parity: packed resident codes · full-width resident codes ----

# ONE shared whole-fit shape: every driver-level test below uses the same
# (row bucket, F, max_depth, nbins) so they all land on a single packed and
# a single full-width compiled tree program — the suite pays each trace
# once, not per test.
_FIT_N, _FIT_F, _FIT_DEPTH = 4096, 6, 4
_FIT_X, _FIT_Y = make_classification(n=_FIT_N, f=_FIT_F, seed=7)
_FIT_NAMES = [f"f{i}" for i in range(_FIT_F)] + ["label"]


def _frame(X, y, names):
    from h2o3_tpu.frame.frame import Frame

    return Frame.from_numpy(np.column_stack([X, y]),
                            names=names).asfactor("label")


def _train(est, X, y, names, overlap=None, full_width=False):
    """Train on a cold dataset cache. `full_width` sends the fit down the
    full-width resident path (no sub-byte packing of the resident codes),
    the one DART, `checkpoint=`, lossguide and nbins > 256 fits run."""
    from h2o3_tpu.models import dataset_cache, shared_tree

    dataset_cache.clear()
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("H2O3_TREE_OVERLAP", raising=False)
        if overlap is not None:
            mp.setenv("H2O3_TREE_OVERLAP", overlap)
        if full_width:
            mp.setattr(shared_tree, "_pack_bits_for", lambda nbins, n: 0)
        est.train(y="label", training_frame=_frame(X, y, names))
    return est


def _fit_gbm(X, y, names, overlap=None, full_width=False, **params):
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    return _train(H2OGradientBoostingEstimator(seed=42, **params), X, y,
                  names, overlap=overlap, full_width=full_width)


def _assert_models_bitexact(a, b):
    assert a.model.ntrees_built == b.model.ntrees_built
    for k in range(len(a.model.forest)):
        for f in treelib.Tree._fields:
            assert np.array_equal(
                np.asarray(getattr(a.model.forest[k], f)),
                np.asarray(getattr(b.model.forest[k], f))), (k, f)
    va = getattr(a.model, "varimp_table", None)
    vb = getattr(b.model, "varimp_table", None)
    if va is not None or vb is not None:
        assert [r[0] for r in va] == [r[0] for r in vb]
        np.testing.assert_array_equal([r[1] for r in va],
                                      [r[1] for r in vb])


def test_gbm_fit_parity_packed_vs_full_width(cloud1, _tree_env):
    """Whole-fit pin: packed resident codes × overlapped scoring with
    early stopping produce the bit-identical forest, gain-based varimp,
    scoring history, and predictions of the full-width resident path."""
    X, y, names = _FIT_X, _FIT_Y, _FIT_NAMES
    params = dict(ntrees=12, max_depth=_FIT_DEPTH, learn_rate=0.1,
                  score_tree_interval=3, stopping_rounds=2,
                  stopping_tolerance=1e-9)
    resident_bits = lambda: histogram.kernel_stats()["plans"][-1]["pack_bits"]
    new = _fit_gbm(X, y, names, **params)
    assert resident_bits() in (4, 5, 6)
    old = _fit_gbm(X, y, names, full_width=True, **params)
    assert resident_bits() == 0
    _assert_models_bitexact(new, old)
    h_new = [e.get("logloss") for e in new.model.scoring_history]
    h_old = [e.get("logloss") for e in old.model.scoring_history]
    assert h_new == h_old
    fr = _frame(X, y, names)
    pa = new.model.predict(fr)
    pb = old.model.predict(fr)
    np.testing.assert_array_equal(np.asarray(pa.vec("1").data),
                                  np.asarray(pb.vec("1").data))


def test_gbm_fit_parity_overlap_off(cloud1, _tree_env):
    """H2O3_TREE_OVERLAP=0 (no speculative chunk) is bit-identical to the
    overlapped default — overlap is a scheduling change, not a math one."""
    X, y, names = _FIT_X, _FIT_Y, _FIT_NAMES
    params = dict(ntrees=10, max_depth=_FIT_DEPTH, score_tree_interval=2,
                  stopping_rounds=1, stopping_tolerance=1e-9)
    a = _fit_gbm(X, y, names, overlap="1", **params)
    b = _fit_gbm(X, y, names, overlap="0", **params)
    _assert_models_bitexact(a, b)


def test_early_stop_discards_speculative_chunk(cloud1, _tree_env):
    """When the stopper FIRES with a speculative chunk in flight, the
    chunk is discarded and the pre-dispatch state restored: tree count,
    forest, and the training metrics computed from the restored margins
    all match the never-speculated path (H2O3_TREE_OVERLAP=0) bit for bit."""
    X, y, names = _FIT_X, _FIT_Y, _FIT_NAMES
    # tiny learn rate + huge tolerance → the stopper fires mid-run
    params = dict(ntrees=40, max_depth=_FIT_DEPTH, learn_rate=0.01,
                  score_tree_interval=2, stopping_rounds=1,
                  stopping_tolerance=0.5)
    new = _fit_gbm(X, y, names, **params)
    old = _fit_gbm(X, y, names, overlap="0", **params)
    assert new.model.ntrees_built < 40, "stopper must fire for this pin"
    _assert_models_bitexact(new, old)
    np.testing.assert_array_equal(new.model.training_metrics.logloss(),
                                  old.model.training_metrics.logloss())


def test_drf_fit_parity_packed_vs_full_width(cloud1, _tree_env):
    """DRF: per-node mtries column sampling + OOB scoring on packed
    resident codes match the full-width resident path bit for bit."""
    from h2o3_tpu.models.drf import H2ORandomForestEstimator

    def fit(full_width):
        drf = H2ORandomForestEstimator(ntrees=8, max_depth=_FIT_DEPTH,
                                       seed=42, score_tree_interval=4)
        return _train(drf, _FIT_X, _FIT_Y, _FIT_NAMES, full_width=full_width)

    _assert_models_bitexact(fit(False), fit(True))


def test_cv_fold_reuse_parity_packed_vs_full_width(cloud1, _tree_env):
    """CV fold reuse (PR 4) composes with resident packing: fold models
    slice the parent's PACKED artifact and the cross-validated parent is
    bit-identical to the run whose folds slice a full-width one."""
    X, y, names = _FIT_X, _FIT_Y, _FIT_NAMES
    # folds inherit the parent's padded row bucket (_npad_floor), so even
    # the fold fits reuse the shared compiled programs
    params = dict(ntrees=6, max_depth=_FIT_DEPTH, nfolds=2,
                  keep_cross_validation_predictions=True)
    new = _fit_gbm(X, y, names, **params)
    old = _fit_gbm(X, y, names, full_width=True, **params)
    _assert_models_bitexact(new, old)
    ma = new.model.cross_validation_metrics
    mb = old.model.cross_validation_metrics
    assert ma is not None and mb is not None
    np.testing.assert_array_equal(ma.logloss(), mb.logloss())
    np.testing.assert_array_equal(ma.auc(), mb.auc())


# -- whole-fit parity: the code operand built once a fit · widened a tree ----

def _fit_both_operand_forms(make_est, X=_FIT_X, y=_FIT_Y, names=_FIT_NAMES):
    """Train `make_est()` twice with the Pallas kernel (H2O3_HIST_METHOD,
    which every tree estimator reads) in interpret mode:
    as the rule says (one device, the kernel's levels: the operand is built
    once a fit) and with the rule made to answer "program" (every tree
    program widens the codes itself). Returns both with their fit plans."""
    from jax.experimental.pallas import tpu as pltpu
    from h2o3_tpu.models import shared_tree

    out = []
    with pltpu.force_tpu_interpret_mode():
        for forced in (None, {"form": "program", "row_chunk": 0}):
            with pytest.MonkeyPatch.context() as mp:
                # the fit's programs are traced in this thread, where
                # interpret mode is on
                mp.setenv("H2O3_WARM_THREAD", "0")
                mp.setenv("H2O3_HIST_METHOD", "pallas_factored")
                if forced is not None:
                    mp.setattr(shared_tree, "_cfg_operand_form",
                               lambda cfg, _f=forced: dict(_f))
                est = _train(make_est(), X, y, names)
            out.append((est, histogram.kernel_stats()["plans"][-1]))
    return out


def _gbm_kernel():
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    return H2OGradientBoostingEstimator(seed=42, ntrees=3, max_depth=3)


def _drf_kernel():
    from h2o3_tpu.models.drf import H2ORandomForestEstimator

    return H2ORandomForestEstimator(seed=42, ntrees=3, max_depth=3)


def _xgb_custom_kernel():
    from h2o3_tpu.models.xgboost import H2OXGBoostEstimator

    est = H2OXGBoostEstimator(seed=42, ntrees=3, max_depth=3)
    # a custom objective's round: `single_tree_jit` from the caller's (g, h)
    est._objective_fn = lambda m, y: (jax.nn.sigmoid(m) - y,
                                      jnp.full_like(m, 0.25))
    return est


@pytest.mark.parametrize("make_est", [_gbm_kernel, _drf_kernel,
                                      _xgb_custom_kernel])
def test_fit_parity_code_operand_fit_vs_program(cloud1, _tree_env, make_est):
    """Whole fits through `tree_jit` (GBM, DRF) and `single_tree_jit` (a
    custom-objective XGBoost round): the fit plan says where the kernel's
    operand was built, and the forest is the same either way."""
    (a, plan_a), (b, plan_b) = _fit_both_operand_forms(make_est)
    assert plan_a["code_operand"] == "fit"
    # 6 features padded to the kernel's 8, the rows to the largest row
    # chunk of the plan's levels (8,192 at 21 bins, 2,048 at 256)
    chunk = max(lv["row_chunk"] for lv in plan_a["levels"])
    assert plan_a["operand_bytes"] == 8 * (-(-_FIT_N // chunk) * chunk) * 4
    assert plan_b["code_operand"] == "program"
    assert plan_b["operand_bytes"] == 0
    assert all(lv["method"] == "pallas_factored"
               for p in (plan_a, plan_b) for lv in p["levels"])
    # and on which bin axis the kernel ran them: GBM's and DRF's 20 bins
    # and the NA bin on 24, XGBoost's 256 as they are
    padded = {21: 24, 256: 256}[plan_a["nbins"]]
    assert all(lv["bins_padded"] == padded
               for p in (plan_a, plan_b) for lv in p["levels"])
    _assert_models_bitexact(a, b)
    counts = histogram.kernel_stats()["code_operand"]
    assert counts.get("fit", 0) > 0 and counts.get("program", 0) > 0


@pytest.mark.parametrize("make_est", [_gbm_kernel, _xgb_custom_kernel])
def test_wide_fit_selects_over_the_code_operand(cloud1, _tree_env, make_est):
    """A frame wider than `_ONEHOT_LOOKUP_MAX`, whole fits through
    `tree_jit` and `single_tree_jit`: handed the operand, the partition
    selects over it, and the plan and the trace-time counter say
    `select`; made to widen for itself, the program gathers, and says
    `gather`. The forests are the same bit for bit."""
    f = 136
    X, y = make_classification(n=1024, f=f, seed=11)
    names = [f"f{i}" for i in range(f)] + ["label"]
    before = dict(histogram.kernel_stats()["partition_read"])
    (a, plan_a), (b, plan_b) = _fit_both_operand_forms(make_est, X, y, names)
    assert plan_a["code_operand"] == "fit"
    assert plan_a["partition_read"] == "select"
    assert plan_b["code_operand"] == "program"
    assert plan_b["partition_read"] == "gather"
    after = histogram.kernel_stats()["partition_read"]
    for read in ("select", "gather"):
        assert after.get(read, 0) > before.get(read, 0), (before, after)
    _assert_models_bitexact(a, b)


def test_cpu_fit_builds_no_code_operand(cloud1, _tree_env):
    """A CPU fit runs the `segment` kernel: its plan says the programs
    widen for themselves, and no operand is held."""
    _fit_gbm(_FIT_X, _FIT_Y, _FIT_NAMES, ntrees=2, max_depth=_FIT_DEPTH)
    plan = histogram.kernel_stats()["plans"][-1]
    assert plan["code_operand"] == "program" and plan["operand_bytes"] == 0
    from h2o3_tpu.runtime import metrics_registry

    assert "h2o3_tree_code_operand_total" in metrics_registry.prometheus_text()


# -- the warm-fit zero-retrace pin (ROADMAP item 2) -------------------------

def test_warm_fit_retraces_zero(cloud1, _tree_env):
    """A warm higgs-shaped fit (same _StepCfg; scalar hyperparameters may
    differ — they are traced, not static) must trace ZERO new programs and
    re-trace nothing, per the PR 6 per-signature XLA tracker."""
    from h2o3_tpu.runtime import phases

    X, y, names = _FIT_X, _FIT_Y, _FIT_NAMES
    _fit_gbm(X, y, names, ntrees=5, max_depth=_FIT_DEPTH,
             learn_rate=0.1)
    before = phases.xla_counts()
    # warm fit: same structural shape, different traced scalar (learn_rate)
    _fit_gbm(X, y, names, ntrees=5, max_depth=_FIT_DEPTH,
             learn_rate=0.2)
    after = phases.xla_counts()
    assert after["retraces"] == before["retraces"], \
        "warm fit re-traced a program signature"
    assert after["traces"] == before["traces"], \
        "warm fit traced a NEW program (cfg key must cover it)"


# -- kernel-selection observability -----------------------------------------

def test_fit_plan_recorded_and_profiler_fold(cloud1, _tree_env):
    X, y = make_classification(n=2048, f=5, seed=17)
    names = [f"f{i}" for i in range(5)] + ["label"]
    _fit_gbm(X, y, names, ntrees=2, max_depth=3)
    stats = histogram.kernel_stats()
    assert stats["plans"], "fit recorded no kernel plan"
    plan = stats["plans"][-1]
    assert plan["hist_method"] == "auto"      # as given; resolved per level
    assert plan["pack_bits"] in (4, 5, 6)
    # the one rule: a CPU runs the sorted scatter
    assert all(lv["method"] == "segment" for lv in plan["levels"])
    assert stats["dispatch"].get("segment", 0) > 0
    from h2o3_tpu.runtime import profiler

    fold = profiler.tree_stats()
    assert fold["active"] and fold["plans"]
    # the dispatch counters reach the Prometheus scrape surface
    from h2o3_tpu.runtime import metrics_registry

    text = metrics_registry.prometheus_text()
    assert "h2o3_tree_hist_dispatch_total" in text


def test_partition_read_recorded_in_kernel_stats(cloud1, _tree_env):
    """Which read the partition took is recorded beside the kernel plan:
    per fit in the plan, cumulatively (trace-time) in the registry, and
    folded into the profiler's `tree` surface."""
    X, y = make_classification(n=2048, f=5, seed=19)
    names = [f"f{i}" for i in range(5)] + ["label"]
    _fit_gbm(X, y, names, ntrees=2, max_depth=3)
    stats = histogram.kernel_stats()
    assert stats["plans"][-1]["partition_read"] == "select"
    assert stats["partition_read"].get("select", 0) > 0
    from h2o3_tpu.runtime import profiler

    assert profiler.tree_stats()["partition_read"] == stats["partition_read"]
    before = stats["partition_read"].get("gather", 0)
    rf = jnp.zeros(8, jnp.int32)
    treelib._row_codes(jnp.zeros((8, 130), jnp.uint8), rf)
    assert histogram.kernel_stats()["partition_read"]["gather"] == before + 1
    plan = histogram.record_fit_plan(
        "test:wide", [("d0", 1)], 21, "segment",
        partition_read=treelib.partition_read(130))
    assert plan["partition_read"] == "gather"
    # a wide fit handed the code operand selects over it, and says so:
    # in its plan, and in the trace-time counter of the read that runs
    levels = treelib.histogram_level_plan(3)
    form = histogram.code_operand_form(levels, 256, "pallas_factored",
                                       platform="tpu")["form"]
    assert form == "fit"
    plan = histogram.record_fit_plan(
        "test:wide_operand", levels, 256, "pallas_factored",
        platform="tpu", partition_read=treelib.partition_read(136, form),
        code_operand=form)
    assert plan["partition_read"] == "select"
    assert plan["code_operand"] == "fit"
    before = histogram.kernel_stats()["partition_read"]["select"]
    got = treelib._row_codes(jnp.zeros((8, 136), jnp.uint8), rf,
                             operand=jnp.zeros((136, 8), jnp.float32))
    assert np.array_equal(np.asarray(got), np.zeros(8, np.int32))
    assert histogram.kernel_stats()["partition_read"]["select"] == before + 1
    from h2o3_tpu.runtime import metrics_registry

    assert "h2o3_tree_partition_read_total" in metrics_registry.prometheus_text()


def test_vmem_fallback_counted_and_logged(_tree_env):
    """The previously-silent `_factored_row_chunk` < 512 fallback is
    observable: resolve_method reports it, record_fit_plan counts it in
    the registry and logs once per fit."""
    from h2o3_tpu.runtime import metrics_registry

    # a level too wide for any VMEM row chunk (L·B blows the scratch)
    sel = histogram.resolve_method(1 << 16, 64, "pallas_factored",
                                   platform="tpu")
    assert sel == {"method": "segment", "row_chunk": None,
                   "fallback": "vmem"}
    # and a feasible one keeps the pallas kernel + its row chunk
    ok = histogram.resolve_method(16, 64, "pallas_factored", platform="tpu")
    assert ok["method"] == "pallas_factored" and ok["row_chunk"] >= 512
    before = metrics_registry.get("h2o3_tree_hist_vmem_fallbacks").total()
    plan = histogram.record_fit_plan(
        "test:vmem", [("d0", 1), ("d16", 1 << 16)], 64,
        "pallas_factored", platform="tpu")
    after = metrics_registry.get("h2o3_tree_hist_vmem_fallbacks").total()
    assert after == before + 1
    assert [lv["fallback"] for lv in plan["levels"]] == [None, "vmem"]


@pytest.mark.parametrize("nbins,padded,chunk", [
    (21, 24, 8192),        # the HIGGS cell: nbins 20 and the NA bin
    (256, 256, 2048),      # the MSLR cell: nothing to pad
    (16, 16, 8192), (33, 40, 8192), (64, 64, 8192), (1024, 1024, 512)])
def test_fit_plan_records_bins_padded(_tree_env, nbins, padded, chunk):
    """The plan of a depth-6 fit as a TPU resolves it says, beside each
    level's `row_chunk`, the bin axis the Pallas kernel pads to
    (`hist_pallas.bins_padded`, a function of `nbins` alone); a level
    another kernel runs says None. It is what /3/Profiler's `tree` serves."""
    from h2o3_tpu.ops import hist_pallas

    assert hist_pallas.bins_padded(nbins) == padded
    levels = treelib.histogram_level_plan(6)
    plan = histogram.record_fit_plan(f"test:bins{nbins}", levels, nbins,
                                     "auto", platform="tpu")
    assert len(plan["levels"]) == len(levels) > 0
    for lv in plan["levels"]:
        assert lv["method"] == "pallas_factored"
        assert lv["bins_padded"] == padded
    assert max(lv["row_chunk"] for lv in plan["levels"]) == chunk
    assert histogram.kernel_stats()["plans"][-1] is plan
    deep = histogram.record_fit_plan(
        f"test:bins{nbins}:deep", [("d0", 1), ("d16", 1 << 16)], nbins,
        "pallas_factored", platform="tpu")
    assert [lv["bins_padded"] for lv in deep["levels"]] == [padded, None]
    cpu = histogram.record_fit_plan(f"test:bins{nbins}:cpu", levels, nbins,
                                    "auto", platform="cpu")
    assert {lv["bins_padded"] for lv in cpu["levels"]} == {None}


def test_dataset_cache_keys_pack_mode(cloud1, _tree_env):
    """A packed and a full-width consumer never share a device artifact."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models import dataset_cache

    dataset_cache.clear()
    X, y = make_classification(n=512, f=4, seed=23)
    fr = Frame.from_numpy(np.column_stack([X, y]),
                          names=["a", "b", "c", "d", "label"])
    calls = []
    for bits in (0, 5, 5):
        dataset_cache.device_codes(
            fr, ["a", "b", "c", "d"], 21, "AUTO", 1, 512,
            builder=lambda: calls.append(1) or jnp.zeros((1,)),
            pack_bits=bits)
    assert len(calls) == 2   # 0-bit and 5-bit miss; second 5-bit hits


@pytest.mark.parametrize("method", ["pallas", "host"])
def test_removed_hist_methods_are_refused(cloud1, _tree_env, method):
    """A kernel name that no longer exists is refused with the valid names,
    from the estimator's `hist_method=` and from H2O3_HIST_METHOD alike,
    before anything is traced."""
    from h2o3_tpu.runtime import phases

    X, y = make_classification(n=512, f=4, seed=29)
    names = [f"f{i}" for i in range(4)] + ["label"]
    _fit_gbm(X, y, names, ntrees=1, max_depth=2)       # frame-side programs
    before = phases.xla_counts()["traces"]
    valid = "auto, onehot, segment"
    with pytest.raises(ValueError, match=valid):
        _fit_gbm(X, y, names, ntrees=1, max_depth=2, hist_method=method)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("H2O3_HIST_METHOD", method)
        with pytest.raises(ValueError, match=valid):
            _fit_gbm(X, y, names, ntrees=1, max_depth=2)
    assert phases.xla_counts()["traces"] == before
    with pytest.raises(ValueError, match=valid):
        histogram.resolve_method(4, 21, method)
