"""Seed determinism + padded-shape invariance of trained models.

The flagship fixed-seed AUC once moved 0.85226 → 0.85022 between rounds;
a bisect pinned it to a histogram-method default change (onehot →
pallas_factored): different f32 accumulation order at 1M rows flips near-tie splits. These tests lock
the invariants that SHOULD hold: same seed ⇒ identical model (across runs,
and across padded row-count changes such as `_bucket_rows` bucketing), per
histogram method.

These are SINGLE-DEVICE pins (cloud1): on a mesh the sharded path's
reduction geometry is a function of the padded shape (S blocks of npad/S
rows), so changing npad moves block boundaries — dust-level histogram
deltas that can flip a near-tie split, exactly the r03 mechanism. The
mesh-side determinism contract is different and pinned in
tests/test_tree_sharded.py: any two fits sharing the canonical block
count (at ANY device count 1/2/4/8) are bit-identical.
"""

import os

import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator


def _frame(n=20_000, f=6, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = ((X[:, 0] * X[:, 1] + 0.5 * X[:, 2] + 0.4 * rng.normal(size=n)) > 0)
    d = {f"f{i}": X[:, i] for i in range(f)}
    d["y"] = y.astype(int).astype(str)
    return (h2o.H2OFrame_from_python(d, column_types={"y": "enum"}),
            [f"f{i}" for i in range(f)])


def _train_probs(fr, x, **env):
    old = {k: os.environ.get(k) for k in env}
    os.environ.update({k: str(v) for k, v in env.items()})
    try:
        gbm = H2OGradientBoostingEstimator(
            ntrees=10, max_depth=5, learn_rate=0.2, seed=42,
            sample_rate=0.8, col_sample_rate=0.8)
        gbm.train(x=x, y="y", training_frame=fr)
        return gbm.predict(fr).vec("1").numeric_np(), float(gbm.auc())
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_same_seed_same_model(cloud1):
    fr, x = _frame()
    p1, auc1 = _train_probs(fr, x)
    p2, auc2 = _train_probs(fr, x)
    assert auc1 == auc2
    np.testing.assert_array_equal(p1, p2)


def test_padded_shape_invariance(cloud1):
    """Bucketing pads 20k rows up to 20480 zero-weight rows. Zero rows add
    exactly 0.0 to every histogram sum, but a different array SHAPE changes
    XLA's f32 reduction order (machine-dependent SIMD regrouping), and a
    dust-level histogram delta can flip ONE near-tie split whose rerouting
    then cascades through later boosting rounds — the bisect mechanism above
    (a method change moved flagship AUC 0.002).
    Measured on this 1-core box: dAUC ≈ 6e-3 with most per-row
    probabilities moving, from exactly such a flip. The invariant that
    HOLDS everywhere is model QUALITY: AUC agrees to ~1e-2 and both
    models clearly learn; per-row equality across padded shapes is pinned
    where it is actually guaranteed — same shape + same seed
    (test_same_seed_same_model), and the sharded lane's canonical-block
    contract (tests/test_tree_sharded.py)."""
    fr, x = _frame()
    p_bucket, auc_bucket = _train_probs(fr, x, H2O3_BUCKET_ROWS="1")
    p_exact, auc_exact = _train_probs(fr, x, H2O3_BUCKET_ROWS="0")
    assert abs(auc_bucket - auc_exact) < 0.02
    assert min(auc_bucket, auc_exact) > 0.8
    # the two probability vectors rank rows the same way to high agreement
    assert np.corrcoef(p_bucket, p_exact)[0, 1] > 0.98
    # flip noise is SYMMETRIC; a real histogram bug (dropped rows/blocks,
    # shifted bins) moves probabilities systematically — calibration and
    # confidence mass must stay put (measured noise: ~5e-4 and ~8e-3)
    assert abs(p_bucket.mean() - p_exact.mean()) < 0.01
    assert abs(np.abs(p_bucket - 0.5).mean()
               - np.abs(p_exact - 0.5).mean()) < 0.03


@pytest.mark.parametrize("method", ["segment", "onehot"])
def test_hist_methods_agree_small(method, cloud1):
    """Histogram methods accumulate in different f32 orders (scatter fold
    vs MXU matmul tree), so a near-tie split may flip and cascade (see
    test_padded_shape_invariance — the same r03 mechanism, dAUC ≈ 1e-3
    measured here for onehot). A WRONG histogram — dropped rows,
    off-by-one bins — moves AUC by orders of magnitude more than this
    bound and destroys the prediction correlation."""
    fr, x = _frame(n=8_000)
    p_auto, auc_auto = _train_probs(fr, x)
    p_m, auc_m = _train_probs(fr, x, H2O3_HIST_METHOD=method)
    assert abs(auc_auto - auc_m) < 0.02
    assert min(auc_auto, auc_m) > 0.8
    assert np.corrcoef(p_auto, p_m)[0, 1] > 0.98
    # systematic-shift detectors (see test_padded_shape_invariance): a
    # kernel that loses or double-counts rows shifts calibration or
    # confidence mass far beyond the symmetric flip noise
    assert abs(p_auto.mean() - p_m.mean()) < 0.01
    assert abs(np.abs(p_auto - 0.5).mean()
               - np.abs(p_m - 0.5).mean()) < 0.03
