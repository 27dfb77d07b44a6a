"""The system against the benchmark's plain GBM reference, on the CPU: the
forest of `H2OGradientBoostingEstimator.train()` at 6,000 x 28, depth 6,
3 trees (the benchmark's test-only copy of the `gbm_higgs` configuration,
columns from the seed), followed by `benchmark/references/gbm_reference.py`,
which imports nothing of h2o3_tpu: it bins the raw columns itself, rebuilds
the first two trees' histograms level by level at margins it carries itself,
evaluates every admissible split in float64 and walks all trees for the
training metrics."""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")

# What the comparison reads on the CPU at this size, with the reason for its
# room (8 seeds x the traffic's candidates, sandbox readings of this PR):
CPU_LIMITS = {
    # float32 gains against float64 on exact host histograms: only a
    # near-tie can part them (read: at most 1.8e-6)
    "split_gain_gap": 1e-3,
    # a small right child's histogram is parent minus left in float32, so an
    # unsplit 8-row node's stored value is 1.8e-3 off (read: at most 1.8e-3)
    "leaf_value_gap": 5e-3,
    # float32 margins against the float64 walk (read: at most 1.6e-7)
    "logloss_gap": 1e-5,
    # the reported AUC is the 400-bin device reduction; at 6,000 rows a bin
    # holds 15 (read: at most 1.4e-4)
    "auc_gap": 1e-3,
}


@pytest.fixture(scope="module")
def bench():
    added = [p for p in (BENCH,) if p not in sys.path]
    sys.path[:0] = added
    import manifest

    cfg = manifest.load_json(os.path.join(BENCH, "tests", "configs",
                                          "gbm_higgs.json"))
    yield (cfg, manifest.load_module("algos", "gbm"),
           manifest.load_module("references", "gbm_reference"))
    for p in added:
        sys.path.remove(p)


@pytest.mark.parametrize("seed,overrides", [
    (2 ** 31 + 11, {"learn_rate": 0.05, "min_rows": 5,
                    "min_split_improvement": 1e-4, "seed": 7}),
    (3500015857, {"learn_rate": 0.2, "min_rows": 20,
                  "min_split_improvement": 1e-5, "seed": 8}),
])
def test_the_forest_follows_the_plain_reference(cloud1, bench, seed,
                                                overrides):
    cfg, algo, ref = bench
    data = algo.make_data(cfg, seed)
    est = algo.make_estimator(cfg, overrides)
    algo.train(est, algo.make_frame(algo.make_columns(data)))
    result = algo.result(cfg, est, overrides)
    assert result["feat"].shape == (3, 127) and result["is_split"][:, 0].all()
    prep = ref.prepare(cfg, data)
    numbers = ref.compare(cfg, prep, result)
    assert numbers["edges_gap"] < 1e-12
    for name, limit in CPU_LIMITS.items():
        assert np.isfinite(numbers[name]) and numbers[name] <= limit, numbers
    # and the comparison is not blind: the reference's own forest with every
    # node's second-best split, in the program's place, fails it
    wrong = ref.compare(cfg, prep, ref.faulty(cfg, prep, result["params"],
                                              "second_best_split"))
    assert wrong["split_gain_gap"] > 0.05
