"""The system against the benchmark's plain LambdaMART reference, on the CPU:
`H2OXGBoostEstimator(objective="rank:ndcg")` at 6,000 x 136 (so the
partition's gather read runs) in 200 ragged queries of 1 to 250 documents,
256 bins, depth 6, 3 trees (the benchmark's test-only copy of the `xgb_mslr`
configuration at these sizes, columns from the seed), followed by
`benchmark/references/xgbrank_reference.py`, which imports nothing of
h2o3_tpu: it computes the pairwise lambda-gradients per query with no padding
in float64, bins the raw columns itself, rebuilds the first two trees'
histograms level by level at margins it carries itself and walks all trees
for the NDCG and the margins' checksum."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import tree as treelib
from h2o3_tpu.models.xgboost import H2OXGBoostEstimator, _make_lambdarank
from h2o3_tpu.ops.histogram import kernel_stats

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")

# What the comparison reads on the CPU at this size, with the reason for its
# room (sandbox readings of this PR, 8 seeds at 3,000 rows and 2 at 6,000):
CPU_LIMITS = {
    # the program's pass at the reference's margins, both followed rounds:
    # float32 pair terms summed over a padded (G, G) block against float64
    # over the real pairs (read: at most 1.6e-6; bfloat16 pair terms read
    # 4e-3 and more)
    "pair_grad_gap": 2e-5,
    # float32 gains on exact host histograms against float64: only a
    # near-tie can part them (read: at most 1.9e-6)
    "split_gain_gap": 1e-3,
    # a small right child's histogram is parent minus left in float32
    # (read: at most 1.3e-4)
    "leaf_value_gap": 2e-3,
    # the same stable ordering of the same margins (read: at most 4.4e-16)
    "ndcg_gap": 1e-9,
    # float32 margins against the float64 walk (read: at most 1.5e-8)
    "margin_gap": 1e-6,
}
# float32 pair terms summed over a padded (G, G) block against float64 over
# the real pairs, as a share of the largest gradient (read: 3e-7)
GRAD_TOL = 1e-5
OVERRIDES = {"learn_rate": 0.1, "min_rows": 5, "min_split_improvement": 1e-5,
             "seed": 7}


@pytest.fixture(scope="module")
def bench():
    added = [p for p in (BENCH,) if p not in sys.path]
    sys.path[:0] = added
    import manifest

    cfg = manifest.load_json(os.path.join(BENCH, "tests", "configs",
                                          "xgb_mslr.json"))
    cfg = dict(cfg, rows=6000, queries=200,
               estimator=dict(cfg["estimator"], max_bins=256))
    yield (cfg, manifest.load_module("algos", "xgbrank"),
           manifest.load_module("references", "xgbrank_reference"))
    for p in added:
        sys.path.remove(p)


@pytest.fixture(scope="module")
def fitted(bench):
    import jax

    from h2o3_tpu.parallel import mesh

    cfg, algo, ref = bench
    data = algo.make_data(cfg, 2 ** 31 + 11)
    est = algo.make_estimator(cfg, OVERRIDES)
    frame = algo.make_frame(algo.make_columns(data))
    mesh.init(jax.devices()[:1])      # `cloud1`, which is a test's to ask for
    algo.train(est, frame)
    mesh.reset()
    plan = kernel_stats()["plans"][-1]
    return (data, est, frame, plan, algo.result(cfg, est, OVERRIDES),
            ref.prepare(cfg, data))


def program_grads(qid, rel, margin):
    g, h = _make_lambdarank(np.asarray(qid, np.int64),
                            np.asarray(rel, np.float64), 10)(
        jnp.asarray(margin, jnp.float32), None)
    return np.asarray(g, np.float64), np.asarray(h, np.float64)


def reference_grads(ref, qid, rel, margin):
    return ref.lambda_grads(
        ref.Queries(np.asarray(qid), np.asarray(rel, np.float64), 10),
        np.asarray(np.asarray(margin, np.float32), np.float64))


def test_first_round_gradients_of_every_row(bench, fitted):
    _, _, ref = bench
    data = fitted[0]
    zeros = np.zeros(len(data["qid"]))
    g, h = program_grads(data["qid"], data["rel"], zeros)
    G, H = reference_grads(ref, data["qid"], data["rel"], zeros)
    assert np.abs(G).max() > 0.1
    assert np.abs(g - G).max() <= GRAD_TOL * np.abs(G).max()
    assert np.abs(h - H).max() <= GRAD_TOL * np.abs(H).max()


def test_the_forest_follows_the_plain_reference(bench, fitted):
    cfg, _, ref = bench
    data, est, frame, plan, result, prep = fitted
    assert result["feat"].shape == (3, 127) and result["is_split"][:, 0].all()
    numbers = ref.compare(cfg, prep, result)
    assert numbers["edges_gap"] < 1e-12
    for name, limit in CPU_LIMITS.items():
        assert np.isfinite(numbers[name]) and numbers[name] <= limit, numbers
    # ten trees' worth of signal already in three: far above the frame order
    assert result["ndcg"] > ref.ndcg(prep.queries, np.zeros(prep.n)) + 0.2
    # the reported NDCG is read off the fit's own final margins: scoring
    # the frame afresh through the forest gives the same number
    assert result["ndcg"] == pytest.approx(est.ndcg(frame), abs=1e-6)
    # and the comparison is not blind: the reference's own forest on plain
    # RankNet lambdas (no Delta-NDCG), in the program's place, fails it
    wrong = ref.compare(cfg, prep, ref.faulty(cfg, prep, result["params"],
                                              "ranknet_lambda"))
    assert wrong["split_gain_gap"] > 0.05 and wrong["leaf_value_gap"] > 0.05
    assert wrong["pair_grad_gap"] > 1.0
    # nor to the pair terms alone: the control fails by the pass's own number
    below = ref.compare(cfg, prep, ref.control(cfg, prep, result["params"]))
    assert below["pair_grad_gap"] > 1e-3


def test_the_fit_says_what_it_ran(cloud1, bench, fitted):
    _, _, ref = bench
    data, est, frame, plan, result, prep = fitted
    assert treelib.partition_read(136) == "gather"
    assert treelib.partition_read(28) == "select"
    assert plan["partition_read"] == "gather" and plan["nbins"] == 256
    rank = plan["rank"]
    assert rank["queries"] == 200 and rank["group_max"] == 250
    assert rank["pairs"] == prep.queries.pairs() <= rank["pair_slots"]
    assert rank["pair_slots"] == 200 * 250 * 250 and rank["q_chunk"] == 200
    # the reported NDCG is a number, the one ndcg(frame) computes
    reported = est.model.training_metrics.ndcg
    assert reported == est.ndcg() == pytest.approx(est.ndcg(frame), abs=1e-12)
    assert f"{reported:.5f}" in est.model.training_metrics.description


def _case(name):
    """(qid, relevance, margins) of a few queries around one special one."""
    rng = np.random.default_rng(5)
    sizes = [7, 1, 12, 9]
    qid = np.repeat([3, 8, 11, 20], sizes)
    rel = rng.integers(0, 5, len(qid)).astype(float)
    margin = rng.normal(size=len(qid))
    special = qid == 11
    if name == "one_document":
        special = qid == 8
    elif name == "equal_relevance":
        rel[special] = 2.0
    elif name == "zero_relevance":
        rel[special] = 0.0
    elif name == "tied_margins":
        margin[special] = np.repeat([0.5, -0.25, 0.5], 4)
    return qid, rel, margin, special


@pytest.mark.parametrize("name", ["one_document", "equal_relevance",
                                  "zero_relevance", "tied_margins"])
def test_gradients_of_a_special_query(bench, name):
    _, _, ref = bench
    qid, rel, margin, special = _case(name)
    g, h = program_grads(qid, rel, margin)
    G, H = reference_grads(ref, qid, rel, margin)
    assert np.abs(g - G).max() <= GRAD_TOL * np.abs(G).max()
    assert np.abs(h - H).max() <= GRAD_TOL * np.abs(H).max()
    if name == "tied_margins":
        # ties rank in frame order, so equal margins get unequal discounts
        assert np.abs(G[special]).max() > 0
    else:
        # no pair with r_i > r_j (or an ideal DCG of 0): nothing to learn
        assert np.all(G[special] == 0) and np.all(H[special] == 1e-6)
        assert np.all(g[special] == 0) and np.allclose(h[special], 1e-6)


def test_padding_to_the_largest_group_leaks_nothing(bench):
    """Ragged queries padded to the largest G give each query the gradients
    it has among queries of its own size, and alone."""
    rng = np.random.default_rng(9)
    sizes = np.array([20, 7, 20, 3, 1, 20])
    qid = np.repeat(np.arange(len(sizes)), sizes)
    rel = rng.integers(0, 5, len(qid)).astype(float)
    margin = rng.normal(size=len(qid))
    g, h = program_grads(qid, rel, margin)
    for keep in (sizes[qid] == 20, qid == 1, qid == 3):
        ga, ha = program_grads(qid[keep], rel[keep], margin[keep])
        assert np.allclose(g[keep], ga, rtol=1e-6, atol=1e-9)
        assert np.allclose(h[keep], ha, rtol=1e-6, atol=1e-9)


def test_the_same_rows_in_shuffled_query_order_give_the_same_forest(cloud1):
    rng = np.random.default_rng(21)
    sizes = np.maximum(1, rng.lognormal(2.3, 0.8, 60).astype(int))
    qid = np.repeat(np.arange(len(sizes)), sizes)
    X = rng.normal(size=(len(qid), 8))
    rel = np.clip(np.rint(X[:, 0] + 0.5 * X[:, 1]
                          + 0.5 * rng.normal(size=len(qid)) + 1), 0, 4)
    order = np.concatenate([np.flatnonzero(qid == q)
                            for q in rng.permutation(len(sizes))])
    fits = []
    for rows in (np.arange(len(qid)), order):
        fr = Frame.from_dict({**{f"f{i}": X[rows, i] for i in range(8)},
                              "qid": qid[rows].astype(float),
                              "rel": rel[rows]})
        est = H2OXGBoostEstimator(ntrees=3, max_depth=4, max_bins=64, eta=0.2,
                                  min_rows=3, objective="rank:ndcg",
                                  group_column="qid", seed=3)
        est.train(y="rel", training_frame=fr, x=[f"f{i}" for i in range(8)])
        scores = np.empty(len(qid))
        scores[rows] = est.model._margins(est.model._matrix(fr))[:, 0]
        fits.append((est.model.forest[0], scores, est.ndcg()))
    (a, scores_a, ndcg_a), (b, scores_b, ndcg_b) = fits
    # the same trees as functions of the rows: where a node's neighbouring
    # bins are empty two bins cut the same rows apart, and float32 sums in
    # another row order may name the other one
    assert np.array_equal(a.feat, b.feat)
    assert np.array_equal(a.is_split, b.is_split)
    assert np.allclose(scores_a, scores_b, rtol=1e-4, atol=1e-6)
    assert ndcg_a == pytest.approx(ndcg_b, abs=1e-9)
