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
from h2o3_tpu.models import xgboost as xgb
from h2o3_tpu.models.xgboost import H2OXGBoostEstimator, _make_lambdarank
from h2o3_tpu.ops.histogram import kernel_stats
from h2o3_tpu.runtime import phases, tracing

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
    tracing.clear()
    algo.train(est, frame)
    mesh.reset()
    plan = kernel_stats()["plans"][-1]
    (span,) = [s for s in tracing.spans() if s["name"] == "fit.objective"]
    return (data, est, frame, plan, algo.result(cfg, est, OVERRIDES),
            ref.prepare(cfg, data), span["attrs"])


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
    data, est, frame, plan, result, prep, _ = fitted
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
    data, est, frame, plan, result, prep, span = fitted
    assert treelib.partition_read(136) == "gather"
    assert treelib.partition_read(136, "fit") == "select"
    assert treelib.partition_read(28) == "select"
    # a CPU fit has no code operand (`segment` levels): it gathers
    assert plan["code_operand"] == "program"
    assert plan["partition_read"] == "gather" and plan["nbins"] == 256
    rank = plan["rank"]
    assert rank["queries"] == 200 and rank["group_max"] == 250
    assert rank["pairs"] == prep.queries.pairs() <= rank["pair_slots"]
    # 1 to 250 documents a query: two size classes, each query in the
    # smallest that holds it, one chunk each and no padding query
    sizes = np.bincount(np.unique(data["qid"], return_inverse=True)[1])
    small = int((sizes <= 128).sum())
    assert rank["classes"] == [
        dict(width=128, queries=small, padded_queries=small, q_chunk=small),
        dict(width=256, queries=200 - small, padded_queries=200 - small,
             q_chunk=200 - small)]
    assert 0 < small < 200 and "q_chunk" not in rank
    assert rank["pair_slots"] == small * 128 ** 2 + (200 - small) * 256 ** 2
    assert rank["pair_slots"] < 200 * 250 * 250
    # the span says the same, with the classes as a count
    assert span == dict({k: v for k, v in rank.items() if k != "classes"},
                        n_classes=2)
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


def test_padding_to_a_class_width_leaks_nothing(bench):
    """Ragged queries padded to their class's width give each query the
    gradients it has among queries of its own size, and alone."""
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


# Query sizes that cross size classes (the cases above all sit under 128
# documents a query, so in one class).
SIZE_MIXES = {
    "every_class_edge": [5, 127, 128, 129, 300, 1, 700],
    "one_size": [40] * 9,
    "one_large_among_small": [12, 1100, 30, 7, 3],
}


def _mix(name):
    """(qid, relevance, margins, sizes) of a mix's queries, rows contiguous
    and qid ascending."""
    sizes = np.array(SIZE_MIXES[name])
    rng = np.random.default_rng(len(sizes) + int(sizes.sum()))
    qid = np.repeat(3 + 7 * np.arange(len(sizes)), sizes)
    rel = rng.integers(0, 5, len(qid)).astype(float)
    return qid, rel, rng.normal(size=len(qid)), sizes


def _close(a, b):
    """As `GRAD_TOL` reads it: a share of the largest value."""
    return np.abs(a - b).max() <= GRAD_TOL * max(np.abs(b).max(), 1e-6)


@pytest.mark.parametrize("name", list(SIZE_MIXES))
def test_gradients_across_size_classes(bench, name):
    _, _, ref = bench
    qid, rel, margin, sizes = _mix(name)
    g, h = program_grads(qid, rel, margin)
    G, H = reference_grads(ref, qid, rel, margin)
    assert np.abs(G).max() > 0.1
    for q in np.unique(qid):
        keep = qid == q
        assert _close(g[keep], G[keep]) and _close(h[keep], H[keep])
    # and a query of each size has what it gets alone, in its own class
    for q in np.unique(qid)[np.unique(sizes, return_index=True)[1]]:
        keep = qid == q
        ga, ha = program_grads(qid[keep], rel[keep], margin[keep])
        assert _close(g[keep], ga) and _close(h[keep], ha)


@pytest.mark.parametrize("name", list(SIZE_MIXES))
def test_rows_in_any_order_get_their_own_gradients(name):
    """The same rows shuffled, so that queries interleave and a query's
    rows lie apart, and then with the queries' ids renumbered in another
    order: (g, h) row for row."""
    qid, rel, margin, sizes = _mix(name)
    g, h = program_grads(qid, rel, margin)
    rng = np.random.default_rng(17)
    # frame order breaks ties of the margins, and these have none
    rows = rng.permutation(len(qid))
    gs, hs = program_grads(qid[rows], rel[rows], margin[rows])
    assert _close(gs, g[rows]) and _close(hs, h[rows])
    renumbered = rng.permutation(len(sizes))[(qid - 3) // 7]
    gr, hr = program_grads(renumbered[rows], rel[rows], margin[rows])
    assert _close(gr, g[rows]) and _close(hr, h[rows])


@pytest.mark.parametrize("sizes, widths", [
    ([5, 127, 128, 129, 300, 1, 700], [128, 256, 512, 768]),
    ([40] * 9, [128]),                     # one size: one class
    ([250] * 4, [256]),                    # the program of PR 30, G to a tile
    ([12, 1100, 30, 7, 3], [128, 1152]),   # no query there: no class
    ([1, 1251, 120, 500, 200, 1000], [128, 256, 512, 1024, 1280]),
    ([129, 100_000], [1024, 100_096]),     # of 8 widths 1,024 is the least
])
def test_widths_follow_from_the_sizes_alone(sizes, widths):
    assert list(xgb._class_widths(np.array(sizes))) == widths
    assert len(xgb._class_widths(np.arange(1, 10 ** 6, 997))) == 8


@pytest.mark.parametrize("name", list(SIZE_MIXES))
def test_the_plan_counts_the_slots_the_program_holds(monkeypatch, name):
    seen = []
    monkeypatch.setattr(
        xgb, "_lambdarank_pass",
        lambda margin, classes, slot: seen.append((classes, slot)))
    _, rel, margin, sizes = _mix(name)
    plans = []
    for base in (0, 1000):      # not from the ids, nor from the relevance
        qid = np.repeat(base + np.arange(len(sizes)), sizes)
        objective = _make_lambdarank(qid, np.roll(rel, base), 10)
        objective(margin, None)
        plans.append(objective.rank_plan)
    assert plans[0]["classes"] == plans[1]["classes"]
    plan = plans[0]
    assert ([c["width"] for c in plan["classes"]]
            == list(xgb._class_widths(sizes)))
    assert sum(c["queries"] for c in plan["classes"]) == len(sizes)
    # what the plan says is what the program's arguments hold
    classes, slot = seen[-1]
    held = 0
    for c, (idx, rmat, gmat, inv) in zip(plan["classes"], classes):
        chunks, q_chunk, width = idx.shape
        assert (q_chunk, width) == (c["q_chunk"], c["width"])
        assert chunks * q_chunk == c["padded_queries"] >= c["queries"]
        assert q_chunk * width * width <= xgb._PAIR_BLOCK
        assert rmat.shape == gmat.shape == idx.shape
        assert inv.shape == idx.shape[:2]
        held += idx.size * width
    assert plan["pair_slots"] == held >= plan["pairs"]
    # every row owns one slot, and no two the same
    assert len(np.unique(np.asarray(slot))) == len(slot) == sizes.sum()


def test_a_class_is_padded_to_its_chunks_not_to_a_fixed_chunk(monkeypatch,
                                                              bench):
    """More queries than one block holds: the chunk comes from the count."""
    _, _, ref = bench
    monkeypatch.setattr(xgb, "_PAIR_BLOCK", 4 * 128 * 128)
    sizes = np.array([3] * 9 + [200])
    qid = np.repeat(np.arange(10), sizes)
    rng = np.random.default_rng(2)
    rel = rng.integers(0, 5, len(qid)).astype(float)
    margin = rng.normal(size=len(qid))
    objective = _make_lambdarank(qid, rel, 10)
    # 9 queries where a block holds 4: three chunks of 3, not 12 slots
    assert objective.rank_plan["classes"] == [
        dict(width=128, queries=9, padded_queries=9, q_chunk=3),
        dict(width=256, queries=1, padded_queries=1, q_chunk=1)]
    g, h = program_grads(qid, rel, margin)
    G, H = reference_grads(ref, qid, rel, margin)
    assert _close(g, G) and _close(h, H)


def test_a_second_objective_on_the_same_sizes_compiles_nothing():
    phases.install_listener()
    qid, rel, margin, _ = _mix("every_class_edge")
    program_grads(qid, rel, margin)
    before = phases.xla_counts()
    # other relevance, other margins, other ids: the same shapes
    g, _ = program_grads(qid + 5, rel[::-1].copy(), -margin)
    after = phases.xla_counts()
    assert np.abs(g).max() > 0
    assert (after["compiles"], after["traces"]) == (before["compiles"],
                                                    before["traces"])


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
