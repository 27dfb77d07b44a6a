"""The six readers the ranking cell brought, on a hand-built trace: the
arithmetic, and None where a parent commit's trace or shapes hold nothing
for them."""

import types

import pytest

import manifest
from reduce_trace import Trace

ALGO = manifest.load_module("algos", "xgbrank")
# 819e6 rows x 12 bytes = 0.012 s of required traffic a pass at 819 GB/s
SHAPES = {"rows": 819_000_000, "rank_rows": 819_000_000, "features": 8,
          "code_bits": 8, "depth": 2, "pairs": 10, "pair_slots": 4000}


def built():
    # one fit of two rounds: a pass of 0.6 s then a tree of 1.0 s, twice,
    # inside a fit.iterate of 4.0 s of a train of 5.0 s
    modules = [("jit__lambdarank_pass(7)", 1.0, 0.6),
               ("jit_single_tree_jit(9)", 1.7, 1.0),
               ("jit__lambdarank_pass(7)", 2.8, 0.6),
               ("jit_single_tree_jit(9)", 3.5, 1.0),
               ("jit_predict_forest_fused(3)", 5.2, 0.3)]
    host = [("main", "bench.window", 0.0, 6.0), ("main", "train", 0.5, 5.0),
            ("main", "train.fit", 0.5, 5.0),
            ("main", "fit.objective", 0.5, 0.4),
            ("main", "fit.iterate", 0.95, 4.0), ("main", "fit.ndcg", 5.0, 0.5)]
    return Trace([{"ops": [], "modules": modules}], host)


def ctx(trace, **over):
    out = {"trace": trace, "algo": ALGO, "cfg": {"algo": "xgbrank"},
           "shapes": SHAPES, "device_kind": "TPU v5 lite", "steps": 2,
           "fits": 1, "counters": {}}
    out.update(over)
    return out


def read(name, c):
    return manifest.load_module("metrics", name).read(c)


def test_reader_arithmetic():
    c = ctx(built())
    assert read("rank_pass_ms", c) == pytest.approx(600.0)
    share, note = read("rank_pass_roofline", c)
    assert share == pytest.approx(100 * 0.012 * 2 / 1.2)
    assert "bytes" in note and "2 passes" in note
    assert read("rank_pair_fill_pct", c) == pytest.approx(0.25)
    assert read("fit_objective_ms", c) == pytest.approx(400.0)
    assert read("fit_ndcg_ms", c) == pytest.approx(500.0)
    # 4.0 s of fit.iterate less 1.2 s of passes and 2.0 s of trees
    assert read("rank_iterate_wait_ms", c) == pytest.approx(800.0)
    # the accepted tree readers find the custom-objective lane's program
    assert read("tree_step_ms", c) == pytest.approx(1000.0)


def test_nothing_to_read_returns_none():
    bare = Trace([{"ops": [], "modules": [("jit_tree_jit(1)", 0.0, 1.0)]}], [])
    other = types.SimpleNamespace(TRACE_STEP_PROGRAM=r"^jit_tree_jit\(")
    for name in ("rank_pass_ms", "rank_pass_roofline", "rank_iterate_wait_ms"):
        assert read(name, ctx(bare)) is None
        assert read(name, ctx(Trace([], []))) is None
        # an adapter that names no ranking program (another algorithm's cell)
        assert read(name, ctx(built(), algo=other)) is None
    assert read("fit_objective_ms", ctx(bare)) is None
    assert read("fit_ndcg_ms", ctx(bare)) is None
    # a program whose fit plan holds no `rank` entry
    no_plan = {k: v for k, v in SHAPES.items()
               if k not in ("pairs", "pair_slots")}
    assert read("rank_pair_fill_pct", ctx(built(), shapes=no_plan)) is None
    assert read("rank_pass_roofline", ctx(built(), shapes=no_plan)) is None


def test_the_new_metrics_are_the_new_cells():
    per_layer = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in ("rank_pass_ms", "rank_pass_roofline", "rank_pair_fill_pct",
                 "fit_objective_ms", "rank_iterate_wait_ms", "fit_ndcg_ms"):
        assert per_layer[name]["workloads"] == ["xgb_mslr_fit_sweep"]
        assert per_layer[name]["moves"] == "fit_wall_s"
    for name in ("tree_step_ms", "tree_hist_roofline", "tree_nonhist_pct",
                 "train_unnamed_pct", "h2d_mb_per_fit"):
        assert "xgb_mslr_fit_sweep" in per_layer[name]["workloads"]
    assert "xgb_mslr_fit_sweep" not in \
        per_layer["fit_iterate_wait_ms"]["workloads"]
