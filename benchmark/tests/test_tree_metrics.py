"""The four readers the GBM cell brought, on a hand-built trace: the
arithmetic, and None where a parent commit's trace or counters hold nothing
for them."""

import types

import pytest

import manifest
from reduce_trace import Trace

ALGO = manifest.load_module("algos", "gbm")
SHAPES = {"rows": 819_000_000, "features": 8, "code_bits": 8, "depth": 2}


def built(hist_name="%tree_hist_factored"):
    # two trees of 1.0 s each; inside each, two histogram calls of 0.1 s
    modules = [("jit_tree_jit(9)", 0.0, 1.0), ("jit_tree_jit(9)", 1.5, 1.0),
               ("jit__binom_binned_stats(3)", 3.0, 0.5)]
    ops = [(f"{hist_name}.{i}", at, 0.1)
           for i, at in enumerate((0.1, 0.5, 1.6, 2.0))]
    ops += [("%fusion.10", 0.2, 0.3), ("%fusion.10", 1.7, 0.3)]
    return Trace([{"ops": ops, "modules": modules}], [])


def ctx(trace, **over):
    out = {"trace": trace, "algo": ALGO, "cfg": {"algo": "gbm"},
           "shapes": SHAPES, "device_kind": "TPU v5 lite", "steps": 2,
           "fits": 4, "counters": {"cache": {
               "matrix_hits": 4, "matrix_misses": 0, "bins_hits": 4,
               "bins_misses": 1, "device_hits": 3, "device_misses": 1,
               "evictions": 0}}}
    out.update(over)
    return out


def read(name, c):
    return manifest.load_module("metrics", name).read(c)


@pytest.mark.parametrize("hist_name", ["%tree_hist_factored",
                                       "%build_histograms_pallas_factored"])
def test_reader_arithmetic(hist_name):
    c = ctx(built(hist_name))
    assert read("tree_step_ms", c) == pytest.approx(1000.0)
    assert read("tree_nonhist_pct", c) == pytest.approx(80.0)
    # one pass: 819e6 x (8 + 12) bytes = 0.02 s at 819 GB/s; four passes in
    # 0.4 s of kernel time
    share, note = read("tree_hist_roofline", c)
    assert share == pytest.approx(20.0) and "bytes" in note and "4 passes" in note
    assert read("cache_misses_per_fit", c) == pytest.approx(0.5)


def test_nothing_to_read_returns_none():
    bare = Trace([{"ops": [("%fusion.1", 0.0, 1.0)],
                   "modules": [("jit_inner(1)", 0.0, 1.0)]}], [])
    for name in ("tree_step_ms", "tree_nonhist_pct", "tree_hist_roofline"):
        assert read(name, ctx(bare)) is None
        assert read(name, ctx(Trace([], []))) is None
        # an adapter that names no tree program (another algorithm's cell)
        assert read(name, ctx(built(), algo=types.SimpleNamespace())) is None
    assert read("cache_misses_per_fit", ctx(built(), counters={})) is None
    assert read("cache_misses_per_fit",
                ctx(built(), counters={"cache": {}})) is None


def test_the_new_metrics_are_the_new_cells():
    per_layer = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in ("tree_step_ms", "tree_nonhist_pct", "tree_hist_roofline",
                 "cache_misses_per_fit"):
        assert per_layer[name]["workloads"] == ["gbm_higgs_fit_sweep"]
        assert per_layer[name]["moves"] == "fit_wall_s"
