"""first_fit.py and the seven readers it serves, on a hand-made span list:
the first `train` root and its descendants through two levels, None where
the ring cannot answer (evictions, no root, the warm-up not in it, a program
from before `tracing.dropped()`), each reader's arithmetic, and its None
where the program opens no such span."""

import pytest

import first_fit
import manifest
from reduce_trace import Trace

READERS = ("setup_fit_s", "setup_design_s", "setup_bin_s", "setup_compile_s",
           "setup_cache_load_s", "setup_trace_s")


def span(name, sid, parent, ts, dur, kind="fit", **attrs):
    return {"name": name, "kind": kind, "span_id": sid, "parent_id": parent,
            "ts": ts, "duration_s": dur, "thread": "MainThread",
            "attrs": attrs, "events": []}


def ring_of(drop=(), kinds=("fit", "xla", "program")):
    """A warm-up fit of 60 s and two fits of the window, recorded as the
    ring holds them: a span lands when it ENDS, so children come before
    their parents and the roots are not in order of start."""
    spans = [
        span("program.import", "i0", None, 0.0, 2.0, kind="program"),
        # the warm-up fit
        span("design.matrix", "a3", "a2", 10.1, 8.0, cache="miss"),
        span("design.bins", "a4", "a2", 18.1, 30.0, xla_trace_s=0.25),
        span("xla.cache_load", "x1", "a6", 49.0, 0.5, kind="xla",
             program="jit_expand", sig="jit_expand-1"),
        span("design.upload", "a6", "a5", 48.9, 1.0),
        span("design.codes", "a5", "a2", 48.1, 4.0),
        # the warm-up thread's span: a child of fit.design on another thread
        span("xla.compile", "x2", "a7", 50.0, 7.0, kind="xla",
             program="jit_tree_jit", sig="jit_tree_jit-2"),
        span("xla.compile", "x3", "a7", 57.0, 1.5, kind="xla",
             program="jit__binom_binned_stats", sig="s-3"),
        span("design.warm", "a7", "a2", 49.9, 9.0, xla_trace_s=2.0),
        span("fit.design", "a2", "a1", 10.1, 45.0),
        span("fit.objective", "a8", "a1", 55.1, 3.0),
        span("xla.compile", "x4", "a9", 58.2, 0.125, kind="xla",
             program="jit__stack_args", sig="s-4"),
        span("xla.compile", "x5", "a9", 58.4, 0.25, kind="xla",
             program="jit_add", sig="s-5"),
        span("fit.iterate", "a9", "a1", 58.1, 10.0, xla_trace_s=0.5),
        span("train.fit", "a1", "a0", 10.1, 59.5),
        span("train", "a0", None, 10.0, 60.0, xla_trace_s=0.125),
        # two fits of the window: hits, no request
        span("design.matrix", "b3", "b2", 80.0, 0.001, cache="hit"),
        span("fit.design", "b2", "b0", 80.0, 0.3),
        span("train", "b0", None, 80.0, 4.0),
        span("fit.design", "c2", "c0", 90.0, 0.3),
        span("train", "c0", None, 90.0, 4.0),
        # a compile request outside any fit: nobody's descendant
        span("xla.compile", "x9", None, 95.0, 99.0, kind="xla", program="p"),
    ]
    return [s for s in spans if s["name"] not in drop and s["kind"] in kinds]


@pytest.fixture
def ring(monkeypatch):
    def put(spans, dropped=0):
        monkeypatch.setattr(first_fit, "ring", lambda: (spans, dropped))
    put(ring_of())
    return put


CTX = {"fits": 2}


def read(name, ctx=CTX):
    got = manifest.load_module("metrics", name).read(ctx)
    return got[0] if isinstance(got, tuple) else got


def test_the_first_train_root_and_its_descendants_through_two_levels(ring):
    root, under = first_fit.tree(CTX)
    assert root["span_id"] == "a0"
    ids = {s["span_id"] for s in under}
    # x1 is four levels down (train.fit, fit.design, design.codes,
    # design.upload); nothing of the later fits, the import or the orphan
    assert ids == {"a1", "a2", "a3", "a4", "a5", "a6", "a7", "a8", "a9",
                   "x1", "x2", "x3", "x4", "x5"}


WANT = {"setup_fit_s": 60.0,
        "setup_design_s": 48.0,            # fit.design 45 + fit.objective 3
        "setup_bin_s": 42.0,               # matrix 8 + bins 30 + codes 4
        "setup_compile_s": 8.875,          # 7 + 1.5 + 0.125 + 0.25
        "setup_cache_load_s": 0.5,
        "setup_trace_s": 2.875}            # 0.25 + 2 + 0.5 + 0.125


@pytest.mark.parametrize("name", READERS)
def test_reader_arithmetic(ring, name):
    assert read(name) == pytest.approx(WANT[name])
    entry = [m for m in manifest.load_manifest()["per_layer"]
             if m["name"] == name]
    assert entry and entry[0]["moves"] == "setup_s"
    assert entry[0]["source"] == "program_span" and entry[0]["workloads"]
    assert WANT["setup_compile_s"] + WANT["setup_cache_load_s"] \
        + WANT["setup_trace_s"] <= WANT["setup_fit_s"]


def test_notes_name_the_longest_programs_and_count_the_loads(ring):
    value, note = manifest.load_module("metrics", "setup_compile_s").read(CTX)
    assert note == ("(4 programs; jit_tree_jit: 7.000; "
                    "jit__binom_binned_stats: 1.500; jit_add: 0.250)")
    _, note = manifest.load_module("metrics", "setup_cache_load_s").read(CTX)
    assert note == "(1 programs)"


@pytest.mark.parametrize("name", READERS)
def test_none_where_the_ring_cannot_answer(ring, monkeypatch, name):
    ring(ring_of(), dropped=1)                   # something was evicted
    assert read(name) is None
    ring(ring_of(drop=("train",)))               # no root
    assert read(name) is None
    ring(ring_of())
    assert read(name, {"fits": 3}) is None       # the warm-up is not in it
    assert read(name, {"fits": 2}) is not None
    # a program from before `tracing.dropped()`: the real ring, asked
    monkeypatch.undo()
    from h2o3_tpu.runtime import tracing

    monkeypatch.delattr(tracing, "dropped")
    assert first_fit.ring() is None and read(name) is None


MISSING = {"setup_design_s": ("fit.design", "fit.objective"),
           "setup_bin_s": ("design.matrix", "design.bins", "design.codes")}


@pytest.mark.parametrize("name", READERS[1:])
def test_none_where_the_program_opens_no_such_span(ring, name):
    if name in MISSING:
        ring(ring_of(drop=MISSING[name]))
        assert read(name) is None
        return
    # a listener that was never installed leaves no request on the tree:
    # absent, not 0
    ring(ring_of(kinds=("fit", "program")))
    assert read(name) is None
    # with the pipeline on the tree, a kind of request that did not happen
    # reads 0: a warm cache compiles nothing
    ring(ring_of(drop=("xla.compile",)))
    assert read("setup_compile_s") == 0.0
    assert read("setup_cache_load_s") == 0.5


def test_the_real_ring_is_read():
    from h2o3_tpu.runtime import tracing

    tracing.clear()
    for _ in range(2):
        with tracing.span("train", kind="fit"):
            with tracing.span("fit.design", kind="fit"):
                tracing.record_span("xla.compile", 0.01, kind="xla",
                                    program="jit_f", sig="jit_f-0")
    spans, dropped = first_fit.ring()
    assert dropped == 0 and len(spans) == 6
    root, under = first_fit.tree({"fits": 1})
    assert [s["name"] for s in under] == ["fit.design", "xla.compile"]
    assert root["ts"] == min(s["ts"] for s in spans if s["name"] == "train")
    tracing.clear()


# -- the device's share of the tree fit's training metrics ---------------------

def test_tree_metrics_device_ms():
    modules = [("jit_tree_jit(9)", 0.0, 1.0),
               ("jit__binom_binned_stats(3)", 3.0, 0.5),
               ("jit__binom_binned_stats(3)", 7.0, 0.75)]
    ctx = {"trace": Trace([{"ops": [], "modules": modules}], []), "fits": 2}
    reader = manifest.load_module("metrics", "tree_metrics_device_ms").read
    assert reader(ctx) == pytest.approx(625.0)
    bare = Trace([{"ops": [], "modules": modules[:1]}], [])
    assert reader(dict(ctx, trace=bare)) is None
    assert reader(dict(ctx, trace=Trace([], []))) is None
    assert reader(dict(ctx, fits=0)) is None
    entry, = [m for m in manifest.load_manifest()["per_layer"]
              if m["name"] == "tree_metrics_device_ms"]
    assert entry["moves"] == "fit_wall_s"
    assert entry["workloads"] == ["gbm_higgs_fit_sweep"]
    assert entry["layer"] == "training metrics"
