"""A frame's first fit on the span tree (ISSUE 35): the compile pipeline's
listener hears every estimator, a compile request is a span with a length
where the first call of a program was made, trace seconds tally on the open
span, the ring counts what it evicts, and the tree fit's `fit.metrics` is
tiled by stages. The fresh-process cases share ONE child process (a GBM
`train()` where no estimator-engine fit, server or serving engine ran)."""

import json
import os
import random
import subprocess
import sys
import time
from collections import deque

import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import shared_tree
from h2o3_tpu.runtime import metrics_registry as registry
from h2o3_tpu.runtime import phases, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# two fits at a shape no other test uses, in a process of their own: the
# counts before and after the first, and every span the process recorded
FRESH = r"""
import json, sys
import numpy as np
import h2o3_tpu
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
from h2o3_tpu.runtime import phases, tracing

rng = np.random.default_rng(35)
n = 5017
X = rng.normal(size=(n, 5))
y = (X[:, 0] - X[:, 1] + rng.normal(size=n) > 0).astype(np.int64)
fr = Frame.from_dict({**{f"x{i}": X[:, i] for i in range(5)},
                      "y": np.asarray(["n", "p"], dtype=object)[y]},
                     column_types={"y": "enum"})
before = phases.xla_counts()
counts = []
for _ in range(2):
    H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=1).train(
        y="y", training_frame=fr)
    counts.append(phases.xla_counts())
print(json.dumps(dict(before=before, counts=counts, spans=tracing.spans(),
                      dropped=tracing.dropped())))
"""


@pytest.fixture(scope="module")
def fresh():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, "-c", FRESH], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    roots = sorted((s for s in out["spans"] if s["name"] == "train"),
                   key=lambda s: s["ts"])
    assert len(roots) == 2 and out["dropped"] == 0
    out["fits"] = [(r, _under(out["spans"], r)) for r in roots]
    return out


def _under(spans, root):
    """Every descendant of `root` by parent id, transitively."""
    ids, found = {root["span_id"]}, []
    grew = True
    while grew:
        grew = False
        for s in spans:
            if s["parent_id"] in ids and s["span_id"] not in ids:
                ids.add(s["span_id"])
                found.append(s)
                grew = True
    return found


def test_a_tree_fit_in_a_fresh_process_is_counted(fresh):
    """Red before the listener was installed in `H2OEstimator.train`: the
    tree path never called `install_listener`, so all of this read 0."""
    assert not any(fresh["before"].values())
    after = fresh["counts"][0]
    assert after["traces"] > 0
    assert after["compiles"] + after["cache_retrievals"] > 0
    # and a second fit at the same shape requests nothing
    assert fresh["counts"][1] == after


def test_first_fit_has_its_compile_requests_as_spans(fresh):
    root, under = fresh["fits"][0]
    xla = [s for s in under if s["kind"] == "xla"]
    assert xla and {s["name"] for s in xla} <= {"xla.compile",
                                                "xla.cache_load"}
    assert len(xla) == fresh["counts"][0]["compiles"]      # one a request
    assert (len([s for s in xla if s["name"] == "xla.cache_load"])
            == fresh["counts"][0]["cache_retrievals"])
    for s in xla:
        assert s["attrs"]["program"].startswith("jit_")
        assert s["attrs"]["sig"].startswith(s["attrs"]["program"] + "-")
        assert s["duration_s"] > 0
    # the tree program and the binned metrics are compiled on the warm-up
    # thread, which continues the fit's trace under `fit.design`
    (warm,) = [s for s in under if s["name"] == "design.warm"]
    (design,) = [s for s in under if s["name"] == "fit.design"]
    assert warm["parent_id"] == design["span_id"]
    assert warm["thread"] != root["thread"]
    assert {"jit_tree_jit", "jit__binom_binned_stats"} <= {
        s["attrs"]["program"] for s in xla if s["parent_id"] == warm["span_id"]}
    # requests of one thread follow each other: their sum is inside the fit
    for thread in {s["thread"] for s in xla}:
        assert sum(s["duration_s"] for s in xla
                   if s["thread"] == thread) <= root["duration_s"]


def test_first_fit_tallies_its_trace_seconds(fresh):
    root, under = fresh["fits"][0]
    tallies = [(s["thread"], s["attrs"]["xla_trace_s"])
               for s in (root, *under) if "xla_trace_s" in s["attrs"]]
    assert tallies and all(v >= 0 for _, v in tallies)
    for thread in {t for t, _ in tallies}:
        assert 0 < sum(v for t, v in tallies if t == thread) \
            <= root["duration_s"]


def test_second_fit_requests_and_traces_nothing(fresh):
    root, under = fresh["fits"][1]
    assert not [s for s in under if s["kind"] == "xla"]
    assert not any(s["attrs"].get("xla_trace_s") for s in (root, *under))
    assert not [s for s in under if s["name"] == "design.warm"]


def test_no_count_event_is_left_on_a_span(fresh):
    """The spans carry the signature and a length; the zero-duration
    `xla_compiles` / `xla_cache_retrievals` annotations are gone."""
    names = {ev["name"] for s in fresh["spans"] for ev in s["events"]}
    assert not names & {"xla_compiles", "xla_cache_retrievals", "xla_traces"}


def test_the_package_records_its_own_import(fresh):
    first = min(fresh["spans"], key=lambda s: s["ts"])
    assert first["name"] == "program.import" and first["kind"] == "program"
    assert first["parent_id"] is None and first["duration_s"] > 0


@pytest.mark.parametrize("fit", [0, 1])
def test_binomial_tree_fit_metrics_is_tiled_by_stages(fresh, fit):
    root, under = fresh["fits"][fit]
    (metrics,) = [s for s in under if s["name"] == "fit.metrics"]
    kids = sorted((s for s in under if s["parent_id"] == metrics["span_id"]
                   and s["kind"] == "fit"), key=lambda s: s["ts"])
    assert [s["name"] for s in kids] == ["metrics.binned", "metrics.margins",
                                         "metrics.make"]
    attrs = kids[0]["attrs"]
    assert attrs == {"device": True, "counts": "edges",
                     "block_rows": attrs["block_rows"]}
    assert 0 < attrs["block_rows"] <= shared_tree._EDGE_COUNT_ROWS
    bare = metrics["duration_s"] - sum(s["duration_s"] for s in kids)
    assert 0 <= bare < max(1e-3, 0.01 * metrics["duration_s"])


def test_validation_metrics_have_their_stage(cloud1):
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    rng = np.random.default_rng(7)
    X = rng.normal(size=(400, 3))
    y = (X[:, 0] + rng.normal(size=400) > 0).astype(int)
    fr = Frame.from_dict({"a": X[:, 0], "b": X[:, 1], "c": X[:, 2],
                          "y": np.asarray(["n", "p"], dtype=object)[y]},
                         column_types={"y": "enum"})
    tracing.clear()
    H2OGradientBoostingEstimator(ntrees=2, max_depth=2, seed=1).train(
        y="y", training_frame=fr, validation_frame=fr)
    spans = tracing.spans()
    (metrics,) = [s for s in spans if s["name"] == "fit.metrics"]
    kids = sorted((s for s in spans if s["parent_id"] == metrics["span_id"]
                   and s["kind"] == "fit"), key=lambda s: s["ts"])
    assert [s["name"] for s in kids][-1] == "metrics.valid"


# -- tally, and the rollups that use it ---------------------------------------

def test_tally_adds_to_the_open_span_and_is_a_noop_without_one():
    assert tracing.current() is None
    tracing.tally("anything", 3)                 # no span open: nothing
    with tracing.span("outer", seen=2) as outer:
        with tracing.span("inner") as inner:
            tracing.tally("seen")                # created at 0, then + 1
            tracing.tally("secs", 0.25)
            tracing.tally("secs", 0.5)
        tracing.tally("seen", 5)                 # the innermost OPEN span
    assert inner.attrs == {"seen": 1, "secs": 0.75}
    assert outer.attrs == {"seen": 7}


def test_train_resolve_still_tallies_its_rollups(cloud1):
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    rng = np.random.default_rng(29)
    X = rng.normal(size=(300, 28))
    y = (X[:, 0] > 0).astype(int)
    fr = Frame.from_dict({**{f"f{i}": X[:, i] for i in range(28)},
                          "y": np.asarray(["n", "p"], dtype=object)[y]},
                         column_types={"y": "enum"})
    seen = []
    for _ in range(2):
        tracing.clear()
        H2OGradientBoostingEstimator(ntrees=1, max_depth=2, seed=1).train(
            y="y", training_frame=fr)
        (r,) = [s for s in tracing.spans() if s["name"] == "train.resolve"]
        seen.append((r["attrs"]["rollups_computed"],
                     r["attrs"]["rollups_reused"]))
    assert seen == [(29, 0), (0, 29)]


# -- the ring counts what it evicts ---------------------------------------------

def test_ring_counts_the_spans_it_drops(monkeypatch):
    monkeypatch.setattr(tracing, "_SPANS", deque(maxlen=4))
    tracing.clear()
    tracing.record_span("warm", 0.0)             # the families exist now
    total = registry.get("h2o3_trace_spans_dropped").total
    at = total()
    for i in range(3):
        tracing.record_span("fill", 0.0)
        assert tracing.dropped() == 0 and tracing.span_count() == i + 2
    for i in range(5):
        with tracing.span("over"):
            pass
        assert tracing.dropped() == i + 1 and tracing.span_count() == 4
    assert total() == at + 5
    tracing.clear()                              # a new ring: nothing lost
    assert tracing.dropped() == 0 and tracing.span_count() == 0
    assert total() == at + 5                     # the counter is cumulative


# -- a compile request is one span: compile, or cache load ----------------------

@pytest.fixture
def every_program_cached():
    """The persistent cache keeps even a millisecond's compile, so that a
    program requested a second time is a cache load."""
    import jax

    was = (jax.config.jax_persistent_cache_min_compile_time_secs,
           jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[0])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", was[1])


def test_a_request_is_a_compile_or_a_cache_load_never_both(
        cloud1, every_program_cached):
    import jax
    import jax.numpy as jnp

    phases.install_listener()
    # a program no earlier run has cached: a float32 constant of its own
    salt = 1.0 + random.randrange(1 << 20) / (1 << 20)

    def program():
        """The same program from a new function object: jax traces and
        requests it again, and nothing else in the process is disturbed
        (`jax.clear_caches()` would make every later test retrace)."""
        def first_fit_span_probe(x):
            return jnp.tanh(x * salt) @ x.T

        return jax.jit(first_fit_span_probe)

    x = jnp.ones((16, 16), jnp.float32)
    f = program()
    before = phases.xla_counts()
    with tracing.span("first_call") as first:
        f(x).block_until_ready()
    with tracing.span("again") as again:
        program()(x).block_until_ready()
    with tracing.span("warm") as warm:
        f(x).block_until_ready()
    spans = tracing.spans()

    def requests(parent):
        return [s for s in spans if s["parent_id"] == parent.span_id
                and s["attrs"].get("program") == "jit_first_fit_span_probe"]

    (compiled,) = requests(first)
    (loaded,) = requests(again)
    assert compiled["name"] == "xla.compile" and compiled["kind"] == "xla"
    assert loaded["name"] == "xla.cache_load" and loaded["kind"] == "xla"
    assert compiled["attrs"]["sig"] == loaded["attrs"]["sig"]
    assert compiled["attrs"]["sig"].startswith("jit_first_fit_span_probe-")
    assert compiled["duration_s"] > 0 and loaded["duration_s"] > 0
    assert compiled["trace_id"] == first.trace_id
    assert not requests(warm) and "xla_trace_s" not in warm.attrs
    assert first.attrs["xla_trace_s"] > 0 and again.attrs["xla_trace_s"] > 0
    # the counters keep their meaning: `compiles` counts requests, hits too
    after = phases.xla_counts()
    assert after["compiles"] - before["compiles"] == 2
    assert after["cache_retrievals"] - before["cache_retrievals"] == 1
    # the same program traced again is a retrace, and says so on its span;
    # no count event beside it
    assert [ev["name"] for ev in again.events] == ["xla_retrace"]
    assert not first.events and not warm.events


def test_trace_seconds_of_nested_jits_are_counted_once(cloud1):
    import jax
    import jax.numpy as jnp

    phases.install_listener()

    @jax.jit
    def inner(x):
        for _ in range(20):
            x = jnp.sin(x) @ x.T
        return x

    @jax.jit
    def outer(x):
        for _ in range(4):
            x = inner(x + 1.0) + jnp.cos(x)
        return x

    bucket0 = phases.totals(("trace",))
    with tracing.span("nested") as sp:
        t0 = time.time()
        jax.make_jaxpr(outer)(jnp.ones((8, 8), jnp.float32))
        wall = time.time() - t0
    assert 0 < sp.attrs["xla_trace_s"] <= wall
    # the bucket adds whole durations, inner traces inside outer ones
    assert phases.totals(("trace",)) - bucket0 >= sp.attrs["xla_trace_s"]
