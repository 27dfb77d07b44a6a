"""The seven readers of the program's fit spans on a hand-built trace (host
events only, one device program): the arithmetic, and None where the span
or the counter is missing."""

import types

import pytest

import manifest
from reduce_trace import Trace

T = "python"
# two fits in a window of 10 s. Fit 1: train [1, 5), fit 2: train [5.5, 9.5);
# children per fit (offsets from the train's start): resolve 0.0-0.2,
# train.fit 0.2-3.8 {response 0.2-0.3, design 0.3-1.3, init 1.3-1.4,
# iterate 1.4-2.4, metrics 2.4-3.7 {metrics.d2h 2.4-2.5}}, publish 3.8-3.9.
FIT = [("train", 0.0, 4.0), ("train.resolve", 0.0, 0.2),
       ("train.fit", 0.2, 3.6), ("fit.response", 0.2, 0.1),
       ("fit.design", 0.3, 1.0), ("fit.init", 1.3, 0.1),
       ("fit.iterate", 1.4, 1.0), ("fit.metrics", 2.4, 1.3),
       ("metrics.d2h", 2.4, 0.1), ("train.publish", 3.8, 0.1)]


def built(drop=()):
    host = [(T, "bench.window", 0.0, 10.0),
            # not the program's: inside train.fit, never a child of it
            (T, "PjitFunction(inner)", 2.45, 0.5),
            # a train() that began before the window: not a whole fit of it
            (T, "train", -1.0, 1.5)]
    for at in (1.0, 5.5):
        host += [(T, n, at + s, d) for n, s, d in FIT if n not in drop]
    # the step program ran 0.4 s inside each fit.iterate, and once outside
    modules = [("jit_inner(1)", 2.5, 0.4), ("jit_inner(1)", 7.0, 0.4),
               ("jit_inner(1)", 9.8, 0.1), ("jit_expand(2)", 1.5, 0.1)]
    return Trace([{"ops": [], "modules": modules}], host)


def ctx(trace, **over):
    algo = types.SimpleNamespace(TRACE_STEP_PROGRAM=r"^jit_inner\(")
    out = {"trace": trace, "algo": algo, "fits": 2,
           "counters": {"phases": {"bytes_h2d": 50_000_000, "h2d_s": 0.0}}}
    out.update(over)
    return out


WANT = {"train_entry_ms": 400.0,          # 4.0 - 3.6
        "fit_design_ms": 1000.0,
        "fit_iterate_wait_ms": 600.0,     # 1.0 - 0.4 of device time
        "fit_metrics_ms": 1300.0,
        "outside_train_ms": 1000.0,       # (10 - 2 x 4) / 2
        # train: 4.0 - (0.2 + 3.6 + 0.1) = 0.1; train.fit: 3.6 - 3.5 = 0.1
        "train_unnamed_pct": 100.0 * 0.2 / 4.0,
        "h2d_mb_per_fit": 25.0}
MISSING = {"train_entry_ms": "train.fit", "fit_design_ms": "fit.design",
           "fit_iterate_wait_ms": "fit.iterate",
           "fit_metrics_ms": "fit.metrics", "outside_train_ms": "train",
           "train_unnamed_pct": "train.fit"}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic_and_absence(name):
    read = manifest.load_module("metrics", name).read
    assert read(ctx(built())) == pytest.approx(WANT[name])
    if name in MISSING:
        assert read(ctx(built(drop=(MISSING[name],)))) is None
        # a program from before the spans: nothing but the window event
        bare = Trace([], [(T, "bench.window", 0.0, 10.0)])
        assert read(ctx(bare)) is None
    else:
        assert read(ctx(built(), counters={"phases": {}})) is None
    entry = [m for m in manifest.load_manifest()["per_layer"]
             if m["name"] == name]
    assert entry and entry[0]["workloads"] and entry[0]["moves"] == "fit_wall_s"
