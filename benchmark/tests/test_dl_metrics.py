"""The MLP step's two readers on a hand-built trace: the arithmetic, the
steps counted from the runs the trace holds, and None where there is
nothing to read."""

import types

import numpy as np
import pytest

import manifest
import work_counts
from reduce_trace import Trace

SHAPES = {"layers": [803, 200, 200, 2], "batch": 32, "steps_per_chunk": 1000}
# three runs of the step program, one of them dropped from the trace's
# modules line, and another program that is not the step
MODULES = [("jit_dl_train_chunk(4)", 0.0, 0.030),
           ("jit_dl_train_chunk(4)", 1.0, 0.034),
           ("jit__scorer(5)", 1.1, 0.5)]


def ctx(modules=MODULES, **over):
    out = {"trace": Trace([{"ops": [], "modules": modules}], []),
           "algo": manifest.load_module("algos", "dl"), "shapes": SHAPES,
           "fits": 3, "steps": 3000, "cfg": {"algo": "dl"},
           "device_kind": "TPU v5 lite"}
    out.update(over)
    return out


def read(name, c):
    got = manifest.load_module("metrics", name).read(c)
    return got[0] if isinstance(got, tuple) else got


def test_the_step_time_counts_the_steps_of_the_runs_in_the_trace():
    # 64 ms over two runs of 1,000 steps
    assert read("dl_step_us", ctx()) == pytest.approx(32.0)
    least = work_counts.least_time(work_counts.counts("dl").step(SHAPES),
                                   "TPU v5 lite")["seconds"]
    assert read("dl_step_roofline", ctx()) == pytest.approx(
        100.0 * least * 2000 / 0.064)


@pytest.mark.parametrize("name", ["dl_step_us", "dl_step_roofline"])
def test_nothing_to_read(name):
    assert read(name, ctx(modules=MODULES[2:])) is None
    assert read(name, ctx(shapes=dict(SHAPES, steps_per_chunk=0))) is None
    assert read(name, ctx(algo=types.SimpleNamespace())) is None
    entry, = [m for m in manifest.load_manifest()["per_layer"]
              if m["name"] == name]
    assert "dl_airlines_fit_sweep" in entry["workloads"]
    assert entry["moves"] == "fit_wall_s" and entry["layer"] == "MLP step"


def test_a_step_of_the_cell_is_bound_by_bytes():
    work = work_counts.counts("dl").step(SHAPES)
    # 6 B W - B n0 n1 multiply-adds' operations and 15 a parameter; the
    # batch's rows and 8 float32 passes over the 201,402 parameters
    assert work == {"ops": 2.0 * 32 * (3 * 201000 - 803 * 200)
                    + 15.0 * 201402,
                    "bytes": 4.0 * 32 * 805 + 32.0 * 201402}
    assert work_counts.least_time(work, "TPU v5 lite")["bound"] == "bytes"


def _prep(n=100, batch=32, epochs=1.0):
    """A reference's view of n rows: one factor of three levels, one
    numeric, widths [4, 5, 2], epochs x n samples in steps of the batch."""
    ref = manifest.load_module("references", "dl_reference")
    rng = np.random.default_rng(5)
    data = {"codes": {"f": rng.integers(0, 3, n).astype(np.int32)},
            "numeric": {"x": rng.normal(size=n)},
            "domains": {"f": ["a", "b", "c"]},
            "y": rng.integers(0, 2, n)}
    cfg = {"estimator": {"hidden": [5], "activation": "Rectifier",
                         "mini_batch_size": batch, "epochs": epochs}}
    return ref, ref.prepare(cfg, data)


def test_the_replay_is_held_to_the_configuration():
    ref, prep = _prep()
    init, order = ref.own_replay(prep, {"seed": 3})
    # 4 steps of 32: all 100 rows, then the first 28 again
    assert order.size == 128 and np.array_equal(order[100:], order[:28])
    assert ref.replay_departures(prep, init, order, init) == 0
    half = order[:2 * 32]                      # the fit stopped half way
    assert ref.replay_departures(prep, init, half, init) == 1
    part = np.concatenate([order[:64], order[:64]])   # half the rows, twice
    assert ref.replay_departures(prep, init, part, init) >= 1
    wide = [(np.zeros((4, 6), np.float32), np.zeros(6, np.float32))] + init[1:]
    assert ref.replay_departures(prep, wide, order, init) >= 1
    assert ref.replay_departures(prep, init, order, wide) >= 1
    (W, b), last = init
    assert ref.replay_departures(prep, [(W * 2, b), last], order, init) == 1
    assert ref.replay_departures(prep, [(W, b + 1), last], order, init) == 1


def test_epochs_after_the_first_start_where_the_last_one_ended():
    # 250 samples in 8 steps: the first epoch 4 steps (all 100 rows and 28
    # again), the second ends at sample 200 (step 7), the third at 250
    ref, prep = _prep(epochs=2.5)
    assert prep.steps == 8 and prep.epoch_steps == [0, 4, 7, 8]
    init, order = ref.own_replay(prep, {"seed": 3})
    assert ref.replay_departures(prep, init, order, init) == 0
    again = order.copy()
    again[129] = again[128]                   # a row twice in epoch two
    assert ref.replay_departures(prep, init, again, init) == 1


def test_a_departed_replay_reads_above_every_limit():
    ref, prep = _prep()
    init, order = ref.own_replay(prep, {"seed": 3})
    got = ref.compare({}, prep, {"params": {}, "weights": init,
                                 "replay": lambda: (init, order[:64])})
    assert got["replay_departures"] == 1
    assert all(v == float("inf") for k, v in got.items()
               if k != "replay_departures")
