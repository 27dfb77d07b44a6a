"""The three readers the four-chip GLM cell brought, on a hand-built reduced
trace of two chips: the arithmetic (an uneven all-gather, one chip's rows),
and None where a trace holds nothing for them — one chip with no collective,
a program from before the spans."""

import pytest

import manifest
from reduce_trace import Trace

ALGO = manifest.load_module("algos", "glm_mesh")
# ops bound on a v5e: 2 n p^2 + 4 n p = 1.97e12 + ... at n = 985,000 a chip
SHAPES = {"rows": 1_970_000, "coefficients": 1000, "steps_per_fit": 2}
T = "python"


def built(collectives=True, spans=True):
    # one fit of two IRLS iterations: the fused program ran 0.5 s on each
    # chip; chip 0 arrived early and waited 30 + 50 ms in its all-gathers,
    # chip 1 arrived last: 4 + 6 ms. An all-gather of another program (the
    # scorer's, 0.2 s) is outside the fused program's event and not counted.
    def chip(waits, other):
        ops = [("%fusion.7", 2.0, 0.2), ("%fusion.9", 2.25, 0.2)]
        if collectives:
            ops += [("%all-gather-start.1", 2.2, 0.001),
                    ("%all-gather-done.1", 2.201, waits[0] - 0.001),
                    ("%all-gather.7", 2.45, waits[1]),
                    ("%all-gather.2", 3.0, other)]
        return {"ops": ops, "modules": [("jit_inner(11)", 2.0, 0.5),
                                        ("jit_score(4)", 3.0, 0.3),
                                        ("jit_expand(2)", 1.5, 0.1)]}

    host = [(T, "bench.window", 0.0, 6.0)]
    if spans:
        host += [(T, "train", 1.0, 4.0), (T, "train.fit", 1.0, 4.0),
                 (T, "fit.design", 1.0, 0.9), (T, "design.upload", 1.5, 0.3),
                 # a frame scored outside train(): not a fit's upload
                 (T, "design.upload", 5.2, 0.2)]
    return Trace([chip((0.030, 0.050), 0.2), chip((0.004, 0.006), 0.2)], host)


def ctx(trace, **over):
    out = {"trace": trace, "algo": ALGO, "cfg": {"algo": "glm_mesh"},
           "shapes": SHAPES, "device_kind": "TPU v5 lite", "chips": 2,
           "steps": 2, "fits": 1, "counters": {}}
    out.update(over)
    return out


def read(name, c):
    got = manifest.load_module("metrics", name).read(c)
    return got[0] if isinstance(got, tuple) else got


def test_fold_is_the_longest_chips_collective_time_an_iteration():
    # chip 0: 30 + 50 ms inside the fused program, over 2 iterations
    assert read("irls_fold_ms", ctx(built())) == pytest.approx(40.0)
    swapped = built()
    swapped.devices.reverse()
    assert read("irls_fold_ms", ctx(swapped)) == pytest.approx(40.0)


def test_shard_roofline_counts_one_chips_rows_against_its_time():
    n, p = SHAPES["rows"] / 2, SHAPES["coefficients"]
    least = (2.0 * n * p * p + 4.0 * n * p) / 197e12      # ops bound
    want = 100.0 * least * 2 / 0.5
    assert read("irls_shard_roofline", ctx(built())) == pytest.approx(want)
    # all rows against one chip's time, as `irls_roofline` reads, is twice it
    whole = read("irls_roofline", ctx(built()))
    assert whole == pytest.approx(2 * want)
    assert 0.0 < want < 100.0


def test_upload_is_the_fits_own_span():
    assert read("design_upload_ms", ctx(built())) == pytest.approx(300.0)


@pytest.mark.parametrize("name,empty", [
    ("irls_fold_ms", lambda: built(collectives=False)),       # one chip's trace
    ("irls_fold_ms", lambda: Trace([], [(T, "bench.window", 0.0, 6.0)])),
    ("irls_shard_roofline", lambda: Trace([], [(T, "bench.window", 0.0, 6.0)])),
    ("design_upload_ms", lambda: built(spans=False)),
])
def test_nothing_to_read_is_none(name, empty):
    assert read(name, ctx(empty())) is None


@pytest.mark.parametrize("name", ["irls_fold_ms", "irls_shard_roofline"])
def test_no_iteration_in_the_window_is_none(name):
    assert read(name, ctx(built(), steps=0)) is None


def test_the_new_metrics_are_the_new_cells():
    man = manifest.load_manifest()
    for name, layer in (("irls_shard_roofline", "estimator engine"),
                        ("irls_fold_ms", "sharding"),
                        ("design_upload_ms", "design build")):
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert "glm_airlines_fit_x4" in entry["workloads"]
        assert entry["layer"] == layer and entry["moves"] == "fit_wall_s"
    roof = next(m for m in man["per_layer"] if m["name"] == "irls_roofline")
    assert "glm_airlines_fit_x4" not in roof["workloads"]
