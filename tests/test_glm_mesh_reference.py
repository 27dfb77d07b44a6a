"""The mesh GLM fit against the benchmark's plain reference, on the CPU's
eight virtual devices: `benchmark/references/glm_reference.py` imports
nothing of h2o3_tpu and knows nothing of chips, so the same data give the
same answer on any layout. The fit runs through the cell's own adapter
(`benchmark/algos/glm_mesh.py`, which refuses the dense host design) at the
benchmark's test-only copy of the `glm_airlines_x4` configuration, with rows
that are NOT a multiple of the block grid (zero-weight padding), on meshes of
2, 4 and 8 devices, and must also equal the one-device forced-shard lane
(`H2O3_EST_SHARD=1`) bit for bit."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from h2o3_tpu.models import dataset_cache, estimator_engine as est
from h2o3_tpu.models import glm
from h2o3_tpu.parallel import mesh

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SEED = 2 ** 31 + 33
ROWS = 8003                    # 8,008 on the 8-block grid: 5 pad rows


@pytest.fixture(scope="module")
def bench():
    added = [p for p in (BENCH,) if p not in sys.path]
    sys.path[:0] = added
    import manifest

    cfg = dict(manifest.load_json(os.path.join(
        BENCH, "tests", "configs", "glm_airlines_x4.json")), rows=ROWS)
    algo = manifest.load_module("algos", cfg["algo"])
    ref = manifest.load_module("references", algo.REFERENCE)
    data = algo.make_data(cfg, SEED)
    yield cfg, algo, ref, data, ref.prepare(cfg, data)
    for p in added:
        sys.path.remove(p)


def _fit(bench, ndev, forced_shard=False):
    cfg, algo, _, data, _ = bench
    mesh.reset()
    mesh.init(jax.devices()[:ndev])
    dataset_cache.clear()
    if forced_shard:
        os.environ["H2O3_EST_SHARD"] = "1"
    try:
        model = algo.make_estimator(cfg, {})
        algo.train(model, algo.make_frame(algo.make_columns(data)))
    finally:
        os.environ.pop("H2O3_EST_SHARD", None)
    return model, algo.result(cfg, model, {}), algo.shapes(cfg, model)


@pytest.fixture(scope="module")
def blocks_lane(bench):
    model, result, shapes = _fit(bench, 1, forced_shard=True)
    assert shapes["local_blocks"] == 8 and shapes["fold_bytes"] == 0
    return np.asarray(model.model.beta), result


@pytest.mark.parametrize("ndev", [2, 4, 8])
def test_mesh_fit_agrees_with_the_plain_reference(bench, ndev):
    cfg, _, ref, _, prep = bench
    _, result, shapes = _fit(bench, ndev)
    assert shapes["n_devices"] == ndev and shapes["rows_per_device"] == 8008 // ndev
    numbers = ref.compare(cfg, prep, result)
    for name, limit in cfg["limits"].items():
        assert np.isfinite(numbers[name]) and numbers[name] <= limit, numbers


@pytest.mark.parametrize("ndev", [2, 4, 8])
def test_mesh_fit_equals_the_one_device_blocks_lane_bitwise(bench, blocks_lane,
                                                            ndev):
    model, result, _ = _fit(bench, ndev)
    beta1, result1 = blocks_lane
    assert np.array_equal(np.asarray(model.model.beta), beta1)
    assert result["coef"] == result1["coef"]
    assert result["iterations"] == result1["iterations"]


def test_the_comparison_is_not_blind_on_a_mesh(bench):
    """The reference's own control (bfloat16 matmul inputs) in the program's
    place fails the limits the mesh fit passed."""
    cfg, _, ref, _, prep = bench
    wrong = ref.compare(cfg, prep, ref.control(cfg, prep, cfg["estimator"]))
    assert any(wrong[k] > lim for k, lim in cfg["limits"].items()), wrong


def test_the_chips_partials_fold_to_the_programs_gram(bench):
    """The share tied to the whole: each of four chips' (2, P, P+1) block
    partials, folded left to right in global block order on the host, are the
    Gram the fused program folds after its all-gather, bit for bit, and the
    float64 Gram of the whole design to float32's rounding."""
    cfg, algo, _, data, _ = bench
    cloud = mesh.init(jax.devices()[:4])
    dataset_cache.clear()
    frame = algo.make_frame(algo.make_columns(data))
    x = [c for c in frame.names if c != algo.RESPONSE]
    _, Xd = est.design_matrix(frame, x, standardize=True, add_intercept=True,
                              n_shards=8, n_devices=4)
    n, p = Xd.shape
    rng = np.random.default_rng(3)
    rs = cloud.row_sharding()
    ww_h = np.concatenate([rng.random(ROWS), np.zeros(n - ROWS)]
                          ).astype(np.float32)
    z_h = rng.normal(size=n).astype(np.float32)
    ww, z = jax.device_put(ww_h, rs), jax.device_put(z_h, rs)
    rows = P(mesh.ROWS_AXIS)

    def shares(X, w, zz):
        return glm._block_partials(X, w, zz, 2)

    def whole(X, w, zz):
        return est.fold_blocks(glm._block_partials(X, w, zz, 2),
                               mesh.ROWS_AXIS)

    parts = np.asarray(jax.jit(mesh.shard_call(
        shares, cloud, in_specs=(rows,) * 3, out_specs=rows))(Xd, ww, z))
    gram = np.asarray(jax.jit(mesh.shard_call(
        whole, cloud, in_specs=(rows,) * 3, out_specs=P(),
        check_vma=False))(Xd, ww, z))
    assert parts.shape == (8, p, p + 1) and parts.dtype == np.float32
    folded = parts[0]
    for part in parts[1:]:
        folded = folded + part
    assert np.array_equal(folded, gram)
    X64 = np.asarray(Xd, np.float64)
    exact = (X64 * ww_h[:, None].astype(np.float64)).T @ np.concatenate(
        [X64, z_h[:, None].astype(np.float64)], axis=1)
    assert np.allclose(gram, exact, rtol=2e-5, atol=2e-3)
    # every chip holds its own two blocks: rows 2k and 2k+1 of the stack
    half = n // 8
    for k in (0, 5):
        s = slice(k * half, (k + 1) * half)
        own = (X64[s] * ww_h[s, None]).T @ np.concatenate(
            [X64[s], z_h[s, None].astype(np.float64)], axis=1)
        assert np.allclose(parts[k], own, rtol=2e-5, atol=2e-3)
