"""Ask the chip's compiler, without the chip: ahead-of-time compiles of the
main path's kernels and programs for a described (not attached) `v5e:2x2`,
at the flagship's real widths — plus an interpret-mode numeric check of both
Pallas kernels on the CPU. A compile that passes is not a chip run; what it
guards is what interpret mode cannot see (tiling, VMEM, HBM).

The topology is described inside a module-scoped fixture (only one process
may hold the TPU library; never at import, in a skipif or in parametrize),
and all of these live in ONE file so one worker owns them."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from h2o3_tpu.ops import hist_pallas, histogram

N, F = 1_000_000, 28
NBINS = 21          # the flagship's resolved nbins (GBM nbins=20, +1 NA bin)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off around the
    module: such a compile is written to the cache but cannot be read back
    without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n_nodes", [1, 32, 64])
def test_factored_kernel_compiles_for_v5e(one_chip, n_nodes):
    rc = histogram.resolve_method(n_nodes, NBINS, "pallas_factored")
    assert rc["fallback"] is None and rc["row_chunk"] >= 512
    compiled = hist_pallas.build_histograms_pallas_factored.lower(
        _sds((F, N), jnp.float32, one_chip), _sds((N,), jnp.int32, one_chip),
        _sds((3, N), jnp.float32, one_chip),
        n_nodes=n_nodes, nbins=NBINS, row_chunk=rc["row_chunk"]).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_factored_row_chunk_is_this_chips_limit(one_chip):
    """`_factored_row_chunk` against the stated VMEM_LIMIT_BYTES: where the
    scratch term binds, the chunk it picks compiles and the next one up is
    refused by the v5e compiler — the bound is the chip's, not assumed."""
    L = 128
    rc = histogram._factored_row_chunk(L, NBINS)
    assert 512 <= rc < 8192
    args = (_sds((F, N), jnp.float32, one_chip),
            _sds((N,), jnp.int32, one_chip),
            _sds((3, N), jnp.float32, one_chip))
    hist_pallas.build_histograms_pallas_factored.lower(
        *args, n_nodes=L, nbins=NBINS, row_chunk=rc).compile()
    with pytest.raises(Exception, match="(?i)vmem"):
        hist_pallas.build_histograms_pallas_factored.lower(
            *args, n_nodes=L, nbins=NBINS, row_chunk=2 * rc).compile()


def test_fused_scorer_page_compiles_for_v5e(one_chip):
    """One page of the fused forest scorer at the flagship's 100 trees
    (padded to 128) × depth 6 fits a v5e; the whole 1M rows in one program
    does not (51 GB of gather transients) — which is why `_margins` pages."""
    from h2o3_tpu.models import shared_tree
    from h2o3_tpu.models import tree as treelib

    nt, depth = 128, 6
    meta, n_rows = treelib.score_round_meta(depth)
    walk = _sds((nt, n_rows, 128), jnp.float32, one_chip)
    value = _sds((nt, 2 ** (depth + 1) - 1), jnp.float32, one_chip)
    page = shared_tree._score_page_rows(nt)
    compiled = treelib.predict_forest_fused.lower(
        walk, value, _sds((page, F), jnp.float32, one_chip),
        max_depth=depth).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < (8 << 30)
    with pytest.raises(Exception, match="(?i)exceed|exhausted|memory"):
        treelib.predict_forest_fused.lower(
            walk, value, _sds((N, F), jnp.float32, one_chip),
            max_depth=depth).compile()


def _flagship_step_cfg(npad):
    from h2o3_tpu.models import shared_tree
    from h2o3_tpu.ops import packing

    cfg = shared_tree._StepCfg(
        npad=npad, K=1, F=F, nbins=NBINS, problem="binomial",
        dist="bernoulli", mode="gbm", max_depth=6, has_mtries=False,
        no_row_sampling=True, has_col_sampling=False, has_monotone=False,
        tweedie_power=1.5, quantile_alpha=0.5, hist_method="pallas_factored",
        pack_bits=packing.pack_bits_for(NBINS, npad))
    # as `_make_step_cfg` resolves it: one device, the kernel's levels
    return cfg._replace(
        code_operand=shared_tree._cfg_operand_form(cfg)["form"])


def _packed_rows(npad, bits):
    from h2o3_tpu.ops import packing

    return npad // packing.GROUP_ROWS[bits] * packing.GROUP_BYTES[bits]


def test_tree_step_compiles_for_v5e(one_chip):
    """The per-tree program the estimator's driver dispatches (gradients →
    6 histogram levels → split search → partition → margin update) at
    1M×28, depth 6, as `_make_step_cfg` resolves it for the flagship: its
    code argument is the pair of the packed codes and the fit's operand,
    and it widens nothing. Temporaries: 11,808,256 bytes, where the program
    that widened for itself (PR 33, and still `code_operand="program"`)
    holds 674,733,568 at this size, 512 MB of it the row-major float32
    (N, 28→128) intermediate."""
    import re

    from h2o3_tpu.models import shared_tree
    from h2o3_tpu.parallel import mesh as cloudlib

    npad = 1 << 20
    cfg = _flagship_step_cfg(npad)
    assert cfg.code_operand == "fit"
    tree_jit, _ = shared_tree._build_tree_step_fns(cfg, cloudlib.cloud())
    f32, s = jnp.float32, one_chip
    codes = (_sds((_packed_rows(npad, cfg.pack_bits), F), jnp.uint8, s),
             _sds(shared_tree._operand_shape(cfg), f32, s))
    assert codes[1].shape == (32, npad)
    compiled = tree_jit.lower(
        _sds((npad, 1), f32, s), _sds((1, 1), f32, s), _sds((1,), f32, s),
        codes, _sds((npad, 1), f32, s),
        _sds((npad,), f32, s), _sds((npad,), f32, s),
        _sds((F, NBINS - 2), f32, s), _sds((F,), f32, s), _sds((9,), f32, s),
        _sds((2,), jnp.uint32, s), _sds((), jnp.int32, s)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 6
    # nothing of the widen is left in the tree program: no packed word (the
    # unused codes of the pair are not even a parameter of the executable),
    # and no gather but the split thresholds' (one edge a node, at most 32)
    assert "u8[" not in text
    gathered = [int(n) for n in re.findall(r"= \w+\[(\d+)\][^=]* gather\(",
                                           text)]
    assert gathered and max(gathered) <= 32, gathered
    assert "tree.partition/gather" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < (64 << 20)


@pytest.mark.parametrize("bits,nbins", [(4, 16), (5, NBINS), (6, 33)])
def test_code_operand_program_compiles_for_v5e(one_chip, bits, nbins):
    """The once-a-fit program that widens the resident codes into the
    histogram kernel's operand (`build_code_operand`) at 1M×28: the row
    groups are read through a reshape, so no gather is left of the strided
    slices; the output is the kernel's padded (32, N) float32; and its
    temporaries are one 262,144-row block's (134 MB at 5 bits; widening
    the whole matrix in one go reserves 671 MB here, 7.4 GB at 11.5M
    rows)."""
    from h2o3_tpu.ops import packing

    npad = 1 << 20
    assert packing.pack_bits_for(nbins, npad) == bits
    compiled = histogram.build_code_operand.lower(
        _sds((_packed_rows(npad, bits), F), jnp.uint8, one_chip),
        pack_bits=bits, row_chunk=8192).compile()
    text = compiled.as_text()
    assert "gather" not in text
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 32 * npad * 4
    assert mem.temp_size_in_bytes < (192 << 20)


# -- interpret-mode numerics on the CPU ---------------------------------------

def _hist_inputs(n=3000, f=11, nbins=NBINS, n_nodes=4, seed=0):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, nbins, (n, f)).astype(np.uint8)
    node = rng.integers(0, n_nodes, n).astype(np.int32)
    # bf16-exact values: the kernels' one-hot matmul rounds the weighted
    # values to bf16, so exact inputs make the comparison a logic check
    g = rng.integers(-8, 9, n).astype(np.float32) / 4
    h = rng.integers(1, 9, n).astype(np.float32) / 8
    w = (rng.random(n) < 0.9).astype(np.float32)
    return codes, node, g, h, w


@pytest.mark.parametrize("method", ["pallas_factored"])
def test_pallas_kernels_match_onehot_in_interpret_mode(method):
    from jax.experimental.pallas import tpu as pltpu

    n_nodes = 4
    codes, node, g, h, w = _hist_inputs(n_nodes=n_nodes)
    args = [jnp.asarray(a) for a in (codes, node, g, h, w)]
    ref = histogram.build_histograms(*args, n_nodes, NBINS, method="onehot")
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(lambda *a: histogram.build_histograms(
            *a, n_nodes, NBINS, method=method))(*args)
    assert got.shape == ref.shape == (n_nodes, codes.shape[1], NBINS, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0, atol=1e-4)
    # and against exact numpy counts: Σw per (node, feature 0, bin)
    cnt = np.zeros((n_nodes, NBINS))
    np.add.at(cnt, (node, codes[:, 0]), w)
    np.testing.assert_allclose(np.asarray(got)[:, 0, :, 0], cnt, atol=1e-4)
