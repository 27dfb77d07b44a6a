"""Ask the chip's compiler, without the chip: ahead-of-time compiles of the
main path's kernels and programs for a described (not attached) `v5e:2x2`,
at the flagship's real widths — plus an interpret-mode numeric check of both
Pallas kernels on the CPU. A compile that passes is not a chip run; what it
guards is what interpret mode cannot see (tiling, VMEM, HBM).

The topology is described inside a module-scoped fixture (only one process
may hold the TPU library; never at import, in a skipif or in parametrize),
and all of these live in ONE file so one worker owns them. The same fixture
asks the library, before it loads, to dump what Mosaic makes of a kernel
(`--xla_mosaic_dump_to`): `test_onehot_step_has_no_strided_store` counts the
instructions of one grid step in it."""

import collections
import functools
import glob
import os
import re
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from h2o3_tpu.ops import hist_pallas, histogram

N, F = 1_000_000, 28
NBINS = 21          # the flagship's resolved nbins (GBM nbins=20, +1 NA bin)


@pytest.fixture(scope="module")
def mosaic_dump(tmp_path_factory):
    """The directory the TPU library writes every Mosaic kernel's passes to
    (15 files, ~10 MB a compile), gone with the module."""
    path = tmp_path_factory.mktemp("mosaic")
    yield str(path)
    shutil.rmtree(path, ignore_errors=True)


@pytest.fixture(scope="module")
def topo(mosaic_dump):
    from jax.experimental import topologies

    # read once, when the library loads: nothing of this file has loaded it
    # before this call, and no other file does
    was = os.environ.get("LIBTPU_INIT_ARGS")
    os.environ["LIBTPU_INIT_ARGS"] = (
        f"{was or ''} --xla_mosaic_dump_to={mosaic_dump}").strip()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if was is None:
            del os.environ["LIBTPU_INIT_ARGS"]
        else:
            os.environ["LIBTPU_INIT_ARGS"] = was


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


@pytest.fixture(autouse=True)
def _drop_dumps(request):
    """Keep the dump directory to one test's compiles (the tree program's
    are ~100 MB). Touches no topology: a test that describes none has no
    directory either."""
    yield
    if "mosaic_dump" in request.fixturenames:
        for path in glob.glob(request.getfixturevalue("mosaic_dump") + "/*"):
            os.remove(path)


def _kernel_args(sharding):
    return (_sds((F, N), jnp.float32, sharding),
            _sds((N,), jnp.int32, sharding),
            _sds((3, N), jnp.float32, sharding))


# 4- and 6-bit codes (16, 33), the flagship (21), XGBoost's 64 and 256, and
# `nbins_cats`' 1,024: the bin axis as the kernel pads it is 16, 24, 40, 64,
# 256, 1,024
@pytest.mark.parametrize("nbins", [16, NBINS, 33, 64, 256, 1024])
@pytest.mark.parametrize("n_nodes", [1, 32, 64])
def test_factored_kernel_compiles_for_v5e(one_chip, n_nodes, nbins):
    rc = histogram.resolve_method(n_nodes, nbins, "pallas_factored")
    assert rc["fallback"] is None and rc["row_chunk"] >= 512
    compiled = hist_pallas.build_histograms_pallas_factored.lower(
        *_kernel_args(one_chip),
        n_nodes=n_nodes, nbins=nbins, row_chunk=rc["row_chunk"]).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert hist_pallas.bins_padded(nbins) % 8 == 0
    assert 0 <= hist_pallas.bins_padded(nbins) - nbins < 8


def test_onehot_step_has_no_strided_store(one_chip, mosaic_dump):
    """What Mosaic makes of ONE grid step at the flagship's shape (B 21,
    L 16, R 8,192), read from its final LLO: the (8·Bp, R) one-hot costs one
    compare and one select a vreg and half a pack, and nothing else. With
    the bin axis unpadded (`repeat(codes, 21) == iota % 21`, through PR 35)
    the same step held 1,344 sublane-strided stores, 1,008 sublane rotates,
    1,844 loads, 5,787 selects and 27 remainders; an `astype` of the compare
    instead of the `where` costs a convert a vreg more."""
    L, R = 16, 8192
    Bp = hist_pallas.bins_padded(NBINS)
    assert Bp == 24
    hist_pallas.build_histograms_pallas_factored.lower(
        *_kernel_args(one_chip), n_nodes=L, nbins=NBINS,
        row_chunk=R).compile()
    dumps = glob.glob(f"{mosaic_dump}/*tree_hist_factored*post-finalize-llo*")
    if not dumps:
        pytest.skip("this TPU library wrote no Mosaic dump")
    assert len(dumps) == 1
    with open(dumps[0]) as fh:
        ops = collections.Counter(re.findall(r"\bllo\.([\w.]+)", fh.read()))
    vregs = 8 * Bp * R // 1024                    # float32 one-hot vregs
    assert ops["vcmp.eq.f32"] == vregs == 1536
    assert ops["vector_store_slane_stride"] == 0
    assert ops["vrot.slane"] == 0
    # the only remainder left is the weighted scratch's `% L`, a sublane
    # tile of its (3L, 1) column each, under `fb == 0`
    assert ops["vrem.s32"] <= -(-3 * L // 8)
    # one select a one-hot vreg, and the scratch's node mask (3L·R/1,024)
    assert ops["vselect"] <= vregs + 3 * L * R // 1024 + 16
    assert ops["vcvt.s32.f32"] < vregs // 2
    assert ops["vunpack"] == 0
    # the cast packs whole tiles: two float32 vregs into one bfloat16
    assert ops["vpack"] == (vregs + 3 * L * R // 1024) // 2
    assert ops["vector_load"] < 600


def test_factored_row_chunk_is_this_chips_limit(one_chip):
    """`_factored_row_chunk` against the stated VMEM_LIMIT_BYTES: where the
    scratch term binds, the chunk it picks compiles and the next one up is
    refused by the v5e compiler — the bound is the chip's, not assumed."""
    L = 128
    rc = histogram._factored_row_chunk(L, NBINS)
    assert 512 <= rc < 8192
    args = _kernel_args(one_chip)
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


def _flagship_step_cfg(npad, n_features=F, nbins=NBINS):
    from h2o3_tpu.models import shared_tree
    from h2o3_tpu.ops import packing

    cfg = shared_tree._StepCfg(
        npad=npad, K=1, F=n_features, nbins=nbins, problem="binomial",
        dist="bernoulli", mode="gbm", max_depth=6, has_mtries=False,
        no_row_sampling=True, has_col_sampling=False, has_monotone=False,
        tweedie_power=1.5, quantile_alpha=0.5, hist_method="pallas_factored",
        pack_bits=packing.pack_bits_for(nbins, npad))
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


def test_wide_tree_step_selects_over_the_operand_for_v5e(one_chip):
    """The per-tree program of a custom objective's round
    (`single_tree_jit`) at the ranking cell's shape: 2,359,296 padded rows
    of 136 full-width features, 256 bins. Its code argument is the pair of
    the resident uint8 codes and the fit's `(136, N)` float32 operand, and
    the partition reads a row's split-feature code by a select over the
    operand at this width too (`tree.partition_read`): no gather is left
    over the rows (at the parent, six `u8[N] gather`s of the row-major
    codes, one a level), the codes are not a parameter of the executable,
    and the temporaries are 92,767,744 bytes where the gathers' program
    reserved 106,296,832."""
    from h2o3_tpu.models import shared_tree
    from h2o3_tpu.models import tree as treelib
    from h2o3_tpu.parallel import mesh as cloudlib

    npad, f, nbins = 2_359_296, 136, 256
    cfg = _flagship_step_cfg(npad, f, nbins)
    assert cfg.pack_bits == 0 and cfg.code_operand == "fit"
    assert treelib.partition_read(f, cfg.code_operand) == "select"
    _, single_jit = shared_tree._build_tree_step_fns(cfg, cloudlib.cloud())
    f32, s = jnp.float32, one_chip
    codes = (_sds((npad, f), jnp.uint8, s),
             _sds(shared_tree._operand_shape(cfg), f32, s))
    assert codes[1].shape == (f, npad)
    col = _sds((npad,), f32, s)
    compiled = single_jit.lower(
        _sds((npad, 1), f32, s), codes, _sds((npad, 1), f32, s), col, col,
        _sds((f, nbins - 2), f32, s), _sds((f,), f32, s), _sds((9,), f32, s),
        _sds((2,), jnp.uint32, s), _sds((), jnp.int32, s), col,
        col).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 6
    gathered = [int(n) for n in re.findall(r"= \w+\[(\d+)\][^=]* gather\(",
                                           text)]
    assert npad not in gathered, gathered
    assert "tree.partition/gather" not in text
    # the resident uint8 codes are read by nothing, so they are no
    # parameter of the executable
    params = re.findall(r"= (\w+\[[\d,]*\])[^=]* parameter\(", text)
    assert f"u8[{npad},{f}]" not in params, params
    assert compiled.memory_analysis().temp_size_in_bytes < (128 << 20)


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


def test_binned_metrics_program_compiles_for_v5e(one_chip):
    """The tree fit's device training metrics at the flagship's 11,534,336
    padded rows: the per-bin counts come from per-edge counts over
    8,192-row blocks, so the program holds no scatter, no gather over the
    rows (only the quantiles' two 400-element reads), no `while` of
    ⌈log2 401⌉ = 9 trips (the per-row binary search: nine gathers of every
    row from the 400-entry edge table, 1.25 s of every HIGGS fit on a v5e),
    just the block loop, and no `(400, rows)` indicator. Temporaries:
    92,629,504 bytes, where the binary search and its scatter-adds
    reserved 334,915,072."""
    from h2o3_tpu.models import shared_tree

    npad = 11_534_336
    col = _sds((npad, 1), jnp.float32, one_chip)
    compiled = shared_tree._binom_binned_stats.lower(
        col, col, _sds((), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert "scatter" not in text and "searchsorted" not in text
    gathered = re.findall(r"= \w+\[([\d,]*)\][^=]* gather\(", text)
    assert str(npad) not in ",".join(gathered), gathered
    trips = []
    for cond in re.findall(r" while\([^)]*\), condition=(%[\w.]+)", text):
        body = re.search(re.escape(cond) + r" \(.*?\n\}", text, re.S)
        trips += [int(k) for k in
                  re.findall(r"constant\((\d+)\)", body.group(0))]
    assert trips == [npad // shared_tree._EDGE_COUNT_ROWS], trips
    assert compiled.memory_analysis().temp_size_in_bytes < (128 << 20)


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


def _emulated(codes, node, vals, n_nodes, nbins, row_chunk):
    """The kernel's arithmetic in plain jnp: the node-weighted values cast
    to bfloat16, an exact 0/1 bin one-hot, one float32-accumulating matmul a
    feature block and a row chunk, the chunks added in row order. At the
    kernel's own matmul shapes (rows padded to the chunk, features to eight,
    bins to `bins_padded`), because the CPU's dot sums in an order that
    depends on them (one ulp in one of 528 sums otherwise)."""
    n, f = codes.shape
    bp = hist_pallas.bins_padded(nbins)
    fpad, npad = -(-f // 8) * 8, -(-n // row_chunk) * row_chunk
    codes_t = jnp.pad(codes.T.astype(jnp.float32),
                      ((0, fpad - f), (0, npad - n)), constant_values=-1.0)
    node = jnp.pad(node, (0, npad - n))
    vals = jnp.pad(vals, ((0, 0), (0, npad - n)))
    l_of = np.arange(3 * n_nodes) % n_nodes
    c_of = np.arange(3 * n_nodes) // n_nodes
    w = (vals[c_of] * (node[None, :] == l_of[:, None])).astype(jnp.bfloat16)
    bins = jnp.arange(bp, dtype=jnp.float32)
    oh = (codes_t[:, None, :] == bins[None, :, None]).astype(
        jnp.bfloat16).reshape(fpad // 8, 8 * bp, npad)
    acc = jnp.zeros((fpad // 8, 3 * n_nodes, 8 * bp), jnp.float32)
    for r0 in range(0, npad, row_chunk):
        rows = slice(r0, r0 + row_chunk)
        acc = acc + jnp.stack([
            jax.lax.dot_general(w[:, rows], blk[:, rows],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for blk in oh])
    acc = acc.reshape(fpad // 8, 3 * n_nodes, 8, bp)[..., :nbins]
    acc = acc.transpose(0, 2, 1, 3).reshape(fpad, 3 * n_nodes, nbins)[:f]
    return acc.reshape(f, 3, n_nodes, nbins).transpose(2, 0, 3, 1)


# "features": two feature blocks, the second with five pad features, ONE row
# chunk most of which is pad rows, through `build_histograms` at the chunk
# `resolve_method` picks. "chunks": three row chunks, the last part pad
# rows, of one feature block with three pad features, the kernel called at a
# small chunk. (Both at once is the chip's: the interpreter refuses an
# output block that is revisited after another was written.)
@pytest.mark.parametrize("layout,n,f", [("features", 1000, 11),
                                        ("chunks", 1300, 5)])
@pytest.mark.parametrize("n_nodes", [1, 4])
@pytest.mark.parametrize("nbins", [16, NBINS, 33, 256])
def test_factored_kernel_numerics_in_interpret_mode(nbins, n_nodes, layout,
                                                    n, f):
    from jax.experimental.pallas import tpu as pltpu

    def kernel(codes, node, g, h, w, nb=nbins):
        if layout == "features":
            return histogram.build_histograms(
                codes, node, g, h, w, n_nodes, nb, method="pallas_factored")
        return hist_pallas.build_histograms_pallas_factored(
            histogram.feature_major(codes), node, jnp.stack([w, g * w, h * w]),
            n_nodes, nb, row_chunk=512)

    row_chunk = 512 if layout == "chunks" else histogram.resolve_method(
        n_nodes, nbins, "pallas_factored")["row_chunk"]
    assert (n > row_chunk) == (layout == "chunks") and n % row_chunk
    codes, node, g, h, w = (jnp.asarray(a) for a in _hist_inputs(
        n=n, f=f, nbins=nbins, n_nodes=n_nodes, seed=nbins + n_nodes))
    ref = histogram.build_histograms(codes, node, g, h, w, n_nodes, nbins,
                                     method="onehot")
    # values that bfloat16 rounds: the kernel's own order of sums shows
    rng = np.random.default_rng(nbins * 7 + n_nodes)
    g2 = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h2 = jnp.asarray(rng.random(n).astype(np.float32))
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(kernel)(codes, node, g, h, w)
        got2 = jax.jit(kernel)(codes, node, g2, h2, w)
        # the same codes on a wider bin axis: bins no code reaches
        wide = jax.jit(functools.partial(kernel, nb=nbins + 3))(
            codes, node, g2, h2, w)
    # no padded bin, feature or row comes back, and none has added
    # anything: these values' sums are exact in float32
    assert got.shape == ref.shape == (n_nodes, f, nbins, 3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    cnt = np.zeros((n_nodes, nbins))
    np.add.at(cnt, (np.asarray(node), np.asarray(codes)[:, 0]), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(got)[:, 0, :, 0], cnt)
    # bit for bit the stated arithmetic, not only close to it
    emu = _emulated(codes, node, jnp.stack([w, g2 * w, h2 * w]), n_nodes,
                    nbins, row_chunk)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(emu))
    assert not np.array_equal(
        np.asarray(got2),
        np.asarray(histogram.build_histograms(codes, node, g2, h2, w, n_nodes,
                                              nbins, method="segment")))
    # a bin that matches no code is an exact zero column and moves no other
    assert wide.shape == (n_nodes, f, nbins + 3, 3)
    assert not np.asarray(wide)[:, :, nbins:].any()
    np.testing.assert_array_equal(np.asarray(wide)[:, :, :nbins],
                                  np.asarray(got2))
