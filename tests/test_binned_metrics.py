"""The tree fit's device training metrics (`shared_tree._binom_binned_stats`):
its per-bin counts come from per-edge counts over row blocks, and must be the
per-row binary search and scatter-adds they replaced, output for output and
bit for bit, down to the metrics `from_binned` builds from them."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu.models import shared_tree
from h2o3_tpu.models.metrics import ModelMetricsBinomial

BLOCK = shared_tree._EDGE_COUNT_ROWS


@functools.partial(jax.jit, static_argnames=("nbins",))
def _by_search(margins, y_d, n, nbins: int = 400):
    """The reduction as it was: a bin index a row by binary search over the
    edges, then two scatter-adds into the bins."""
    valid = jnp.arange(margins.shape[0]) < n
    p = jax.nn.sigmoid(margins[:, 0])
    y = y_d[:, 0]
    qs = jnp.nanquantile(jnp.where(valid, p, jnp.nan),
                         jnp.linspace(0.0, 1.0, nbins))
    bins = jnp.searchsorted(qs, p, side="left")
    vf = valid.astype(jnp.float32)
    npos = jax.ops.segment_sum(y * vf, bins, num_segments=nbins + 1)
    nneg = jax.ops.segment_sum((1.0 - y) * vf, bins, num_segments=nbins + 1)
    pc = jnp.clip(p, 1e-15, 1 - 1e-15)
    nll = -jnp.sum(jnp.where(valid & (y > 0.5), jnp.log(pc), 0.0)
                   + jnp.where(valid & (y <= 0.5), jnp.log(1.0 - pc), 0.0))
    sq = jnp.sum(jnp.where(valid, (p - y) ** 2, 0.0))
    return qs, npos, nneg, nll, sq


# case: (padded rows, valid rows)
CASES = {
    "edge_ties": (200_003, 199_000),        # rounded margins: rows on edges
    "pad_rows": (3 * BLOCK + 5, 2 * BLOCK),
    "one_valid_row": (64, 1),
    "saturated": (50_000, 50_000),          # p exactly 0 and exactly 1
    "constant": (2 * BLOCK, 2 * BLOCK),     # all 400 edges equal
    "ragged_blocks": (2 * BLOCK + 77, 2 * BLOCK + 77),
    "under_one_block": (7, 7),
    "nan_margin": (BLOCK + 3, BLOCK + 3),
}


def _inputs(case):
    rows, n = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    m = rng.normal(size=rows).astype(np.float32)
    if case == "edge_ties":
        m = np.round(m, 1)
    elif case == "saturated":
        m = m * 200
    elif case == "constant":
        m[:] = 0.3
    elif case == "nan_margin":
        m[5] = np.nan
    y = (rng.random(rows) < 0.45).astype(np.float32)
    return m[:, None], y[:, None], n


@pytest.mark.parametrize("case", sorted(CASES))
def test_edge_counts_equal_the_binary_search_bit_for_bit(case):
    margins, y, n = _inputs(case)
    want = [np.asarray(a) for a in _by_search(margins, y, jnp.int32(n))]
    got = [np.asarray(a) for a in
           shared_tree._binom_binned_stats(margins, y, jnp.int32(n))]
    for name, w, g in zip(("qs", "npos", "nneg", "nll", "sq"), want, got):
        assert w.dtype == g.dtype and w.shape == g.shape, name
        assert np.array_equal(w, g, equal_nan=True), name
    npos, nneg = got[1], got[2]
    assert npos.sum() + nneg.sum() == n
    if case == "saturated":
        p = np.asarray(jax.nn.sigmoid(margins[:n, 0]))
        assert (p == 0).any() and (p == 1).any()
    if case == "nan_margin":
        # searchsorted's order: a NaN score lies above every edge, so its
        # row is the last bin's only one (the top edge is the largest score)
        assert npos[400] + nneg[400] == 1
    mw, mg = (ModelMetricsBinomial.from_binned(*a[:3], float(a[3]),
                                               float(a[4]))
              for a in (want, got))
    assert np.array_equal(mw.auc, mg.auc, equal_nan=True)
    assert np.array_equal(mw.logloss, mg.logloss, equal_nan=True)
