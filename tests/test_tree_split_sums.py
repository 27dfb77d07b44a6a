"""The split search's right-hand sums at 11M-row magnitudes, on a crafted
histogram: a node with W ~ 1.1e7 and H ~ 2.75e6 whose features' totals agree
only to a few float32 ulps (as an accumulation over 11M rows leaves them) and
one long-tailed feature with tail bins of 5-15 rows, through `_split_sums`
and through every split search that reads it.

On the parent's arithmetic (`GR, HR, WR = G - GL, H - HL, W - WL` on float32
cumsums) these tests FAIL: the tail child's HR comes out as -1.0, HR + lambda
as 0, its gain as inf, and every search splits 11 rows off the node on
(feature 1, bin 17) where (feature 0, bin 9) gains 1,760,000; with weighted
rows a tail child's WR is off by up to a float32 ulp of 7.7e6, several per
cent of the child. The old code is not kept to prove it."""

import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu.models import tree as treelib

B = 21                      # 20 value bins and the NA bin
LAM = 1.0
MIN_ROWS = 5.0
TAIL = np.array([15, 9, 6, 5], np.float64)      # rows in feature 1's bins 16-19


def crafted(weight: float):
    """(1, 3, B, 3) float32 histogram {w, g, h} of one node. Feature 0 carries the signal (the mean
    gradient changes sign after bin 9); feature 1 has the long right tail;
    feature 2's tail bins hold 1-4 rows, never enough for `min_rows`."""
    rows = np.zeros((3, B))
    rows[0, :20] = 550_000
    rows[1, :16] = 687_497
    rows[1, 0] += 13
    rows[1, 16:20] = TAIL
    rows[2, :17] = 647_058
    rows[2, 0] += 4
    rows[2, 16] += 1
    rows[2, 17:20] = [4, 3, 2]
    assert (rows.sum(axis=1) == 11_000_000).all()
    w = rows * weight
    g = np.zeros((3, B))
    g[0, :10], g[0, 10:20] = -0.2 * w[0, :10], 0.2 * w[0, 10:20]
    for f in (1, 2):
        tail = slice(16, 20) if f == 1 else slice(17, 20)
        g[f, tail] = 0.5 * w[f, tail]
        g[f, :16] = -g[f, tail].sum() / 16
    h = 0.25 * w
    # what float32 accumulation over 11M rows leaves: feature 1's bins sum
    # to the node's H plus 15 ulps of 2.75e6 (1.4e-6 of it). With unit
    # weights every h is a multiple of 0.25 below 2^22: all sums are exact
    h[1, 3] += 3.75 * weight
    return np.stack([w, g, h], axis=-1)[None].astype(np.float32)


def oracle(hist32, min_rows):
    """Float64 numpy by the definition: left of a split at bin b are bins
    0..b, right of it the bins above b; node totals from feature 0."""
    x = hist32.astype(np.float64)
    w, g, h = x[..., 0], x[..., 1], x[..., 2]
    left = lambda a: np.cumsum(a, axis=2)
    right = lambda a: np.flip(np.cumsum(np.flip(a, 2), axis=2), 2) - a
    G, H = g[:, 0].sum(axis=1), h[:, 0].sum(axis=1)
    gain = (left(g) ** 2 / (left(h) + LAM) + right(g) ** 2 / (right(h) + LAM)
            - (G ** 2 / (H + LAM))[:, None, None])
    ok = (left(w) >= min_rows) & (right(w) >= min_rows)
    ok[:, :, -1] = False
    gain = np.where(ok, gain, -np.inf)
    f, b = np.unravel_index(np.argmax(gain[0]), gain[0].shape)
    return {"feat": int(f), "bin": int(b), "gain": float(gain[0, f, b]),
            "ok": ok[0], "WR": right(w)[0]}


def totals(hist):
    w, g, h = treelib._node_totals(jnp.asarray(hist))
    return g, h


def via_fused(hist, search=None):
    g, h = totals(hist)
    fn = search or treelib._fused_level_best
    bg, bf, bb, _, _ = fn(jnp.asarray(hist), jnp.ones(1, bool),
                          jnp.ones(3, jnp.float32), None, B, MIN_ROWS, LAM,
                          0.0, g, h)
    return int(bf[0]), int(bb[0]), float(bg[0])


def via_flat(hist):
    return via_fused(hist, treelib._flat_level_best)


def via_search_splits(hist):
    bg, bf, bb, *_ = treelib._search_splits(
        jnp.asarray(hist), jnp.ones(3, jnp.float32), B, MIN_ROWS, LAM, 0.0)
    return int(bf[0]), int(bb[0]), float(bg[0])


def via_build_tree(hist, monkeypatch, fused: bool):
    """The whole builder, one level deep, on the crafted root histogram."""
    monkeypatch.setattr(treelib, "build_histograms",
                        lambda *a, **k: jnp.asarray(hist))
    # the patched histogram is a constant of the trace: every case gets a
    # row count nothing else has traced
    n = 24 + int(fused) + 2 * int(float(hist[0, 0, 0, 0]) != 550_000)
    tr, _, gains, _ = treelib.build_tree(
        jnp.zeros((n, 3), jnp.uint8), jnp.zeros(n), jnp.ones(n), jnp.ones(n),
        jnp.ones(3, jnp.float32), jnp.zeros((3, B - 2), jnp.float32),
        max_depth=1, nbins=B, min_rows=MIN_ROWS, reg_lambda=LAM,
        hist_method="onehot", fused_split=fused)
    assert bool(tr.is_split[0])
    return int(tr.feat[0]), int(tr.bin[0]), float(gains.sum())


SEARCHES = {
    "fused": lambda hist, mp: via_fused(hist),
    "flat": lambda hist, mp: via_flat(hist),
    "search_splits": lambda hist, mp: via_search_splits(hist),
    "build_tree_fused": lambda hist, mp: via_build_tree(hist, mp, True),
    "build_tree_flat": lambda hist, mp: via_build_tree(hist, mp, False),
}


@pytest.mark.parametrize("weight", [1.0, 0.7], ids=["unit", "weighted"])
def test_right_sums_are_the_tail_bins_own_sums(weight):
    hist = crafted(weight)
    want = oracle(hist, MIN_ROWS)
    WL, GL, HL, WR, GR, HR = (np.asarray(a)[0] for a in
                              treelib._split_sums(jnp.asarray(hist)))
    # the last value bin and the NA bin have nothing above them
    assert (WR[:, -2:] == 0).all() and (HR[:, -2:] == 0).all()
    if weight == 1.0:
        # a tail child's WR is its row count, exactly
        assert WR[1, 15:19].tolist() == [35.0, 20.0, 11.0, 5.0]
        assert WR[2, 16:19].tolist() == [9.0, 5.0, 2.0]
    tail = (slice(None), slice(14, 20))
    np.testing.assert_allclose(WR[tail], want["WR"][tail], rtol=1e-6)
    # a tail child's H is a sum of its own few bins: positive, a quarter of
    # its (weighted) rows
    np.testing.assert_allclose(HR[1, 15:19], 0.25 * WR[1, 15:19], rtol=1e-6)
    # min_rows admits and refuses where float64 does, everywhere
    ok = (WL >= MIN_ROWS) & (WR >= MIN_ROWS)
    ok[:, -1] = False
    assert (ok == want["ok"]).all()
    # left and right of every split add up to the feature's own total
    np.testing.assert_allclose((WL + WR)[:, :-1],
                               np.broadcast_to(WL[:, -1:], (3, B - 1)),
                               rtol=1e-6)


@pytest.mark.parametrize("search", sorted(SEARCHES))
@pytest.mark.parametrize("weight", [1.0, 0.7], ids=["unit", "weighted"])
def test_every_split_search_takes_the_float64_best(monkeypatch, search,
                                                   weight):
    hist = crafted(weight)
    want = oracle(hist, MIN_ROWS)
    assert (want["feat"], want["bin"]) == (0, 9) and want["gain"] > 1e5
    feat, bin_, gain = SEARCHES[search](hist, monkeypatch)
    assert (feat, bin_) == (want["feat"], want["bin"])
    assert np.isfinite(gain) and gain == pytest.approx(want["gain"], rel=1e-4)


def test_the_searches_agree_bit_for_bit():
    hist = crafted(0.7)
    assert via_fused(hist) == via_flat(hist) == via_search_splits(hist)
