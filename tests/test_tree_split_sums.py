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
cent of the child. The old code is not kept to prove it.

`plain_level_best` is the reference `_fused_level_best` is held to, bit for
bit, on random histograms: a flat argmax over (L, F, B) gains."""

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


def plain_level_best(hist, node_ok, feat_mask, keep, nbins: int, min_rows,
                     reg_lambda, reg_alpha, gsum, hsum,
                     monotone=None, lo_lvl=None, hi_lvl=None):
    """The tests' reference for `tree._fused_level_best`: the plain split
    search of a dense or a compact level, gain per (L, F, B) from
    `_split_sums`, one flat argmax. Same arguments and results, except that
    the child values are None without `monotone`."""
    L, F = hist.shape[0], hist.shape[1]
    WL, GL, HL, WR, GR, HR = treelib._split_sums(hist)
    G = gsum[:, None, None]
    H = hsum[:, None, None]
    # xgboost CalcSplitGain: L1 soft-threshold the gradient sums
    # before squaring (ThresholdL1); exact no-op at reg_alpha=0
    tl1 = lambda A: jnp.sign(A) * jnp.maximum(jnp.abs(A) - reg_alpha, 0.0)
    GLt, GRt, Gt = tl1(GL), tl1(GR), tl1(G)
    gain = (
        GLt * GLt / (HL + reg_lambda)
        + GRt * GRt / (HR + reg_lambda)
        - Gt * Gt / (H + reg_lambda)
    )
    ok = (WL >= min_rows) & (WR >= min_rows)
    ok = ok & (jnp.arange(nbins)[None, None, :] < nbins - 1)   # no split at NA bin
    ok = ok & (feat_mask[None, :, None] > 0)
    ok = ok & node_ok[:, None, None]
    if monotone is not None:
        # monotone_constraints (hex/tree Constraints / LightGBM): a
        # split on feature f with constraint c is admissible only
        # when c·(value_right − value_left) ≥ 0, where the child
        # values use the SAME soft-thresholded formula as
        # materialized node values and are clamped into the node's
        # inherited bounds. Bound propagation (in `build_tree`) then
        # guarantees zero violations.
        vL = jnp.clip(-GLt / (HL + reg_lambda + 1e-12),
                      lo_lvl[:, None, None], hi_lvl[:, None, None])
        vR = jnp.clip(-GRt / (HR + reg_lambda + 1e-12),
                      lo_lvl[:, None, None], hi_lvl[:, None, None])
        mc = monotone[None, :, None]
        ok = ok & ((mc == 0) | (mc * (vR - vL) >= 0))
    if keep is not None:
        ok = ok & keep[:, :, None]
    gain = jnp.where(ok, gain, -jnp.inf)

    flat = gain.reshape(L, F * nbins)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    bf = (best // nbins).astype(jnp.int32)
    bb = (best % nbins).astype(jnp.int32)
    vLs = vRs = None
    if monotone is not None:
        # child values at the chosen split, gathered from the SAME
        # vL/vR used by the admissibility check (bound propagation)
        flat_pick = lambda A: jnp.take_along_axis(
            A.reshape(L, F * nbins), best[:, None], axis=1)[:, 0]
        vLs, vRs = flat_pick(vL), flat_pick(vR)
    return best_gain, bf, bb, vLs, vRs


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
    return via_fused(hist, plain_level_best)


def via_search_splits(hist):
    bg, bf, bb, *_ = treelib._search_splits(
        jnp.asarray(hist), jnp.ones(3, jnp.float32), B, MIN_ROWS, LAM, 0.0)
    return int(bf[0]), int(bb[0]), float(bg[0])


def via_build_tree(hist, monkeypatch):
    """The whole builder, one level deep, on the crafted root histogram."""
    monkeypatch.setattr(treelib, "build_histograms",
                        lambda *a, **k: jnp.asarray(hist))
    # the patched histogram is a constant of the trace: every case gets a
    # row count nothing else has traced
    n = 24 + 2 * int(float(hist[0, 0, 0, 0]) != 550_000)
    tr, _, gains, _ = treelib.build_tree(
        jnp.zeros((n, 3), jnp.uint8), jnp.zeros(n), jnp.ones(n), jnp.ones(n),
        jnp.ones(3, jnp.float32), jnp.zeros((3, B - 2), jnp.float32),
        max_depth=1, nbins=B, min_rows=MIN_ROWS, reg_lambda=LAM,
        hist_method="onehot")
    assert bool(tr.is_split[0])
    return int(tr.feat[0]), int(tr.bin[0]), float(gains.sum())


SEARCHES = {
    "fused": lambda hist, mp: via_fused(hist),
    "flat": lambda hist, mp: via_flat(hist),
    "search_splits": lambda hist, mp: via_search_splits(hist),
    "build_tree": lambda hist, mp: via_build_tree(hist, mp),
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


def random_level(seed: int, L: int, F: int = 7):
    """(L, F, B, 3) float32 histogram of small integer counts, many bins
    empty: equal gains (ties; feature 5 repeats feature 1) and, without
    lambda, 0/0 gains are common."""
    rng = np.random.default_rng(seed)
    w = rng.integers(0, 4, size=(L, F, B)) * (rng.random((L, F, B)) < 0.6)
    g = rng.integers(-3, 4, size=(L, F, B)) * (w > 0)
    h = 0.25 * w
    hist = np.stack([w, g, h], axis=-1).astype(np.float32)
    hist[:, 5] = hist[:, 1]     # a twin feature: its every gain is a tie
    return hist


FUSED_CASES = {
    "plain": dict(L=8),
    "keep": dict(L=8, keep=True),
    "monotone": dict(L=4, monotone=True),
    "alpha_lambda0": dict(L=8, lam=0.0, alpha=0.5, min_rows=0.0),
    # a compact level: CAP + 1 slots, the trash slot and dead slots masked
    "compact": dict(L=9, keep=True, dead=(2, 5, 8)),
}


@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_fused_level_best_matches_plain_search(case):
    c = FUSED_CASES[case]
    L, F = c["L"], 7
    lam, alpha = c.get("lam", LAM), c.get("alpha", 0.0)
    rng = np.random.default_rng(11)
    node_ok = np.ones(L, bool)
    node_ok[list(c.get("dead", ()))] = False
    keep = mono = lo = hi = None
    if c.get("keep"):
        keep = rng.random((L, F)) < 0.5
        keep[:, 0] |= ~keep.any(axis=1)
        keep = jnp.asarray(keep)
    if c.get("monotone"):
        mono = jnp.asarray(rng.integers(-1, 2, size=F).astype(np.float32))
        lo = jnp.asarray(rng.uniform(-2.0, -0.1, L).astype(np.float32))
        hi = jnp.asarray(rng.uniform(0.1, 2.0, L).astype(np.float32))
    feat_mask = jnp.asarray((np.arange(F) != 3).astype(np.float32))
    saw_nan = False
    for seed in range(6):
        hist = jnp.asarray(random_level(seed, L, F))
        _, g, h = treelib._node_totals(hist)
        args = (hist, jnp.asarray(node_ok), feat_mask, keep, B,
                c.get("min_rows", 2.0), lam, alpha, g, h)
        kw = dict(monotone=mono, lo_lvl=lo, hi_lvl=hi)
        got = treelib._fused_level_best(*args, **kw)
        want = plain_level_best(*args, **kw)
        for a, b in zip(got[:3], want[:3]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if mono is not None:
            # the child values feed the bound propagation where a node splits
            split = np.isfinite(np.asarray(want[0]))
            assert split.any()
            for a, b in zip(got[3:], want[3:]):
                np.testing.assert_array_equal(np.asarray(a)[split],
                                              np.asarray(b)[split])
        best = np.asarray(want[0])
        saw_nan |= bool(np.isnan(best).any())
        assert (best[~node_ok] == -np.inf).all()
    if case == "alpha_lambda0":
        assert saw_nan      # a NaN gain wins, first occurrence, on both
