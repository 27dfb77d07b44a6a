"""Plain reference for the `xgbrank` cells (LambdaMART: gradient-boosted
trees on pairwise lambda-gradients weighted by the change of NDCG, xgboost's
`rank:ndcg` as `H2OXGBoostEstimator` states it), and the comparison that
decides `correct`. Imports nothing of h2o3_tpu and takes nothing it made but
the result under test: the forest in its heap layout, the reported NDCG@k,
the reported root mean squared margin error, and `pair_grads`, the pairwise
pass under test as a function from margins to (G, H), which it questions at
margins of its own.

The estimator, as the configuration states it. For a query q with documents
i in frame order, margins s_i (all 0 before the first tree), relevance r_i:

  gain       g_i = 2^r_i - 1
  rank       pi_i = #{j: s_j > s_i} + #{j: s_j = s_i, j before i}   (0-based,
             the stable descending sort's: at round 1 it IS the frame order)
  discount   d_i = 1 / log2(pi_i + 2)
  ideal DCG  IDCG_q = sum over t < min(k, n_q) of (2^r_(t) - 1) / log2(t + 2),
             relevance sorted descending
  for every ordered pair with r_i > r_j:
             Delta_ij = |g_i - g_j| |d_i - d_j| / IDCG_q   (0 where IDCG_q = 0)
             rho_ij = sigmoid(-clip(s_i - s_j, -35, 35))
             lambda_ij = rho_ij Delta_ij
  G_i = -sum_j lambda_ij + sum_j lambda_ji
  H_i = max(1e-6, sum of rho (1 - rho) Delta over the pairs i is in)

Trees are depthwise on quantile bins (`max_bins` - 1 value bins from
np.unique(np.quantile(column, linspace)), a missing value in a bin of its
own that goes right), Newton gains G^2/(H + lambda), a split admissible when
both children hold `min_rows` rows and taken when its gain passes
`min_split_improvement`, leaf values -G/(H + lambda) times the learn rate,
initial margin 0.

The program's stated departure from the published truncated Delta-NDCG: the
discount d_i runs over ALL ranks of a query while the ideal DCG stops at k, so
a swap of two documents both below rank k still carries a (small) weight.
The reference follows the statement above, as the program does.

Nothing here is padded: the queries of one size are laid side by side, so a
(queries, n, n) block holds n^2 real slots a query, in float64 on the host.

The comparison FOLLOWS the forest under test, as the GBM reference does and
for its reason (two correct builders part ways at the first near-tie). The
reference bins the raw columns itself, and for the first `follow_trees` trees
it computes ITS OWN lambda-gradients at margins it carries itself, routes the
rows by the splits under test, rebuilds every level's histograms (float32
inside 131,072-row groups on the device, float64 across them on the host),
evaluates every admissible split's gain in float64 and reads `split_gain_gap`
and `leaf_value_gap` as the GBM cell defines them, and

  pair_grad_gap  at each followed round, the (G, H) the pass under test
               returns at the reference's margins against the reference's
               own: the worst row's |difference| as a share of the larger of
               the reference's value and the median size over the rows that
               have pairs, the larger of the two statistics' readings. It
               sees the pair terms directly: a tree averages thousands of
               rows' gradients and hides a rounding of each

then it walks all trees in float64 for

  ndcg_gap     |reported NDCG@k - its own| (mean over the queries with two
               documents or more and a positive ideal DCG, tied margins in
               frame order)
  margin_gap   the reported root mean squared error of the margins against
               the relevance (the program's checksum over its own final
               training margins, all rows) against the same number from the
               float64 walk, as a share of it

`build(...)` is the same estimator built by the reference, for putting the
reference in the program's place: as it stands, one precision below the
stated (`control`: the pair terms rho Delta and rho (1 - rho) Delta rounded
to bfloat16 before their sums, the histogram statistics to float8_e4m3), or
with one of FAULTS planted. It builds `reference.build_trees` trees: the
comparison follows fewer."""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from refmath import rounded

HI = jax.lax.Precision.HIGHEST
FAULTS = ("state_unchanged", "half_batch", "altered_answer",
          "second_best_split", "ranknet_lambda", "queries_cut",
          "stale_metrics")
# (histogram statistics, pair terms) of a stand-in
DTYPES = {"exact": (None, None),
          "below": (jnp.float8_e4m3fn, ml_dtypes.bfloat16)}
PAIR_SLOTS = 1 << 19          # pair slots a numpy block: 4 MB of float64
GROUP_BLOCKS = 128            # histogram blocks summed in float32 on the device
THREADS = 8


def heap_size(depth: int) -> int:
    return 2 ** (depth + 1) - 1


# -- the pairwise objective ---------------------------------------------------

class Queries:
    """The rows of every query in frame order, queries of one size side by
    side: `by_size` holds (rows (q, n), relevance (q, n), 1/IDCG (q,)) per
    distinct size n. `cut` keeps a query's first `cut` documents only (the
    planted fault of a careless bucketing)."""

    def __init__(self, qid: np.ndarray, rel: np.ndarray, k: int, cut=None):
        self.n_rows, self.k = len(qid), int(k)
        order = np.argsort(qid, kind="stable")
        qs = qid[order]
        starts = np.flatnonzero(np.r_[True, qs[1:] != qs[:-1]])
        sizes = np.diff(np.r_[starts, len(qs)])
        if cut is not None:
            sizes = np.minimum(sizes, int(cut))
        self.by_size = []
        for n in np.unique(sizes):
            rows = order[starts[sizes == n][:, None] + np.arange(n)[None, :]]
            r = rel[rows].astype(np.float64)
            ideal = -np.sort(-r, axis=1)[:, :self.k]
            idcg = ((2.0 ** ideal - 1.0)
                    / np.log2(np.arange(ideal.shape[1]) + 2.0)).sum(axis=1)
            inv = np.where(idcg > 0, 1.0 / np.maximum(idcg, 1e-300), 0.0)
            self.by_size.append((rows, r, inv))

    def pairs(self) -> int:
        """The ordered pairs (r_i > r_j) the objective is a sum over."""
        return int(sum((r[:, :, None] > r[:, None, :]).sum()
                       for _, r, _ in self.blocks()))

    def blocks(self):
        """by_size cut into blocks of at most PAIR_SLOTS pair slots."""
        for rows, r, inv in self.by_size:
            step = max(1, PAIR_SLOTS // (rows.shape[1] ** 2))
            for a in range(0, len(rows), step):
                yield rows[a:a + step], r[a:a + step], inv[a:a + step]


def _pair_block(s, r, inv, delta_ndcg: bool, dtype):
    """(G, H) of the documents of a block of equal-sized queries: s, r (q, n)
    margins and relevance, inv (q,) 1/IDCG."""
    n = s.shape[1]
    order = np.argsort(-s, axis=1, kind="stable")
    rank = np.empty_like(order)
    np.put_along_axis(rank, order, np.arange(n)[None, :], axis=1)
    rho = 1.0 / (1.0 + np.exp(np.clip(s[:, :, None] - s[:, None, :], -35, 35)))
    weight = rho
    if delta_ndcg:
        gain = 2.0 ** r - 1.0
        disc = 1.0 / np.log2(rank + 2.0)
        weight = (rho * np.abs(gain[:, :, None] - gain[:, None, :])
                  * np.abs(disc[:, :, None] - disc[:, None, :])
                  * inv[:, None, None])
    pair = r[:, :, None] > r[:, None, :]
    lam = np.where(pair, weight, 0.0)
    hess = lam * (1.0 - rho)
    if dtype is not None:
        lam = lam.astype(dtype).astype(np.float64)
        hess = hess.astype(dtype).astype(np.float64)
    return (-lam.sum(axis=2) + lam.sum(axis=1),
            hess.sum(axis=2) + hess.sum(axis=1))


def lambda_grads(queries: Queries, margin: np.ndarray, delta_ndcg=True,
                 dtype=None):
    """Float64 (G, H) of every row at float64 margins. A row of no query
    (left out by `cut`) keeps G 0 and H at its floor."""
    G = np.zeros(queries.n_rows)
    H = np.zeros(queries.n_rows)

    def one(block):
        rows, r, inv = block
        g, h = _pair_block(margin[rows], r, inv, delta_ndcg, dtype)
        G[rows], H[rows] = g, h           # a row is in one block only

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(one, queries.blocks()))
    return G, np.maximum(H, 1e-6)


def ndcg(queries: Queries, margin: np.ndarray) -> float:
    """Mean NDCG@k over the queries with two documents or more and a positive
    ideal DCG; tied margins rank in frame order."""
    total, counted = 0.0, 0
    for rows, r, inv in queries.by_size:
        if rows.shape[1] < 2:
            continue
        order = np.argsort(-margin[rows], axis=1, kind="stable")
        top = np.take_along_axis(r, order, axis=1)[:, :queries.k]
        dcg = ((2.0 ** top - 1.0)
               / np.log2(np.arange(top.shape[1]) + 2.0)).sum(axis=1)
        total += float((dcg * inv)[inv > 0].sum())
        counted += int((inv > 0).sum())
    return total / max(counted, 1)


# -- histograms, routing, gains -------------------------------------------------

@functools.partial(jax.jit, static_argnames=("nodes", "bins", "block", "fold",
                                             "dtype"))
def _hist_groups(codes, local, vals, nodes: int, bins: int, block: int,
                 fold: int, dtype=None):
    """(groups, 3*nodes, F*bins): per group of `fold` blocks of rows the sums
    of the three statistics per (node, feature, bin), one matmul a block
    summed in float32; a row whose `local` node is outside [0, nodes) adds
    nothing."""
    F, npad = codes.shape
    vals = rounded(vals, dtype)
    node_ids = jnp.arange(nodes, dtype=jnp.int32)[:, None]
    bin_ids = jnp.arange(bins, dtype=jnp.int32)

    def one_group(first):
        def one_block(b, acc):
            at = first + b * block
            c = jax.lax.dynamic_slice(codes, (0, at), (F, block))
            nd = jax.lax.dynamic_slice(local, (at,), (block,))
            v = jax.lax.dynamic_slice(vals, (0, at), (3, block))
            here = (nd[None, :] == node_ids).astype(jnp.float32)
            wmat = (v[:, None, :] * here[None, :, :]).reshape(3 * nodes, block)
            oh = (c.T.astype(jnp.int32)[:, :, None] == bin_ids
                  ).astype(jnp.float32)       # (block, F, bins)
            return acc + jnp.dot(wmat, oh.reshape(block, -1), precision=HI)

        return jax.lax.fori_loop(
            0, fold, one_block, jnp.zeros((3 * nodes, F * bins), jnp.float32))

    return jax.lax.map(one_group,
                       jnp.arange(0, npad, block * fold, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("width",))
def _route(codes, node, feat, bins, is_split, base, width: int):
    """One level down the heap for the rows at the level that starts at heap
    node `base`: a row at a split node moves to the child its code selects
    (code <= bin goes left), any other row stays. Tables and codes are read
    through one-hot selections: a per-row gather is the slow way on the chip."""
    local = node - base
    f = jnp.full(node.shape, -1, jnp.int32)
    b = jnp.zeros(node.shape, jnp.int32)
    for j in range(width):
        here = (local == j) & jax.lax.dynamic_index_in_dim(
            is_split, base + j, keepdims=False)
        f = jnp.where(here, jax.lax.dynamic_index_in_dim(
            feat, base + j, keepdims=False), f)
        b = jnp.where(here, jax.lax.dynamic_index_in_dim(
            bins, base + j, keepdims=False), b)

    def pick(j, code):
        return jnp.where(f == j, jax.lax.dynamic_index_in_dim(
            codes, j, keepdims=False).astype(jnp.int32), code)

    code = jax.lax.fori_loop(0, codes.shape[0], pick,
                             jnp.zeros(node.shape, jnp.int32))
    child = 2 * node + 1 + (code > b).astype(jnp.int32)
    return jnp.where(f >= 0, child, node)


class Prepared:
    """The data as the reference holds it: its own bin codes on the device,
    feature-major, padded to whole blocks with weightless rows; the queries."""

    def __init__(self, cfg: dict, data: dict):
        est, ref = cfg["estimator"], cfg["reference"]
        self.names = list(data["names"])
        self.nvalue = int(est["max_bins"]) - 1
        self.bins = self.nvalue + 1            # the last one is the NA bin
        self.n = len(data["rel"])
        F = len(self.names)
        self.block = 1 << max(7, ((1 << 28) // (4 * F * self.bins)
                                  ).bit_length() - 1)
        self.fold = min(GROUP_BLOCKS, -(-self.n // self.block))
        group = self.block * self.fold
        self.npad = -(-self.n // group) * group
        codes = np.zeros((F, self.npad), np.uint8)
        qs = np.linspace(0, 1, self.nvalue + 1)[1:-1]

        def bin_column(j):
            col = np.asarray(data["columns"][self.names[j]], np.float64)
            na = np.isnan(col)
            e = np.unique(np.quantile(col[~na], qs))
            c = np.clip(np.searchsorted(e, col, side="left"), 0,
                        self.nvalue - 1)
            codes[j, :self.n] = np.where(na, self.nvalue, c)
            return e

        with ThreadPoolExecutor(THREADS) as pool:   # a column a task
            self.edges = list(pool.map(bin_column, range(F)))
        self.codes = jnp.asarray(codes)
        self.rel = np.asarray(data["rel"], np.float64)
        self.ones = np.pad(np.ones(self.n), (0, self.npad - self.n))
        self.k = int(est.get("ndcg_k", 10))
        self.qid = np.asarray(data["qid"])
        self.queries = Queries(self.qid, self.rel, self.k)
        self.fault_cut = int(ref["fault_cut"])
        self.width = 2 ** (int(est["max_depth"]) - 1)
        self.build_trees = int(ref["build_trees"])

    def stats(self, G, H, w=None):
        """(3, npad) float32 on the device: w, G w, H w."""
        w = self.ones if w is None else w
        pad = lambda a: np.pad(a, (0, self.npad - self.n))
        return jnp.asarray(np.stack([w, pad(G) * w, pad(H) * w]), jnp.float32)

    def level_hist(self, node, vals, depth: int, dtype=None) -> np.ndarray:
        """(nodes, F, bins, 3) float64 histogram of the rows at `depth`. The
        device program is always `width` nodes wide, so every level shares
        one program."""
        nodes, F = 2 ** depth, len(self.names)
        part = _hist_groups(self.codes, node - (nodes - 1), vals, self.width,
                            self.bins, self.block, self.fold, dtype)
        tot = np.asarray(part, np.float64).sum(axis=0)   # (3*width, F*bins)
        return tot.reshape(3, self.width, F, self.bins
                           )[:, :nodes].transpose(1, 2, 3, 0)

    def route(self, node, feat, bins, is_split, depth: int):
        pad = lambda a: jnp.pad(jnp.asarray(a), (0, self.width))
        return _route(self.codes, node, pad(feat), pad(bins), pad(is_split),
                      2 ** depth - 1, self.width)

    def node_sums(self, node, vals, nodes: int) -> np.ndarray:
        """(nodes, 3) float64 totals per heap node, summed on the host."""
        v = np.asarray(vals, np.float64)
        nd = np.asarray(node)
        return np.stack([np.bincount(nd, weights=v[i], minlength=nodes)
                         for i in range(3)], axis=1)

    def leaves(self, result: dict, t: int):
        """Heap node of every row after tree `t`, by the splits of `result`."""
        depth = int(result["params"]["max_depth"])
        node = jnp.zeros(self.npad, jnp.int32)
        for d in range(depth):
            node = self.route(node, result["feat"][t], result["bin"][t],
                              result["is_split"][t], d)
        return node

    def rmse(self, margin: np.ndarray) -> float:
        return float(np.sqrt(np.mean((margin - self.rel) ** 2)))


def gains(hist: np.ndarray, min_rows: float, lam: float):
    """Float64 gain of every split (nodes, F, bins), -inf where it is not
    admissible."""
    w, g, h = hist[..., 0], hist[..., 1], hist[..., 2]
    G, H = (a[:, 0].sum(axis=1) for a in (g, h))
    WL, GL, HL = (np.cumsum(a, axis=2) for a in (w, g, h))
    right = lambda a: np.flip(np.cumsum(np.flip(a, 2), axis=2), 2) - a
    WR, GR, HR = right(w), right(g), right(h)
    gain = (GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam)
            - (G ** 2 / (H + lam))[:, None, None])
    ok = (WL >= min_rows) & (WR >= min_rows)
    ok[:, :, -1] = False                       # no split at the NA bin
    return np.where(ok, gain, -np.inf)


def leaf_values(tot: np.ndarray, lam: float, lr: float) -> np.ndarray:
    return -tot[:, 1] / (tot[:, 2] + lam + 1e-12) * lr


def grad_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The worst row's |got - want| as a share of the larger of |want| and
    the median |want| over the rows above the Hessian's floor (a row with no
    pair holds G 0 and H at the floor on both sides)."""
    size = np.abs(want)
    paired = size[size > 1e-6]
    scale = np.maximum(size, np.median(paired) if len(paired) else 1.0)
    return float(np.max(np.abs(np.asarray(got, np.float64) - want) / scale))


def _params(params: dict) -> tuple:
    return (int(params["max_depth"]), float(params["min_rows"]),
            float(params["min_split_improvement"]),
            float(params.get("reg_lambda", 1.0)), float(params["learn_rate"]))


def check(prep: Prepared, result: dict, follow_trees: int) -> dict:
    depth, min_rows, msi, lam, lr = _params(result["params"])
    if list(result["names"]) != prep.names:
        raise ValueError("the forest under test names other columns")
    ntrees = result["feat"].shape[0]
    margin = np.full(prep.n, float(result["f0"]))
    split_gap = leaf_gap = pair_gap = 0.0
    for t in range(ntrees):
        if t < follow_trees:
            G, H = lambda_grads(prep.queries, margin)
            g, h = result["pair_grads"](margin)
            pair_gap = max(pair_gap, grad_gap(g, G), grad_gap(h, H))
            vals = prep.stats(G, H)
            node = jnp.zeros(prep.npad, jnp.int32)
            live = np.ones(1, bool)
            for d in range(depth):
                base = 2 ** d - 1
                if live.any():
                    gain = gains(prep.level_hist(node, vals, d), min_rows, lam)
                    best = gain.reshape(len(live), -1).max(axis=1)
                    took = result["is_split"][t, base:base + len(live)]
                    f_t = result["feat"][t, base:base + len(live)]
                    b_t = result["bin"][t, base:base + len(live)]
                    taken = np.where(took, gain[np.arange(len(live)), f_t, b_t],
                                     0.0)
                    want = np.where(best > max(msi, 1e-10), best, 0.0)
                    med = np.median(want[live & (want > 0)]) if (
                        live & (want > 0)).any() else 0.0
                    scale = np.maximum(np.maximum(want, med), 1e-300)
                    gap = np.where(np.isfinite(taken),
                                   np.maximum(want - taken, 0.0) / scale, 1.0)
                    split_gap = max(split_gap, float(gap[live].max()))
                    live = np.repeat(live & took, 2)
                node = prep.route(node, result["feat"][t], result["bin"][t],
                                  result["is_split"][t], d)
            tot = prep.node_sums(node, vals, heap_size(depth))
            held = tot[:, 0] > 0
            want = leaf_values(tot, lam, lr)[held]
            got = result["value"][t].astype(np.float64)[held]
            scale = np.maximum(np.abs(want), np.median(np.abs(want)))
            leaf_gap = max(leaf_gap, float(np.max(np.abs(got - want) / scale)))
        else:
            node = prep.leaves(result, t)
        margin = margin + result["value"][t].astype(np.float64)[
            np.asarray(node)[:prep.n]]
    own_ndcg, own_rmse = ndcg(prep.queries, margin), prep.rmse(margin)
    return {"pair_grad_gap": pair_gap, "split_gain_gap": split_gap,
            "leaf_value_gap": leaf_gap,
            "ndcg_gap": abs(result["ndcg"] - own_ndcg),
            "margin_gap": abs(result["rmse"] - own_rmse) / own_rmse}


def build(prep: Prepared, params: dict, precision: str = "exact",
          fault: str | None = None) -> dict:
    """The estimator built by the reference: the result a fit would hand to
    `check`. `precision="below"` rounds the pair terms and the histogram
    statistics one step below what the configuration states (float32,
    bfloat16); `fault` plants one of FAULTS: the margins stay at the initial
    one (every tree fits the first tree's gradients); every second row left
    out of the trees; the first tree's largest leaf value altered by 1%;
    every node takes its second-best admissible split; the pair weight
    without Delta-NDCG (plain RankNet lambdas); every query cut to its first
    `fault_cut` documents; the NDCG and the margins' checksum reported from
    the margins of one tree before the last (metrics of another forest than
    the one handed over)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    depth, min_rows, msi, lam, lr = _params(params)
    ntrees, T = min(int(params["ntrees"]), prep.build_trees), heap_size(depth)
    stat_dt, low_dt = DTYPES[precision]
    queries = prep.queries if fault != "queries_cut" else Queries(
        prep.qid, prep.rel, prep.k, cut=prep.fault_cut)
    grads = lambda m: lambda_grads(queries, m, fault != "ranknet_lambda",
                                   low_dt)
    w = prep.ones * (np.arange(prep.npad) % 2 == 0) \
        if fault == "half_batch" else None
    feat_a = np.zeros((ntrees, T), np.int32)
    bin_a = np.zeros((ntrees, T), np.int32)
    split_a = np.zeros((ntrees, T), bool)
    value_a = np.zeros((ntrees, T), np.float32)
    margin = np.zeros(prep.n)
    vals = prep.stats(*grads(margin), w)
    for t in range(ntrees):
        if t and fault != "state_unchanged":
            vals = prep.stats(*grads(margin), w)
        node = jnp.zeros(prep.npad, jnp.int32)
        live = np.ones(1, bool)
        for d in range(depth):
            base, L = 2 ** d - 1, 2 ** d
            if live.any():
                gain = gains(prep.level_hist(node, vals, d, stat_dt),
                             min_rows, lam)
                flat = gain.reshape(L, -1)
                order = np.argsort(-flat, axis=1, kind="stable")
                pick = order[:, 1 if fault == "second_best_split" else 0]
                best = flat[np.arange(L), pick]
                took = live & (best > max(msi, 1e-10))
                feat_a[t, base:base + L] = np.where(took, pick // prep.bins, 0)
                bin_a[t, base:base + L] = np.where(took, pick % prep.bins, 0)
                split_a[t, base:base + L] = took
                live = np.repeat(took, 2)
            node = prep.route(node, feat_a[t], bin_a[t], split_a[t], d)
        tot = prep.node_sums(node, vals, T)
        value_a[t] = np.where(tot[:, 0] > 0, leaf_values(tot, lam, lr), 0.0)
        if fault == "altered_answer" and t == 0:
            value_a[0, np.argmax(np.abs(value_a[0]))] *= 1.01
        reported = margin if fault == "stale_metrics" else None
        margin = margin + value_a[t].astype(np.float64)[
            np.asarray(node)[:prep.n]]
    reported = margin if reported is None else reported
    return {"params": dict(params), "f0": 0.0, "feat": feat_a, "bin": bin_a,
            "is_split": split_a, "value": value_a, "edges": prep.edges,
            "names": prep.names, "ndcg": ndcg(prep.queries, reported),
            "rmse": prep.rmse(reported), "pair_grads": grads}


def prepare(cfg: dict, data: dict) -> Prepared:
    return Prepared(cfg, data)


def compare(cfg: dict, prep: Prepared, result: dict) -> dict:
    numbers = check(prep, result, int(cfg["reference"]["follow_trees"]))
    # the grid the program quantized on against the reference's own: read,
    # never compared (a forest on another grid fails split_gain_gap)
    numbers["edges_gap"] = float(max(
        np.max(np.abs(np.asarray(a) - b) / np.maximum(np.abs(b), 1e-12),
               initial=0.0)
        if len(a) == len(b) else np.inf
        for a, b in zip(result["edges"], prep.edges)))
    return numbers


def control(cfg: dict, prep: Prepared, params: dict,
            precision: str = "below", fault: str | None = None) -> dict:
    """The reference in the program's place, one precision below the stated."""
    return build(prep, params, precision, fault)


def faulty(cfg: dict, prep: Prepared, params: dict, fault: str) -> dict:
    """The reference in the program's place with one fault planted."""
    return build(prep, params, "exact", fault)
