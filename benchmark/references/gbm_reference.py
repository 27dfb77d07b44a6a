"""Plain reference for the `gbm` cells, and the comparison that decides
`correct`. Imports nothing of h2o3_tpu and takes nothing it made but the
result under test: the forest in its heap layout, the initial margin and the
reported training metrics.

The estimator, as the configuration states it: depthwise gradient boosting on
`UniformAdaptive` bins (nbins uniform bins over each column's [min, max], bin
k holding (lo + k*step, lo + (k+1)*step]), bernoulli deviance, Newton gains
G^2/(H + lambda) with lambda 1, a split admissible when both children hold
`min_rows` rows and taken when its gain passes `min_split_improvement`, leaf
values -G/(H + lambda) times the learn rate.

The comparison FOLLOWS the forest under test, because two correct builders
part ways at the first near-tie and a forest cannot be compared node by node
with another forest. The reference bins the raw columns itself (float64 on
the host), and for the first `follow_trees` trees it routes the rows by the
splits under test, rebuilds every level's histograms at margins it carries
itself (plain jax.numpy in 65,536-row blocks at `highest`, float64 across
blocks on the host), evaluates every admissible split's gain in float64 and
reads

  split_gain_gap   the widest share by which a taken split's gain lies below
                   the best admissible gain of its node, against the larger
                   of that best and the level's median best (1.0 for a split
                   that float64 does not admit at all)
  leaf_value_gap   the worst terminal node's value against -G/(H + lambda) * lr
                   from float64 totals, against the larger of that value and
                   the tree's median leaf

then walks all trees for the float64 logloss and the exact AUC of the final
margins: `logloss_gap`, `auc_gap` of the reported training metrics.

`build(...)` is the same estimator built by the reference, for putting the
reference in the program's place: as it stands (it then passes its own
comparison), one precision below the stated (`control`: histogram statistics
rounded to float8_e4m3, the leaf totals' terms to bfloat16, where the program
rounds the statistics to bfloat16 and sums the leaves in float32), or with
one of FAULTS planted."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from refmath import auc_exact, block_rows, rounded

HI = jax.lax.Precision.HIGHEST
FAULTS = ("state_unchanged", "half_batch", "altered_answer",
          "second_best_split")
DTYPES = {"exact": (None, None),
          "below": (jnp.float8_e4m3fn, jnp.bfloat16)}


def heap_size(depth: int) -> int:
    return 2 ** (depth + 1) - 1


@functools.partial(jax.jit, static_argnames=("nodes", "bins", "block", "dtype"))
def _hist_blocks(codes, local, vals, nodes: int, bins: int, block: int,
                 dtype=None):
    """(blocks, 3*nodes, F*bins): per block of rows the sums of the three
    statistics per (node, feature, bin), one matmul a block; a row whose
    `local` node is outside [0, nodes) adds nothing."""
    nb = local.shape[0] // block
    vals = rounded(vals, dtype)

    def one_block(args):
        c, nd, v = args                       # (F, R) u8, (R,), (3, R)
        at = (nd[None, :] == jnp.arange(nodes, dtype=jnp.int32)[:, None]
              ).astype(jnp.float32)           # (nodes, R)
        wmat = (v[:, None, :] * at[None, :, :]).reshape(3 * nodes, block)
        oh = (c.T[:, :, None] == jnp.arange(bins, dtype=jnp.uint8)
              ).astype(jnp.float32)           # (R, F, bins)
        return jnp.dot(wmat, oh.reshape(block, -1), precision=HI)

    split = lambda a: a.reshape(a.shape[0], nb, block).transpose(1, 0, 2)
    return jax.lax.map(one_block, (split(codes), local.reshape(nb, block),
                                   split(vals)))


@functools.partial(jax.jit, static_argnames=("nodes", "block", "dtype"))
def _node_sums(node, vals, nodes: int, block: int, dtype=None):
    """(blocks, 3, nodes): per block the three statistics summed per node."""
    nb = node.shape[0] // block
    vals = rounded(vals, dtype)

    def one_block(args):
        nd, v = args
        oh = (nd[:, None] == jnp.arange(nodes, dtype=jnp.int32)[None, :]
              ).astype(jnp.float32)
        return jnp.dot(v, oh, precision=HI)

    return jax.lax.map(one_block, (
        node.reshape(nb, block),
        vals.reshape(3, nb, block).transpose(1, 0, 2)))


@jax.jit
def _grad_vals(margin, y, w):
    p = jax.nn.sigmoid(margin)
    return jnp.stack([w, (p - y) * w, p * (1 - p) * w])


@functools.partial(jax.jit, static_argnames=("width",))
def _route(codes, node, feat, bins, is_split, base, width: int):
    """One level down the heap for the rows at the level that starts at heap
    node `base`: a row at a split node moves to the child its code selects
    (code <= bin goes left), any other row stays. Tables and codes are read
    through one-hot selections (`width` nodes a level at most, F features):
    a per-row gather is the slow way on the chip."""
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
    code = jnp.zeros(node.shape, jnp.int32)
    for j in range(codes.shape[0]):
        code = jnp.where(f == j, codes[j].astype(jnp.int32), code)
    child = 2 * node + 1 + (code > b).astype(jnp.int32)
    return jnp.where(f >= 0, child, node)


@jax.jit
def _add_leaves(margin, node, value):
    return margin + value[node]


class Prepared:
    """The data as the reference holds it: its own bin codes on the device,
    feature-major, padded to whole blocks with weightless rows."""

    def __init__(self, cfg: dict, data: dict):
        self.names = list(data["names"])
        self.nvalue = int(cfg["estimator"]["nbins"])
        self.bins = self.nvalue + 1           # the last one is the NA bin
        self.n = len(data["y"])
        self.block = block_rows(self.n)
        self.npad = -(-self.n // self.block) * self.block
        pad = self.npad - self.n
        codes = np.zeros((len(self.names), self.npad), np.uint8)
        self.edges = []
        buf = np.empty(1 << 20, np.float64)   # one work buffer, reused
        for j, name in enumerate(self.names):
            col = data["columns"][name]
            lo, hi = float(col.min()), float(col.max())
            step = (hi - lo) / self.nvalue if hi > lo else 1.0
            for a in range(0, self.n, len(buf)):
                part = col[a:a + len(buf)]
                b = buf[:len(part)]
                np.subtract(part, lo, out=b, dtype=np.float64)
                np.divide(b, step, out=b)
                np.ceil(b, out=b)
                np.clip(b - 1, 0, self.nvalue - 1, out=b)
                codes[j, a:a + len(part)] = b
            self.edges.append(lo + step * np.arange(1, self.nvalue))
        self.codes = jnp.asarray(codes)
        self.y_host = data["y"].astype(np.float64)
        self.y = jnp.asarray(np.pad(data["y"].astype(np.float32), (0, pad)))
        self.w = jnp.asarray(np.pad(np.ones(self.n, np.float32), (0, pad)))
        mu = float(self.y_host.mean())
        self.f0 = float(np.log(mu / (1 - mu)))
        self.width = 2 ** (int(cfg["estimator"]["max_depth"]) - 1)

    def level_hist(self, node, vals, depth: int, dtype=None) -> np.ndarray:
        """(nodes, F, bins, 3) float64 histogram of the rows at `depth`. The
        device program is always `width` nodes wide (a narrower level fills
        the matmul's tile no better), so every level shares one program."""
        nodes, F = 2 ** depth, len(self.names)
        part = _hist_blocks(self.codes, node - (nodes - 1), vals, self.width,
                            self.bins, self.block, dtype)
        tot = np.asarray(part, np.float64).sum(axis=0)  # (3*width, F*bins)
        return tot.reshape(3, self.width, F, self.bins
                           )[:, :nodes].transpose(1, 2, 3, 0)

    def route(self, node, feat, bins, is_split, depth: int):
        pad = lambda a: jnp.pad(jnp.asarray(a), (0, self.width))
        return _route(self.codes, node, pad(feat), pad(bins), pad(is_split),
                      2 ** depth - 1, self.width)

    def node_sums(self, node, vals, nodes: int, dtype=None) -> np.ndarray:
        """(nodes, 3) float64 totals per heap node."""
        part = _node_sums(node, vals, nodes, self.block, dtype)
        return np.asarray(part, np.float64).sum(axis=0).T

    def metrics(self, margin) -> tuple:
        eta = np.asarray(margin, np.float64)[:self.n]
        ll = float(np.mean(np.logaddexp(0.0, eta) - self.y_host * eta))
        return ll, auc_exact(eta, self.y_host)


def gains(hist: np.ndarray, min_rows: float, lam: float):
    """Float64 gain of every split (nodes, F, bins), -inf where it is not
    admissible, and the node totals (W, G, H)."""
    w, g, h = hist[..., 0], hist[..., 1], hist[..., 2]
    W, G, H = (a[:, 0].sum(axis=1) for a in (w, g, h))
    WL, GL, HL = (np.cumsum(a, axis=2) for a in (w, g, h))
    right = lambda a: np.flip(np.cumsum(np.flip(a, 2), axis=2), 2) - a
    WR, GR, HR = right(w), right(g), right(h)
    gain = (GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam)
            - (G ** 2 / (H + lam))[:, None, None])
    ok = (WL >= min_rows) & (WR >= min_rows)
    ok[:, :, -1] = False                       # no split at the NA bin
    return np.where(ok, gain, -np.inf), (W, G, H)


def leaf_values(tot: np.ndarray, lam: float, lr: float) -> np.ndarray:
    return -tot[:, 1] / (tot[:, 2] + lam + 1e-12) * lr


def _params(params: dict) -> tuple:
    return (int(params["max_depth"]), float(params["min_rows"]),
            float(params["min_split_improvement"]),
            float(params.get("reg_lambda", 1.0)), float(params["learn_rate"]))


def check(prep: Prepared, result: dict, follow_trees: int) -> dict:
    depth, min_rows, msi, lam, lr = _params(result["params"])
    if list(result["names"]) != prep.names:
        raise ValueError("the forest under test names other columns")
    ntrees = result["feat"].shape[0]
    margin = jnp.full(prep.npad, np.float32(result["f0"]), jnp.float32)
    split_gap = leaf_gap = 0.0
    for t in range(ntrees):
        follow = t < follow_trees
        vals = _grad_vals(margin, prep.y, prep.w) if follow else None
        node = jnp.zeros(prep.npad, jnp.int32)
        live = np.ones(1, bool)
        for d in range(depth):
            base = 2 ** d - 1
            if follow and live.any():
                gain, _ = gains(prep.level_hist(node, vals, d), min_rows, lam)
                best = gain.reshape(len(live), -1).max(axis=1)
                took = result["is_split"][t, base:base + len(live)]
                f_t = result["feat"][t, base:base + len(live)]
                b_t = result["bin"][t, base:base + len(live)]
                taken = np.where(took, gain[np.arange(len(live)), f_t, b_t], 0.0)
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
        if follow:
            tot = prep.node_sums(node, vals, heap_size(depth))
            held = tot[:, 0] > 0
            want = leaf_values(tot, lam, lr)[held]
            got = result["value"][t].astype(np.float64)[held]
            scale = np.maximum(np.abs(want), np.median(np.abs(want)))
            leaf_gap = max(leaf_gap, float(np.max(np.abs(got - want) / scale)))
        margin = _add_leaves(margin, node, jnp.asarray(result["value"][t]))
    ll, auc = prep.metrics(margin)
    return {"split_gain_gap": split_gap, "leaf_value_gap": leaf_gap,
            "logloss_gap": abs(result["logloss"] - ll) / ll,
            "auc_gap": abs(result["auc"] - auc)}


def build(prep: Prepared, params: dict, precision: str = "exact",
          fault: str | None = None) -> dict:
    """The estimator built by the reference: the result a fit would hand to
    `check`. `precision="below"` rounds the histogram statistics and the leaf
    totals' terms one step below what the configuration states; `fault`
    plants one of FAULTS: the margins stay at the initial one (every tree
    fits the first tree's gradients); every second row left out; the first
    tree's largest leaf value altered by 1%; every node takes its second-best
    admissible split."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    depth, min_rows, msi, lam, lr = _params(params)
    ntrees, T = int(params["ntrees"]), heap_size(depth)
    stat_dt, leaf_dt = DTYPES[precision]
    w = prep.w
    if fault == "half_batch":
        w = w * (jnp.arange(prep.npad) % 2 == 0)
    feat_a = np.zeros((ntrees, T), np.int32)
    bin_a = np.zeros((ntrees, T), np.int32)
    split_a = np.zeros((ntrees, T), bool)
    value_a = np.zeros((ntrees, T), np.float32)
    margin = jnp.full(prep.npad, np.float32(prep.f0), jnp.float32)
    vals0 = _grad_vals(margin, prep.y, w)
    for t in range(ntrees):
        vals = vals0 if fault == "state_unchanged" else \
            _grad_vals(margin, prep.y, w)
        node = jnp.zeros(prep.npad, jnp.int32)
        live = np.ones(1, bool)
        for d in range(depth):
            base, L = 2 ** d - 1, 2 ** d
            if live.any():
                gain, _ = gains(prep.level_hist(node, vals, d, stat_dt),
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
        tot = prep.node_sums(node, vals, T, leaf_dt)
        value_a[t] = np.where(tot[:, 0] > 0, leaf_values(tot, lam, lr), 0.0)
        if fault == "altered_answer" and t == 0:
            value_a[0, np.argmax(np.abs(value_a[0]))] *= 1.01
        margin = _add_leaves(margin, node, jnp.asarray(value_a[t]))
    ll, auc = prep.metrics(margin)
    return {"params": dict(params), "f0": prep.f0, "feat": feat_a,
            "bin": bin_a, "is_split": split_a, "value": value_a,
            "edges": prep.edges, "names": prep.names, "logloss": ll,
            "auc": auc}


def prepare(cfg: dict, data: dict) -> Prepared:
    return Prepared(cfg, data)


def compare(cfg: dict, prep: Prepared, result: dict) -> dict:
    numbers = check(prep, result, int(cfg["reference"]["follow_trees"]))
    # the grid the program quantized on against the reference's own: read,
    # never compared (a forest on another grid fails split_gain_gap)
    numbers["edges_gap"] = float(max(
        np.max(np.abs(np.asarray(a) - b) / np.maximum(np.abs(b), 1e-12))
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
