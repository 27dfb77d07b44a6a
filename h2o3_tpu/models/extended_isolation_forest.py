"""H2OExtendedIsolationForestEstimator — EIF anomaly detection.

Reference parity: `h2o-algos/src/main/java/hex/tree/isoforextended/
ExtendedIsolationForest.java` (+ `isolationtree/CompressedExtendedIsolationTree`):
each node splits on a random oblique hyperplane — direction n with
`extension_level`+1 non-zero components, intercept p drawn uniformly inside
the node's projected range; anomaly score 2^(−E[pathlen]/c(sample_size))
exactly as (axis-parallel) IsolationForest. Estimator surface
`h2o-py/h2o/estimators/extended_isolation_forest.py`.

TPU shape: a tree is a static heap of depth ceil(log2(sample_size)); one
level = a (rows × p)·(p) projection per node (gathered per-row direction),
`segment_min/max` for the per-node projected range, and an elementwise
route — the whole forest builds as one vmapped jitted program, no dynamic
node objects.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..frame.frame import Frame
from .metrics import ModelMetricsBase
from .model_base import DataInfo, H2OEstimator, H2OModel


def _avg_path(n):
    """c(n): average unsuccessful-search path length in a BST (IF paper)."""
    n = np.maximum(n, 2.0)
    return 2.0 * (np.log(n - 1.0) + 0.5772156649) - 2.0 * (n - 1.0) / n


@functools.partial(jax.jit, static_argnames=("depth",))
def _build_eif_tree(X, dirs, us, depth: int):
    """Build one EIF tree over the (S, p) subsample.

    dirs: (T, p) random directions (already masked to extension level),
    us: (T,) U(0,1) draws for the intercepts. Returns (thr (T,), dirs,
    is_split (T,), path_len (S,)) where path_len includes the c(size)
    correction at the stopping node.
    """
    S = X.shape[0]
    T = dirs.shape[0]               # internal heap: 2^depth - 1
    Tfull = 2 ** (depth + 1) - 1    # + terminal level
    idx = jnp.zeros(S, jnp.int32)
    alive = jnp.ones(S, bool)
    thr_a = jnp.zeros(T, jnp.float32)
    split_a = jnp.zeros(T, bool)
    count_a = jnp.zeros(Tfull, jnp.float32)  # training rows per node at stop

    for d in range(depth):
        L = 2 ** d
        base = L - 1
        node = base + idx  # heap id per row
        nd = dirs[node]                       # (S, p)
        proj = jnp.sum(X * nd, axis=1)        # (S,)
        big = jnp.float32(3.4e38)
        pmin = jax.ops.segment_min(jnp.where(alive, proj, big),
                                   idx, num_segments=L)
        pmax = jax.ops.segment_max(jnp.where(alive, proj, -big),
                                   idx, num_segments=L)
        cnt = jax.ops.segment_sum(alive.astype(jnp.float32),
                                  idx, num_segments=L)
        can_split = (cnt > 1.0) & (pmax > pmin)
        thr = pmin + us[base : base + L] * (pmax - pmin)
        thr_a = thr_a.at[base : base + L].set(jnp.where(can_split, thr, 0.0))
        split_a = split_a.at[base : base + L].set(can_split)
        # leaf nodes at this level keep their row count (for the c(n) credit)
        count_a = count_a.at[base : base + L].set(jnp.where(can_split, 0.0, cnt))

        node_splits = can_split[idx]
        go_right = alive & node_splits & (proj > thr[idx])
        idx = jnp.where(alive & node_splits,
                        2 * idx + go_right.astype(jnp.int32), idx)
        alive = alive & node_splits

    # terminal level: count rows per cell
    Lf = 2 ** depth
    cnt_f = jax.ops.segment_sum(alive.astype(jnp.float32), idx, num_segments=Lf)
    count_a = count_a.at[Lf - 1 :].set(cnt_f)
    return thr_a, split_a, count_a


@functools.partial(jax.jit, static_argnames=("depth",))
def _score_eif_forest(X, dirs, thrs, splits, counts, depth: int):
    """Path length (depth + c(leaf_size) credit) of each row through every
    tree — (ntrees, N)."""

    def one_tree(dirs_t, thr_t, split_t, count_t):
        N = X.shape[0]
        idx = jnp.zeros(N, jnp.int32)
        depth_stop = jnp.full(N, float(depth), jnp.float32)
        stop_node = jnp.zeros(N, jnp.int32)
        live = jnp.ones(N, bool)
        for d in range(depth):
            L = 2 ** d
            base = L - 1
            node = base + idx
            s = split_t[node]
            proj = jnp.sum(X * dirs_t[node], axis=1)
            stopping = live & ~s
            depth_stop = jnp.where(stopping, jnp.float32(d), depth_stop)
            stop_node = jnp.where(stopping, node, stop_node)
            live = live & s
            go_right = live & (proj > thr_t[node])
            idx = jnp.where(live, 2 * idx + go_right.astype(jnp.int32), idx)
        stop_node = jnp.where(live, 2 ** depth - 1 + idx, stop_node)
        # unresolved-subtree credit: c(n) for leaves holding n>1 training rows
        nleaf = count_t[stop_node]
        credit = jnp.where(
            nleaf > 1.5,
            2.0 * (jnp.log(jnp.maximum(nleaf - 1.0, 1.0)) + 0.5772156649)
            - 2.0 * (nleaf - 1.0) / jnp.maximum(nleaf, 1.0),
            0.0,
        )
        return depth_stop + credit

    return jax.vmap(one_tree)(dirs, thrs, splits, counts)


class ExtendedIsolationForestModel(H2OModel):
    algo = "extendedisolationforest"

    def __init__(self, params, x, dinfo, dirs, thrs, splits, counts, depth, sample_size):
        super().__init__(params)
        self.x = list(x)
        self.y = None
        self.dinfo = dinfo
        self.dirs = dirs          # (ntrees, T, p)
        self.thrs = thrs          # (ntrees, T)
        self.splits = splits      # (ntrees, T)
        self.counts = counts      # (ntrees, 2T+1) training rows per node
        self.depth = depth
        self.sample_size = sample_size

    def predict(self, test_data: Frame) -> Frame:
        X = jnp.asarray(self.dinfo.transform(test_data))
        pl = np.asarray(_score_eif_forest(X, self.dirs, self.thrs, self.splits,
                                          self.counts, self.depth), np.float64)
        mean_length = pl.mean(axis=0)
        score = 2.0 ** (-mean_length / _avg_path(self.sample_size))
        return Frame.from_dict({"anomaly_score": score, "mean_length": mean_length})

    def _make_metrics(self, frame: Frame):
        return self.training_metrics


class H2OExtendedIsolationForestEstimator(H2OEstimator):
    algo = "extendedisolationforest"
    supervised = False
    _param_defaults = dict(
        ntrees=100,
        sample_size=256,
        extension_level=0,
        disable_training_metrics=True,
    )

    def _fit(self, x, y, train: Frame, valid: Optional[Frame]):
        p = self._parms
        dinfo = DataInfo(train, x, standardize=False, use_all_factor_levels=True)
        X = dinfo.fit_transform(train)
        n, pdim = X.shape
        ntrees = int(p.get("ntrees", 100))
        S = min(int(p.get("sample_size", 256)), n)
        depth = max(int(np.ceil(np.log2(max(S, 2)))), 1)
        T = 2 ** depth - 1  # internal heap levels 0..depth-1
        ext = int(p.get("extension_level", 0))
        if not 0 <= ext <= pdim - 1:
            raise ValueError(f"extension_level must be in [0, {pdim-1}]")
        seed = int(self._parms.get("_actual_seed", 1234))
        rng = np.random.default_rng(seed)

        # dispatch all tree builds async; ONE stacked D2H at the end (per-tree
        # np.asarray syncs pay the host↔device round-trip ntrees times)
        dirs_all, thr_dev, split_dev, count_dev = [], [], [], []
        for t in range(ntrees):
            rows = rng.choice(n, size=S, replace=False)
            Xs = jnp.asarray(X[rows])
            d = rng.normal(size=(T, pdim)).astype(np.float32)
            # extension_level e ⇒ e+1 non-zero components per direction
            if ext < pdim - 1:
                mask = np.zeros((T, pdim), np.float32)
                for i in range(T):
                    keep = rng.choice(pdim, size=ext + 1, replace=False)
                    mask[i, keep] = 1.0
                d = d * mask
            d /= np.maximum(np.linalg.norm(d, axis=1, keepdims=True), 1e-12)
            us = rng.uniform(size=T).astype(np.float32)
            thr, split, counts = _build_eif_tree(Xs, jnp.asarray(d),
                                                 jnp.asarray(us), depth)
            dirs_all.append(d)
            thr_dev.append(thr)
            split_dev.append(split)
            count_dev.append(counts)

        model = ExtendedIsolationForestModel(
            self, x, dinfo,
            jnp.asarray(np.stack(dirs_all)),
            jnp.stack(thr_dev), jnp.stack(split_dev), jnp.stack(count_dev),
            depth, S,
        )
        model.training_metrics = ModelMetricsBase(nobs=n)
        return model


ExtendedIsolationForest = H2OExtendedIsolationForestEstimator
