"""H2OUpliftRandomForestEstimator — uplift random forest.

Reference parity: `h2o-algos/src/main/java/hex/tree/uplift/UpliftDRF.java` +
`hex/tree/uplift/Divergence.java` (`uplift_metric` ∈ {KL, Euclidean,
ChiSquared}: split gain is the weighted divergence between the treatment and
control response distributions after vs before the split), leaf prediction =
p(y|treated) − p(y|control), metrics `hex/ModelMetricsBinomialUplift.java`
(AUUC / Qini). Estimator surface `h2o-py/h2o/estimators/uplift_random_forest.py`.

TPU shape: same heap-tree / histogram design as `tree.py`, but each level
builds TWO histograms (treatment rows, control rows) via the same
`tpu_hist` op with masked weights; the divergence gain is elementwise math
over the two cumulative histograms. Cross-host merge stays `lax.psum`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..frame.binning import build_bins
from ..frame.frame import Frame
from ..ops.histogram import build_histograms
from .metrics import ModelMetricsBase
from .model_base import H2OEstimator, H2OModel
from .shared_tree import frame_to_matrix
from . import tree as treelib

_EPS = 1e-6


def _divergence(pt, pc, metric: str):
    pt = jnp.clip(pt, _EPS, 1 - _EPS)
    pc = jnp.clip(pc, _EPS, 1 - _EPS)
    if metric == "KL":
        return pt * jnp.log(pt / pc) + (1 - pt) * jnp.log((1 - pt) / (1 - pc))
    if metric == "ChiSquared":
        return (pt - pc) ** 2 / pc + ((1 - pt) - (1 - pc)) ** 2 / (1 - pc)
    return (pt - pc) ** 2 + ((1 - pt) - (1 - pc)) ** 2  # Euclidean


@functools.partial(
    jax.jit,
    static_argnames=("max_depth", "nbins", "min_rows", "metric", "axis_name", "mtries"),
)
def build_uplift_tree(
    codes, y, w_t, w_c, edges,
    max_depth: int, nbins: int, min_rows: float = 10.0,
    metric: str = "KL", axis_name: Optional[str] = None,
    mtries: int = 0, key=None,
):
    """One uplift tree. w_t/w_c are row weights masked to treatment/control
    (0 elsewhere — also handles sampling/padding). Leaf value = p_t − p_c."""
    N, F = codes.shape
    T = treelib.heap_size(max_depth)
    feat_a = jnp.zeros(T, jnp.int32)
    bin_a = jnp.zeros(T, jnp.int32)
    thr_a = jnp.zeros(T, jnp.float32)
    split_a = jnp.zeros(T, bool)
    value_a = jnp.zeros(T, jnp.float32)
    idx = jnp.zeros(N, jnp.int32)
    active = jnp.ones(1, bool)
    if key is None:
        key = jax.random.PRNGKey(0)

    for d in range(max_depth + 1):
        L = 2 ** d
        base = L - 1
        ht = build_histograms(codes, idx, y, jnp.zeros_like(y), w_t,
                              L, nbins, axis_name=axis_name)  # {n_t, Σy_t, 0}
        hc = build_histograms(codes, idx, y, jnp.zeros_like(y), w_c,
                              L, nbins, axis_name=axis_name)
        nt = ht[..., 0].sum(axis=2)[:, 0]   # (L,)
        yt = ht[..., 1].sum(axis=2)[:, 0]
        nc = hc[..., 0].sum(axis=2)[:, 0]
        yc = hc[..., 1].sum(axis=2)[:, 0]
        pt_node = yt / jnp.maximum(nt, _EPS)
        pc_node = yc / jnp.maximum(nc, _EPS)
        value_a = value_a.at[base : base + L].set(
            (pt_node - pc_node).astype(jnp.float32)
        )
        if d == max_depth:
            break

        cnt_t, cy_t = jnp.cumsum(ht[..., 0], axis=2), jnp.cumsum(ht[..., 1], axis=2)
        cnt_c, cy_c = jnp.cumsum(hc[..., 0], axis=2), jnp.cumsum(hc[..., 1], axis=2)
        NT, YT = nt[:, None, None], yt[:, None, None]
        NC, YC = nc[:, None, None], yc[:, None, None]
        ptL = cy_t / jnp.maximum(cnt_t, _EPS)
        pcL = cy_c / jnp.maximum(cnt_c, _EPS)
        ptR = (YT - cy_t) / jnp.maximum(NT - cnt_t, _EPS)
        pcR = (YC - cy_c) / jnp.maximum(NC - cnt_c, _EPS)
        nL = cnt_t + cnt_c
        nR = (NT + NC) - nL
        ntot = jnp.maximum(NT + NC, _EPS)
        d_parent = _divergence(pt_node, pc_node, metric)[:, None, None]
        gain = (
            nL / ntot * _divergence(ptL, pcL, metric)
            + nR / ntot * _divergence(ptR, pcR, metric)
            - d_parent
        )
        # both arms must be represented on both sides (UpliftDRF constraint)
        ok = (cnt_t >= min_rows) & (cnt_c >= min_rows)
        ok &= (NT - cnt_t >= min_rows) & (NC - cnt_c >= min_rows)
        ok &= jnp.arange(nbins)[None, None, :] < nbins - 1
        ok &= active[:, None, None]
        if mtries > 0:
            key, sub = jax.random.split(key)
            keep = jax.random.uniform(sub, (L, F)) < (mtries / F)
            keep = keep.at[:, 0].set(keep[:, 0] | ~keep.any(axis=1))
            ok &= keep[:, :, None]
        gain = jnp.where(ok, gain, -jnp.inf)

        flat = gain.reshape(L, F * nbins)
        best = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
        bf = (best // nbins).astype(jnp.int32)
        bb = (best % nbins).astype(jnp.int32)
        do_split = best_gain > 1e-10

        pad_edges = jnp.concatenate(
            [edges.astype(jnp.float32), jnp.full((F, 1), jnp.inf, jnp.float32)], axis=1
        )
        bthr = pad_edges[bf, jnp.minimum(bb, nbins - 2)]
        feat_a = feat_a.at[base : base + L].set(jnp.where(do_split, bf, 0))
        bin_a = bin_a.at[base : base + L].set(jnp.where(do_split, bb, 0))
        thr_a = thr_a.at[base : base + L].set(jnp.where(do_split, bthr, 0.0))
        split_a = split_a.at[base : base + L].set(do_split)

        rf = bf[idx]
        rb = bb[idx]
        rcode = jnp.take_along_axis(codes, rf[:, None].astype(jnp.int32), axis=1)[:, 0]
        go_right = (rcode.astype(jnp.int32) > rb) & do_split[idx]
        idx = 2 * idx + go_right.astype(jnp.int32)
        active = jnp.repeat(do_split, 2)

    return treelib.Tree(feat_a, bin_a, thr_a, split_a, value_a)


def auuc(y: np.ndarray, treat: np.ndarray, uplift: np.ndarray, nbins: int = 1000,
         kind: str = "qini"):
    """AUUC over the qini (or gain) curve — ModelMetricsBinomialUplift's
    thresholded cumulative-uplift design."""
    order = np.argsort(-uplift, kind="mergesort")
    y, treat = y[order], treat[order]
    n = len(y)
    cum_t = np.cumsum(treat)
    cum_c = np.cumsum(1 - treat)
    cum_yt = np.cumsum(y * treat)
    cum_yc = np.cumsum(y * (1 - treat))
    ks = np.unique(np.linspace(1, n, min(nbins, n)).astype(np.int64)) - 1
    with np.errstate(divide="ignore", invalid="ignore"):
        if kind == "qini":
            vals = cum_yt[ks] - cum_yc[ks] * np.where(cum_c[ks] > 0, cum_t[ks] / np.maximum(cum_c[ks], 1), 0)
        else:  # gain
            vals = (cum_yt[ks] / np.maximum(cum_t[ks], 1)
                    - cum_yc[ks] / np.maximum(cum_c[ks], 1)) * (ks + 1)
    vals = np.nan_to_num(vals)
    return float(np.trapezoid(vals, ks + 1) / n), (ks + 1, vals)


@dataclass
class ModelMetricsBinomialUplift(ModelMetricsBase):
    auuc: float = float("nan")
    qini: float = float("nan")
    auuc_normalized: float = float("nan")
    ate: float = float("nan")  # average treatment effect of predictions


class UpliftRandomForestModel(H2OModel):
    algo = "upliftdrf"

    def __init__(self, params, x, y, bm, forest, max_depth, domain, treatment_col):
        super().__init__(params)
        self.x = list(x)
        self.y = y
        self.bm = bm
        self.forest = forest  # stacked Tree (ntrees, T)
        self.max_depth = max_depth
        self.domain = domain
        self.treatment_col = treatment_col
        self.ntrees_built = int(forest.feat.shape[0])

    def _uplift(self, frame: Frame) -> np.ndarray:
        X, _, _ = frame_to_matrix(frame, self.x, expected_domains=self.bm.domains)
        s = treelib.predict_forest_raw(self.forest, jnp.asarray(X, jnp.float32),
                                       self.max_depth)
        return np.asarray(s, np.float64) / self.ntrees_built

    def predict(self, test_data: Frame) -> Frame:
        u = self._uplift(test_data)
        # h2o returns uplift_predict + p_y1_ct1/p_y1_ct0 columns
        return Frame.from_dict({"uplift_predict": u})

    def _make_metrics(self, frame: Frame):
        u = self._uplift(frame)
        yv = frame.vec(self.y)
        y = np.asarray(yv.data, np.float64) if yv.type == "enum" else yv.numeric_np()
        tv = frame.vec(self.treatment_col)
        t = np.asarray(tv.data, np.float64) if tv.type == "enum" else tv.numeric_np()
        a_qini, _ = auuc(y, t, u, kind="qini")
        a_gain, _ = auuc(y, t, u, kind="gain")
        return ModelMetricsBinomialUplift(
            nobs=len(y), auuc=a_qini, qini=a_qini,
            auuc_normalized=a_qini / max(np.abs(u).mean(), 1e-12) if len(y) else float("nan"),
            ate=float(u.mean()),
        )


class H2OUpliftRandomForestEstimator(H2OEstimator):
    algo = "upliftdrf"
    _param_defaults = dict(
        treatment_column=None,
        uplift_metric="AUTO",      # AUTO→KL
        auuc_type="AUTO",
        auuc_nbins=-1,
        ntrees=50,
        max_depth=10,
        min_rows=10.0,
        nbins=20,
        sample_rate=0.632,
        mtries=-2,
        col_sample_rate_per_tree=1.0,
    )

    def _fit(self, x, y, train: Frame, valid: Optional[Frame]):
        p = self._parms
        tcol = p.get("treatment_column")
        if not tcol:
            raise ValueError("upliftdrf requires treatment_column")
        x = [c for c in x if c != tcol]
        yvec = train.vec(y)
        if yvec.type != "enum" or yvec.nlevels != 2:
            raise ValueError("upliftdrf requires a binary categorical response")
        tvec = train.vec(tcol)
        treat = (np.asarray(tvec.data, np.float32) if tvec.type == "enum"
                 else tvec.numeric_np().astype(np.float32))
        yarr = np.asarray(yvec.data, np.float32)
        metric = {"AUTO": "KL", "KL": "KL", "Euclidean": "Euclidean",
                  "ChiSquared": "ChiSquared"}[str(p.get("uplift_metric", "AUTO"))]

        X, is_cat, doms = frame_to_matrix(train, x)
        nbins = int(p.get("nbins", 20))
        # pad bins to a power of two like shared_tree does
        B = 1
        while B < nbins + 2:
            B *= 2
        bm = build_bins(X, nbins=B, names=list(x), is_categorical=is_cat,
                        domains=doms, seed=int(self._parms.get("_actual_seed", 1234)))
        F = X.shape[1]
        edges = np.full((F, B - 2), np.inf, np.float32)
        for j, e in enumerate(bm.edges):
            edges[j, : min(len(e), B - 2)] = e[: B - 2]

        n = train.nrow
        codes_d = jnp.asarray(bm.codes)
        y_d = jnp.asarray(yarr)
        edges_d = jnp.asarray(edges)
        sample_rate = float(p.get("sample_rate", 0.632))
        mtries = int(p.get("mtries", -2))
        if mtries in (-1, -2, 0):
            mtries = max(1, int(np.sqrt(F)))
        ntrees = int(p.get("ntrees", 50))
        seed = int(self._parms.get("_actual_seed", 1234))
        rng = np.random.default_rng(seed)

        # all trees dispatched async; ONE stacked D2H at the end (a per-tree
        # np.asarray sync would pay the host↔device round-trip ntrees times)
        trees_dev: List = []
        for t in range(ntrees):
            samp = (rng.uniform(size=n) < sample_rate).astype(np.float32)
            wt = jnp.asarray(samp * treat)
            wc = jnp.asarray(samp * (1 - treat))
            tr = build_uplift_tree(
                codes_d, y_d, wt, wc, edges_d,
                max_depth=int(p.get("max_depth", 10)), nbins=B,
                min_rows=float(p.get("min_rows", 10.0)), metric=metric,
                mtries=mtries, key=jax.random.PRNGKey(seed + t),
            )
            trees_dev.append(tr)
        stacked_dev = treelib.stack_trees(trees_dev)
        forest = treelib.Tree(*[np.asarray(f) for f in stacked_dev])

        model = UpliftRandomForestModel(
            self, x, y, bm, forest, int(p.get("max_depth", 10)),
            yvec.domain, tcol,
        )
        model.training_metrics = model._make_metrics(train)
        if valid is not None:
            model.validation_metrics = model._make_metrics(valid)
        return model

    def _cv_predict(self, model, frame: Frame) -> np.ndarray:
        return model._uplift(frame)


UpliftDRF = H2OUpliftRandomForestEstimator
