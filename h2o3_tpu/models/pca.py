"""H2OPrincipalComponentAnalysisEstimator (+ SVD) — dimensionality reduction.

Reference parity: `h2o-algos/src/main/java/hex/pca/PCA.java`
(`pca_method` ∈ {GramSVD, Power, GLRM, Randomized}) and `hex/svd/SVD.java`.
GramSVD — the reference default — is exactly the TPU-friendly path: the
(p×p) Gram `X'X` is one device program over the cached standardized matrix
(ISSUE 15: blocked `ordered_axis_fold` partials under the estimator shard
plan, so an N-device Gram is bit-identical to the 1-device forced-shard
lane), then a tiny host-side f64 eigendecomposition of the p×p result —
ONE D2H per fit, not one per step. Randomized projection (Halko) runs as
ONE jitted power-iteration program (sketch → q subspace iterations with
on-device QR → on-device SVD of the small B), replacing the former
host-QR/host-SVD round-trips. ``H2O3_EST_LEGACY=1`` restores the seed
paths; multi-process clouds stay on them.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..frame.frame import Frame
from ..parallel import distdata
from ..parallel import mesh as cloudlib
from . import estimator_engine as _est
from .metrics import ModelMetricsBase
from .model_base import DataInfo, H2OEstimator, H2OModel


def _gram_fn(cloud, shard_mode: str, n_shards: int):
    """(X, w, mu) → (X−μ)'diag(w)... the centered masked Gram as ONE
    device program: `Xc = (X − μ)·w` (w is the real-row mask — zero pad
    rows must not contribute −μ terms), reduced as `local_blocks` ordered
    block partials under the shard plan. "off" mode computes the plain
    `Xc.T @ Xc` the legacy path jitted, bit-comparable."""
    local_blocks, axis = _est.local_plan(cloud, shard_mode, n_shards)
    key = ("pca_gram", local_blocks, axis)

    def build():
        def inner(X, w, mu):
            Xc = (X - mu[None, :]) * w[:, None]
            if local_blocks:
                sl = _est.block_slices(X.shape[0], local_blocks)
                parts = jnp.stack([Xc[s].T @ Xc[s] for s in sl])
                return _est.fold_blocks(parts, axis)
            return Xc.T @ Xc

        if axis is not None:
            rspec = P(cloudlib.ROWS_AXIS)
            inner = cloudlib.shard_call(
                inner, cloud, in_specs=(rspec, rspec, P()),
                out_specs=P(), check_vma=False)
        return jax.jit(inner)

    return _est.cached_program(cloud, key, build)


def _randomized_fn(cloud, q: int):
    """Halko randomized subspace iteration as ONE device program: sketch
    `Y = Xc @ Ω`, q power iterations `Y ← Xc (Xc' Q)` with on-device QR
    re-orthonormalization, then the SVD of the small `B = Q' Xc` — no host
    QR/SVD round-trip per step (ISSUE 15). Zero pad rows produce zero Q
    rows and drop out of B exactly."""

    def build():
        def inner(X, w, mu, Om):
            Xc = (X - mu[None, :]) * w[:, None]
            Y = Xc @ Om
            for _ in range(q):
                Q, _ = jnp.linalg.qr(Y)
                Y = Xc @ (Xc.T @ Q)
            Q, _ = jnp.linalg.qr(Y)
            B = Q.T @ Xc
            _, s, Vt = jnp.linalg.svd(B, full_matrices=False)
            return s, Vt

        return jax.jit(inner)

    return _est.cached_program(cloud, ("pca_randomized", q), build)


class PCAModel(H2OModel):
    algo = "pca"

    def __init__(self, params, x, dinfo, eigenvectors, eigenvalues, k):
        super().__init__(params)
        self.x = list(x)
        self.y = None
        self.dinfo = dinfo
        self.eigenvectors = eigenvectors  # (p, k)
        self.eigenvalues = eigenvalues    # (k,) variances
        self.k = k

    @property
    def importance(self):
        ev = np.asarray(self.eigenvalues, np.float64)
        sd = np.sqrt(np.maximum(ev, 0))
        prop = ev / max(ev.sum(), 1e-300)
        return {
            "Standard deviation": sd,
            "Proportion of Variance": prop,
            "Cumulative Proportion": np.cumsum(prop),
        }

    def predict(self, test_data: Frame) -> Frame:
        X = self.dinfo.transform(test_data)
        if getattr(self, "_demean_mu", None) is not None:
            X = X - self._demean_mu
        scores = X @ np.asarray(self.eigenvectors)
        return Frame.from_dict({f"PC{i+1}": scores[:, i] for i in range(self.k)})

    transform = predict

    def _make_metrics(self, frame: Frame):
        return self.training_metrics


class H2OPrincipalComponentAnalysisEstimator(H2OEstimator):
    algo = "pca"
    supervised = False
    _param_defaults = dict(
        k=1,
        transform="NONE",
        pca_method="GramSVD",
        use_all_factor_levels=False,
        compute_metrics=True,
        impute_missing=True,
        max_iterations=1000,
    )

    def _fit(self, x, y, train: Frame, valid: Optional[Frame]) -> PCAModel:
        p = self._parms
        k = int(p.get("k", 1))
        transform = p.get("transform", "NONE")
        standardize = transform in ("STANDARDIZE", "NORMALIZE")
        use_all = bool(p.get("use_all_factor_levels", False))
        method = p.get("pca_method", "GramSVD")
        cloud = cloudlib.cloud()
        multiproc = distdata.multiprocess()
        engine_on = not _est.legacy() and not multiproc
        shard_mode, n_shards = (_est.shard_plan(cloud.size, multiproc)
                                if engine_on else ("off", 0))
        if shard_mode == "mesh" and (train.nrow < cloud.size
                                     or method == "Randomized"):
            # distributed QR is out of scope — a mesh cloud runs the
            # Randomized sketch on one device like the seed did
            shard_mode, n_shards = "off", 0

        if engine_on:
            from . import dataset_cache as _dc

            cache0 = _dc.snapshot() if _est.cache_enabled() else None
            ndev_eff = cloud.size if shard_mode == "mesh" else 1
            dinfo, X = _est.host_matrix(train, x, standardize=standardize,
                                        use_all=use_all)
            _, Xd = _est.device_matrix(train, x, standardize=standardize,
                                       use_all=use_all, n_shards=n_shards,
                                       n_devices=ndev_eff)
        else:
            cache0 = None
            ndev_eff = 1
            dinfo = DataInfo(train, x, standardize=standardize,
                             use_all_factor_levels=use_all)
            X = dinfo.fit_transform(train)
            Xd = None
        n, pdim = X.shape
        mu = (X.mean(axis=0).astype(np.float32) if transform == "DEMEAN"
              else np.zeros(pdim, np.float32))
        k = min(k, pdim)

        if not engine_on:
            if transform == "DEMEAN":
                X = X - mu
            Xd = jnp.asarray(X)
            if method in ("GramSVD", "GLRM", "Power"):
                gram = np.asarray(jax.jit(lambda X: X.T @ X)(Xd), np.float64) / max(n - 1, 1)
                evals, evecs = np.linalg.eigh(gram)
                order = np.argsort(-evals)
                evals = np.maximum(evals[order][:k], 0)
                evecs = evecs[:, order][:, :k]
            else:  # Randomized (Halko) — sketch on device, QR/SVD on host
                rng = np.random.default_rng(p["_actual_seed"])
                om = jnp.asarray(rng.normal(size=(pdim, min(k + 10, pdim))).astype(np.float32))
                Y = np.asarray(jax.jit(lambda X, om: X @ om)(Xd, om), np.float64)
                Q, _ = np.linalg.qr(Y)
                B = np.asarray(jax.jit(lambda X, Q: Q.T @ X)(Xd, jnp.asarray(Q, jnp.float32)))
                _, s, Vt = np.linalg.svd(B, full_matrices=False)
                evecs = Vt[:k].T
                evals = (s[:k] ** 2) / max(n - 1, 1)
            _est.record_fit("pca", "legacy", n_shards=0, n_devices=1,
                            method=method)
        else:
            npad = int(Xd.shape[0])
            w = np.zeros(npad, np.float32)
            w[:n] = 1.0
            wd = (jax.device_put(jnp.asarray(w), cloud.row_sharding())
                  if ndev_eff > 1 else jnp.asarray(w))
            mud = jnp.asarray(mu)
            t0 = time.perf_counter()
            if method in ("GramSVD", "GLRM", "Power"):
                fn = _gram_fn(cloud, shard_mode, n_shards)
                with _est.iter_phase():
                    gram_d = fn(Xd, wd, mud)
                    cloudlib.collective_fence(gram_d)
                    gram = np.asarray(gram_d, np.float64) / max(n - 1, 1)
                # p×p eigendecomposition on host in f64 — ONE tiny D2H,
                # exactly the legacy numerics
                evals, evecs = np.linalg.eigh(gram)
                order = np.argsort(-evals)
                evals = np.maximum(evals[order][:k], 0)
                evecs = evecs[:, order][:, :k]
                iters = None
            else:  # Randomized — one fused power-iteration program
                rng = np.random.default_rng(p["_actual_seed"])
                l = min(k + 10, pdim)
                om = jnp.asarray(
                    rng.normal(size=(pdim, l)).astype(np.float32))
                q = max(int(os.environ.get("H2O3_PCA_POWER_ITERS", "2")), 0)
                fn = _randomized_fn(cloud, q)
                with _est.iter_phase():
                    s_d, Vt_d = fn(Xd, wd, mud, om)
                    s = np.asarray(s_d, np.float64)
                    Vt = np.asarray(Vt_d, np.float64)
                evecs = Vt[:k].T
                evals = (s[:k] ** 2) / max(n - 1, 1)
                iters = q
            _est.record_fit(
                "pca",
                {"mesh": "fused_mesh", "blocks": "fused_blocks"}.get(
                    shard_mode, "fused"),
                iterations=iters,
                matrix_cache=(_est.matrix_cache_state(cache0)
                              if cache0 is not None else None),
                n_shards=n_shards, n_devices=ndev_eff, method=method,
                wall_s=time.perf_counter() - t0)

        # deterministic sign (largest |loading| positive)
        for j in range(evecs.shape[1]):
            i = np.abs(evecs[:, j]).argmax()
            if evecs[i, j] < 0:
                evecs[:, j] = -evecs[:, j]

        model = PCAModel(self, x, dinfo, evecs, evals, k)
        if transform == "DEMEAN":
            model._demean_mu = mu.astype(np.float64)
        model.training_metrics = ModelMetricsBase(nobs=n)
        return model


PCA = H2OPrincipalComponentAnalysisEstimator
