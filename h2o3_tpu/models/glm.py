"""H2OGeneralizedLinearEstimator — GLM.

Reference parity: `h2o-algos/src/main/java/hex/glm/GLM.java` (IRLSM /
L_BFGS / COORDINATE_DESCENT solvers), `hex/glm/GLMTask.java`
(`GLMIterationTask` — the distributed Gram `X'WX` MRTask),
`hex/gram/Gram.java` (Cholesky solve), `hex/DataInfo.java` (standardize /
one-hot — see `model_base.DataInfo`), and the estimator surface
`h2o-py/h2o/estimators/glm.py`. The Airlines-logistic IRLS config is a
BASELINE.json headline.

TPU-first shape of IRLSM: the per-iteration Gram is ONE jitted einsum over
row-sharded X — XLA inserts the `psum` over the ``hosts`` axis automatically
(pjit/GSPMD), which is exactly `GLMIterationTask.reduce()`'s tree-add,
compiled. The tiny (p×p) Cholesky solve happens replicated on-device.
Elastic-net L1 is handled by ISTA (soft-thresholded proximal steps) on the
per-iteration quadratic — the same quadratic COORDINATE_DESCENT minimizes.
Multinomial uses full-batch L-BFGS (optax) on the softmax deviance, the
reference's multinomial L_BFGS path.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from ..frame.frame import Frame
from ..parallel import distdata
from ..parallel import mesh as cloudlib
from ..runtime import qos as _qos
from ..runtime import tracing
from . import estimator_engine as _est
from .metrics import (
    ModelMetricsBinomial,
    ModelMetricsMultinomial,
    ModelMetricsRegression,
)
from .model_base import (SCORE_ROW_BUCKET, DataInfo, H2OEstimator, H2OModel,
                         response_info)

FAMILIES = (
    "AUTO", "gaussian", "binomial", "quasibinomial", "multinomial",
    "poisson", "gamma", "tweedie", "negativebinomial", "ordinal", "fractionalbinomial",
)


# -- link functions (hex/glm/GLMModel.GLMParameters.Link) --------------------
def _linkinv(family: str, eta):
    if family in ("binomial", "quasibinomial", "fractionalbinomial"):
        return jax.nn.sigmoid(eta)
    if family in ("poisson", "gamma", "tweedie", "negativebinomial"):
        return jnp.exp(eta)
    return eta


def _family_deviance_sum(family: str, y, mu, w, tweedie_p=1.5, xp=jnp):
    """Σ w·d(y,μ) with the per-family unit deviance d — the quantity lambda
    search minimizes (hex/glm/GLMModel.GLMParameters.deviance per family;
    squared error only for gaussian). `xp` is jnp (device path) or np (host
    f64 path)."""
    if family in ("binomial", "quasibinomial", "fractionalbinomial"):
        mu_c = xp.clip(mu, 1e-15, 1 - 1e-15)
        return -2.0 * xp.sum(w * (y * xp.log(mu_c)
                                  + (1 - y) * xp.log(1 - mu_c)))
    if family == "poisson":
        mu_c = xp.clip(mu, 1e-10, None)
        ylogy = xp.where(y > 0, y * xp.log(xp.clip(y, 1e-10, None) / mu_c), 0.0)
        return 2.0 * xp.sum(w * (ylogy - (y - mu_c)))
    if family == "gamma":
        mu_c = xp.clip(mu, 1e-10, None)
        y_c = xp.clip(y, 1e-10, None)
        return 2.0 * xp.sum(w * (-xp.log(y_c / mu_c) + (y - mu_c) / mu_c))
    if family == "tweedie":
        p = float(tweedie_p)
        if abs(p - 1.0) < 1e-8:     # limit form: poisson deviance
            return _family_deviance_sum("poisson", y, mu, w, xp=xp)
        if abs(p - 2.0) < 1e-8:     # limit form: gamma deviance
            return _family_deviance_sum("gamma", y, mu, w, xp=xp)
        mu_c = xp.clip(mu, 1e-10, None)
        y_c = xp.clip(y, 0.0, None)
        return 2.0 * xp.sum(w * (
            y_c ** (2 - p) / ((1 - p) * (2 - p))
            - y_c * mu_c ** (1 - p) / (1 - p)
            + mu_c ** (2 - p) / (2 - p)))
    return xp.sum(w * (y - mu) ** 2)


def _irls_weights(family: str, eta, mu, y, tweedie_p=1.5):
    """(W, z): working weights and response for one IRLS iteration."""
    if family in ("binomial", "quasibinomial", "fractionalbinomial"):
        W = jnp.clip(mu * (1 - mu), 1e-10, None)
        z = eta + (y - mu) / W
    elif family == "poisson":
        W = jnp.clip(mu, 1e-10, None)
        z = eta + (y - mu) / W
    elif family == "gamma":
        W = jnp.ones_like(mu)
        z = eta + (y - mu) / jnp.clip(mu, 1e-10, None)
    elif family == "tweedie":
        W = jnp.clip(mu ** (2 - tweedie_p), 1e-10, None)
        z = eta + (y - mu) / jnp.clip(mu, 1e-10, None)  # log link
    else:  # gaussian
        W = jnp.ones_like(mu)
        z = y
    return W, z


@jax.jit
def _wsums(y, w):
    """(Σw, Σw·y) as replicated device scalars — safe on sharded inputs."""
    return jnp.sum(w), jnp.sum(w * y)


@functools.partial(jax.jit, static_argnames=("family", "tweedie_p"))
def _deviance_device(X, y, w, beta, family: str, tweedie_p: float):
    eta = jnp.matmul(X, beta, precision=jax.lax.Precision.HIGHEST)
    mu = _linkinv(family, eta)
    return _family_deviance_sum(family, y, mu, w, tweedie_p)


@functools.partial(jax.jit, static_argnames=("family", "tweedie_p"))
def _pearson_sums(X, y, w, beta, family: str, tweedie_p: float):
    """(Σ w·(y−μ)²/V(μ), Σw) — the Pearson X² pieces of the dispersion
    estimate as jit-global reductions (safe on row-sharded X)."""
    eta = jnp.matmul(X, beta, precision=jax.lax.Precision.HIGHEST)
    mu = _linkinv(family, eta)
    if family == "gamma":
        vfun = jnp.maximum(mu, 1e-12) ** 2
    elif family == "tweedie":
        vfun = jnp.maximum(mu, 1e-12) ** tweedie_p
    else:
        vfun = jnp.ones_like(mu)
    return jnp.sum(w * (y - mu) ** 2 / vfun), jnp.sum(w)


# Every IRLS matmul runs at HIGHEST precision: the TPU default rounds f32
# matmul inputs to bf16, which the forced-CPU suites cannot see. On the chip
# it moved the fused IRLS fit's coefficients by 2.4e-3 between the mesh's
# blocked gemm and the one-device einsum (PR 21, four-chip smoke) — the
# Gram is p×p, so the extra passes cost nothing next to reading X.
_HI = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("family",))
def _gram_step(X, y, w, beta, family: str, tweedie_p: float = 1.5):
    """One GLMIterationTask: distributed Gram X'WX and X'Wz (+ psum by XLA
    when X is row-sharded)."""
    eta = jnp.matmul(X, beta, precision=_HI)
    mu = _linkinv(family, eta)
    W, z = _irls_weights(family, eta, mu, y, tweedie_p)
    Ww = W * w
    gram = jnp.einsum("np,n,nq->pq", X, Ww, X, precision=_HI)
    xy = jnp.einsum("np,n->p", X, Ww * z, precision=_HI)
    return gram, xy


def _solve_pen_device(gram, xy, lam, alpha, n_obs, pen_mask, beta_prev,
                      non_negative: bool):
    """Penalized IRLS-quadratic solve ON DEVICE — Cholesky for ridge,
    500-step projected ISTA when l1>0 or non_negative (the same quadratic
    COORDINATE_DESCENT iterates on). Shared by the lambda-path program and
    the fused single-lambda IRLS loop so the two can never drift."""
    pdim = gram.shape[0]
    l2 = lam * (1.0 - alpha) * n_obs
    l1 = lam * alpha * n_obs
    A = gram + jnp.diag(pen_mask * l2)

    def ridge(_):
        return jnp.linalg.solve(
            A + 1e-6 * jnp.eye(pdim, dtype=jnp.float32), xy)

    def ista(_):
        L = jnp.linalg.eigvalsh(A)[-1] + 1e-8
        thr = l1 / L * pen_mask

        def body(i, b):
            b_new = b - (A @ b - xy) / L
            b_new = jnp.sign(b_new) * jnp.maximum(
                jnp.abs(b_new) - thr, 0.0)
            if non_negative:
                b_new = b_new.at[:pdim - 1].set(
                    jnp.maximum(b_new[:pdim - 1], 0.0))
            return b_new

        return jax.lax.fori_loop(0, 500, body, beta_prev)

    return jax.lax.cond((l1 > 0) | non_negative, ista, ridge, None)


@functools.partial(jax.jit, static_argnames=("family", "max_iter",
                                              "non_negative", "tweedie_p"))
def _glm_path_device(X, y, w, Xe, ye, we, lams, alpha, n_obs, beta0,
                     beta_eps, tweedie_p, family: str, max_iter: int,
                     non_negative: bool):
    """The WHOLE elastic-net regularization path as one XLA program.

    lax.scan over λ (warm-started), lax.while_loop IRLS per λ, penalized
    solve on device (Cholesky for ridge, 500-step projected ISTA when
    l1>0), deviance evaluated against (Xe, ye, we) — the validation set
    when given, else training. Replaces ~nlambda·iters host round-trips
    (gram D2H + host solve each) with ONE dispatch; the caller re-solves
    the chosen λ on host in f64 for the reported coefficients
    (hex/glm/GLM.java lambda search, computeSubmodel loop). For gaussian
    the IRLS weights don't depend on β, so the Gram/xy are computed ONCE
    and reused across the whole path (ISSUE 15 warm-start contract) —
    same values every iteration recomputed before, at ~1/iters the
    einsum cost."""
    pdim = X.shape[1]
    pen_mask = jnp.ones(pdim, jnp.float32).at[pdim - 1].set(0.0)

    def solve_pen(gram, xy, lam, beta_prev):
        return _solve_pen_device(gram, xy, lam, alpha, n_obs, pen_mask,
                                 beta_prev, non_negative)

    if family == "gaussian":
        Wg = jnp.ones_like(y) * w
        gram_g = jnp.einsum("np,n,nq->pq", X, Wg, X,
                            precision=jax.lax.Precision.HIGHEST)
        xy_g = jnp.einsum("np,n->p", X, Wg * y,
                          precision=jax.lax.Precision.HIGHEST)

    def deviance(beta):
        eta = jnp.matmul(Xe, beta, precision=jax.lax.Precision.HIGHEST)
        mu = _linkinv(family, eta)
        return _family_deviance_sum(family, ye, mu, we, tweedie_p)

    def fit_one(beta, lam):
        def cond(state):
            it, b, delta = state
            return (it < max_iter) & (delta >= beta_eps)

        def body(state):
            it, b, _ = state
            if family == "gaussian":
                gram, xy = gram_g, xy_g
            else:
                eta = jnp.matmul(X, b, precision=jax.lax.Precision.HIGHEST)
                mu = _linkinv(family, eta)
                W, z = _irls_weights(family, eta, mu, y, tweedie_p)
                Ww = W * w
                gram = jnp.einsum("np,n,nq->pq", X, Ww, X,
                                  precision=jax.lax.Precision.HIGHEST)
                xy = jnp.einsum("np,n->p", X, Ww * z,
                                precision=jax.lax.Precision.HIGHEST)
            nb = solve_pen(gram, xy, lam, b)
            return it + 1, nb, jnp.max(jnp.abs(nb - b))

        _, beta, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), beta, jnp.float32(jnp.inf)))
        # f32 divergence guard: a non-finite β would NaN-poison the
        # warm-start carry for every later λ — reset instead, and report
        # +inf deviance so this λ can never be selected
        ok = jnp.isfinite(beta).all()
        beta = jnp.where(ok, beta, jnp.zeros_like(beta))
        dev = jnp.where(ok, deviance(beta), jnp.float32(jnp.inf))
        return beta, (beta, dev)

    _, (betas, devs) = jax.lax.scan(fit_one, beta0, lams)
    return betas, devs


def _block_partials(X, Ww, z, local_blocks: int):
    """(local_blocks, P, P+1): a lane's share of the Gram, one partial per
    ordered row block. ONE augmented gemm per block — (WwX)' @ [X | z]
    yields gram AND xy from the same dot: the gemm-shaped form lowers
    identically inside a lane's shard_map body and inside the S-block
    single-device program (a separate gemv for xy did NOT — its accumulation
    fused differently per context), which is what makes blocks==mesh
    bit-identical."""
    Xw = X * Ww[:, None]
    Xz = jnp.concatenate([X, z[:, None]], axis=1)
    return jnp.stack([jnp.matmul(Xw[s].T, Xz[s], precision=_HI)
                      for s in _est.block_slices(X.shape[0], local_blocks)])


def _irls_device_fn(cloud, shard_mode: str, n_shards: int, family: str,
                    non_negative: bool, one_step: bool):
    """The fused single-λ IRLS fit as ONE device program (ISSUE 15):
    `lax.while_loop` with the convergence test (max|Δβ| < β_eps) ON
    DEVICE — the host reads only the final (β, iterations, Δ) triple,
    replacing the per-iteration gram D2H + host solve round-trip.

    Row reductions (the Gram X'WX and X'Wz) run as `local_blocks` ordered
    block partials merged by `ordered_axis_fold` under the shard plan —
    mesh-sharded on a multi-device cloud, the same blocked structure
    forced on one device — so an N-device IRLS fit is bit-identical to
    the 1-device forced-shard lane (the PR 9 contract). `one_step` marks
    gaussian with α·λ = 0, whose single solve mirrors the host loop's
    unconditional gaussian break — including under non_negative, where
    both paths do exactly one projected-ISTA pass; plain gaussian hoists
    the β-independent Gram out of the loop. Cached per cloud via the
    engine program cache."""
    local_blocks, axis = _est.local_plan(cloud, shard_mode, n_shards)
    key = ("glm_irls", family, local_blocks, axis, bool(non_negative),
           bool(one_step))

    def build():
        # carry (it, beta, delta) enters as traced arguments and cond gains
        # `it < stop_at`, so the QoS gate can run the fit as a resumable
        # sequence of bounded segments (est.segment_stops) — stop_at =
        # max_iter is the single-dispatch identity (same trip count, same
        # body, same bits; pinned). The gaussian Gram hoist is β-independent,
        # so recomputing it per segment is also bit-identical.
        def inner(X, y, w, beta0, it0, delta0, lam, alpha, n_obs, max_iter,
                  stop_at, beta_eps, tweedie_p):
            pdim = X.shape[1]
            pen_mask = jnp.ones(pdim, jnp.float32).at[pdim - 1].set(0.0)

            # named scopes: the op metadata a trace viewer shows says which
            # fusion is the Gram and which the solve; the PROGRAM's name
            # (`jit_inner`) is what the benchmark's readers key on
            @jax.named_scope("irls.gram")
            def gram_xy(b):
                if local_blocks:
                    # a float32 multiply and a sum along each row, whose
                    # order no row count changes: a matvec picks its kernel
                    # by the lane's rows, and blocks != mesh in eta's last
                    # bit (seen on the CPU's eight devices)
                    eta = jnp.sum(X * b[None, :], axis=1)
                else:
                    eta = jnp.matmul(X, b, precision=_HI)
                mu = _linkinv(family, eta)
                W, z = _irls_weights(family, eta, mu, y, tweedie_p)
                Ww = W * w
                if local_blocks:
                    parts = _block_partials(X, Ww, z, local_blocks)
                    # the all-gather of the lanes' partials and the ordered
                    # left-to-right sum (on one device: the sum alone)
                    with jax.named_scope("irls.fold"):
                        gz = _est.fold_blocks(parts, axis)
                    return gz[:, :-1], gz[:, -1]
                return (jnp.einsum("np,n,nq->pq", X, Ww, X, precision=_HI),
                        jnp.einsum("np,n->p", X, Ww * z, precision=_HI))

            @jax.named_scope("irls.solve")
            def solve(gram, xy, bprev):
                return _solve_pen_device(gram, xy, lam, alpha, n_obs,
                                         pen_mask, bprev, non_negative)

            if one_step or family == "gaussian":
                gram_g, xy_g = gram_xy(beta0)   # gaussian: β-independent
            if one_step:
                beta = solve(gram_g, xy_g, beta0)
                return beta, jnp.int32(1), jnp.max(jnp.abs(beta - beta0))

            def cond(state):
                it, b, delta = state
                return (it < max_iter) & (delta >= beta_eps) & (it < stop_at)

            def body(state):
                it, b, _ = state
                gram, xy = ((gram_g, xy_g) if family == "gaussian"
                            else gram_xy(b))
                nb = solve(gram, xy, b)
                return it + 1, nb, jnp.max(jnp.abs(nb - b))

            it, beta, delta = jax.lax.while_loop(
                cond, body, (it0, beta0, delta0))
            return beta, it, delta

        if axis is not None:
            rspec = P(cloudlib.ROWS_AXIS)
            rep = P()
            inner = cloudlib.shard_call(
                inner, cloud,
                in_specs=(rspec, rspec, rspec) + (rep,) * 10,
                out_specs=(rep, rep, rep), check_vma=False)
        return jax.jit(inner)

    return _est.cached_program(cloud, key, build)


def _solve_penalized(gram, xy, lam, alpha, n_obs, intercept_idx, beta0,
                     non_negative=False):
    """Solve the IRLS quadratic with elastic-net penalty (host, p×p).

    Ridge part closed-form via Cholesky; L1 (and the non_negative
    constraint, used by the StackedEnsemble metalearner) via projected ISTA
    on the quadratic — the same subproblem hex/glm COORDINATE_DESCENT
    iterates on."""
    p = gram.shape[0]
    pen_mask = np.ones(p)
    pen_mask[intercept_idx] = 0.0  # intercept is never penalized
    l2 = lam * (1 - alpha) * n_obs
    l1 = lam * alpha * n_obs
    A = gram + np.diag(pen_mask * l2)
    if l1 == 0 and not non_negative:
        try:
            return np.linalg.solve(A + 1e-8 * np.eye(p), xy)
        except np.linalg.LinAlgError:
            return np.linalg.lstsq(A, xy, rcond=None)[0]
    # (projected) ISTA
    L = np.linalg.eigvalsh(A).max() + 1e-8
    b = beta0.copy()
    for _ in range(500):
        grad = A @ b - xy
        b_new = b - grad / L
        thr = l1 / L * pen_mask
        b_new = np.sign(b_new) * np.maximum(np.abs(b_new) - thr, 0)
        if non_negative:
            b_new[:intercept_idx] = np.maximum(b_new[:intercept_idx], 0.0)
        if np.max(np.abs(b_new - b)) < 1e-9:
            b = b_new
            break
        b = b_new
    return b


def attach_linear_artifacts(model: "GLMModel", train, valid, Xd,
                            cloud_size: int, n: int) -> "GLMModel":
    """Training/validation metrics + |coefficient| varimp for a fitted
    linear model — shared by GLM and the XGBoost gblinear booster.

    Reuses the training design matrix already in HBM for training metrics:
    on one device when it holds the frame's rows and no more, and on a
    one-process mesh always — there a second, unsharded design may be what
    no single chip holds, every shard is addressable, and the zero-weight
    pad rows sit at the tail, which `_make_metrics` slices off on the host.
    A multi-process Xd spans devices this process cannot read."""
    reuse = (int(Xd.shape[0]) == n if cloud_size == 1
             else not distdata.multiprocess())
    with tracing.span("fit.metrics", kind="fit"):
        model.training_metrics = model._make_metrics(
            train, Xd=Xd if reuse else None)
        if valid is not None:
            model.validation_metrics = model._make_metrics(valid)
        # GLM varimp = |standardized coefficient| magnitudes
        beta = model.beta
        b = np.asarray(beta if model.family != "multinomial"
                       else np.abs(beta).mean(axis=0))
        mags = np.abs(b[:-1])
        if mags.sum() > 0:
            order = np.argsort(-mags)
            model.varimp_table = [
                (model.dinfo.coef_names[i], float(mags[i]),
                 float(mags[i] / mags.max()), float(mags[i] / mags.sum()))
                for i in order if mags[i] > 0]
    return model


class GLMModel(H2OModel):
    algo = "glm"

    def __init__(self, params, x, y, dinfo: DataInfo, family, beta, domain,
                 lambda_best=0.0, stderr=None, full_path=None):
        super().__init__(params)
        self.x = list(x)
        self.y = y
        self.dinfo = dinfo
        self.family = family
        self.beta = beta  # (p+1,) with intercept last, or (K, p+1) multinomial
        self.domain = domain
        self.lambda_best = lambda_best
        self.stderr = stderr
        self.full_path = full_path  # lambda-search path [(lam, beta), ...]

    def _names(self) -> List[str]:
        return self.dinfo.coef_names + ["Intercept"]

    def coef(self) -> Dict[str, float]:
        """De-standardized coefficients (GLMModel.coefficients)."""
        if self.family == "multinomial":
            return {
                f"{cls}": dict(zip(self._names(), self._destandardize(self.beta[k])))
                for k, cls in enumerate(self.domain)
            }
        return dict(zip(self._names(), self._destandardize(self.beta)))

    def coef_norm(self) -> Dict[str, float]:
        if self.family == "multinomial":
            return {
                f"{cls}": dict(zip(self._names(), np.asarray(self.beta[k])))
                for k, cls in enumerate(self.domain)
            }
        return dict(zip(self._names(), np.asarray(self.beta)))

    def summary(self):
        s = super().summary()
        # intercepts excluded; a multinomial predictor counts once if active
        # in ANY class (matches the total's per-predictor granularity)
        if self.family == "multinomial":
            slopes = np.abs(np.asarray(self.beta)[:, :-1]).max(axis=0)
        else:
            slopes = np.abs(np.asarray(self.beta)[:-1])
        s.update(family=self.family,
                 number_of_predictors_total=len(self.dinfo.coef_names),
                 number_of_active_predictors=int((slopes > 1e-10).sum()),
                 lambda_=self.lambda_best)
        return s

    def coef_with_p_values(self):
        """Coefficient table with std errors / z / p-values on the DATA scale
        (matches coef()) — requires compute_p_values=True and lambda=0
        (GLMModel p-value output)."""
        if self.stderr is None:
            raise ValueError(
                "p-values unavailable: train with compute_p_values=True "
                "and lambda_=0")
        b = np.asarray(self.beta, np.float64)
        pdim = len(b) - 1
        cov = getattr(self, "covmat", None)
        if self.dinfo.standardize and self.dinfo.means is not None and cov is not None:
            # affine destandardization T: slope_j /= σ_j, intercept absorbs
            # −Σ β_j μ_j/σ_j; covariance transforms as T Cov Tᵀ
            T = np.zeros((pdim + 1, pdim + 1))
            T[np.arange(pdim), np.arange(pdim)] = 1.0 / self.dinfo.stds
            T[pdim, :pdim] = -self.dinfo.means / self.dinfo.stds
            T[pdim, pdim] = 1.0
            b = T @ b
            se = np.sqrt(np.maximum(np.diag(T @ cov @ T.T), 0.0))
        else:
            b = self._destandardize(b)
            se = np.asarray(self.stderr, np.float64)
        z = b / np.maximum(se, 1e-300)
        # two-sided normal p-value (the reference uses z-tests for binomial)
        from math import erfc, sqrt

        pv = [erfc(abs(zz) / sqrt(2.0)) for zz in z]
        return [
            dict(names=n, coefficients=float(bb), std_error=float(s),
                 z_value=float(zz), p_value=float(p))
            for n, bb, s, zz, p in zip(self._names(), b, se, z, pv)
        ]

    def _destandardize(self, b):
        b = np.asarray(b, np.float64)
        if not self.dinfo.standardize or self.dinfo.means is None:
            return b
        out = b.copy()
        out[:-1] = b[:-1] / self.dinfo.stds
        out[-1] = b[-1] - float((b[:-1] * self.dinfo.means / self.dinfo.stds).sum())
        return out

    def _eta_dev(self, frame: Frame, Xd=None):
        """Linear predictor as a DEVICE array. Expansion + matvec run on
        device (compact upload, see DataInfo.device_design); `Xd` lets the
        training loop reuse its HBM design matrix for training metrics.
        HIGHEST matmul precision keeps f32 logits exact (the TPU default
        truncates matmul operands to bf16)."""
        if Xd is None:
            # row-bucketed scoring design: CV folds / paged frames of
            # nearby sizes share one expand + one matmul program. The
            # result may carry up to 511 PAD ROWS — callers slice to
            # frame.nrow on the HOST after materializing (a device-side
            # slice would reintroduce one tiny program per exact size,
            # defeating the bucket)
            Xd = self.dinfo.device_design(frame, fit=False,
                                          add_intercept=True,
                                          row_bucket=SCORE_ROW_BUCKET)
        beta = jnp.asarray(np.asarray(self.beta, np.float32))
        return jnp.matmul(Xd, beta.T, precision=jax.lax.Precision.HIGHEST)

    def _eta(self, frame: Frame, Xd=None) -> np.ndarray:
        # host-side slice drops any row-bucket pad (see _eta_dev)
        return np.asarray(self._eta_dev(frame, Xd=Xd),
                          np.float64)[: frame.nrow]

    def _score_dev(self, frame: Frame, Xd=None):
        """Fitted means as a DEVICE array (link inverse applied on device;
        may carry row-bucket pad rows, see _eta_dev)."""
        eta = self._eta_dev(frame, Xd=Xd)
        if self.family == "multinomial":
            return jax.nn.softmax(eta, axis=1)
        return _linkinv(self.family, eta)

    def _score(self, frame: Frame, Xd=None) -> np.ndarray:
        # ONE n-sized transfer per scoring; the host-side slice drops any
        # row-bucket pad (see _eta_dev)
        return np.asarray(self._score_dev(frame, Xd=Xd),
                          np.float64)[: frame.nrow]

    def predict(self, test_data: Frame) -> Frame:
        out = self._score(test_data)
        if self.family in ("binomial", "quasibinomial"):
            p1 = out
            d = {"predict": np.asarray(self.domain, dtype=object)[(p1 > 0.5).astype(int)],
                 str(self.domain[0]): 1 - p1, str(self.domain[1]): p1}
            return Frame.from_dict(d, column_types={"predict": "enum"})
        if self.family == "multinomial":
            lab = out.argmax(axis=1)
            d = {"predict": np.asarray(self.domain, dtype=object)[lab]}
            for i, cls in enumerate(self.domain):
                d[str(cls)] = out[:, i]
            return Frame.from_dict(d, column_types={"predict": "enum"})
        return Frame.from_dict({"predict": out})

    def _make_metrics(self, frame: Frame, Xd=None):
        mu_dev = self._score_dev(frame, Xd=Xd)
        with tracing.span("metrics.d2h", kind="fit"):
            # the wait for the scoring program and the n-sized transfer
            out = np.asarray(mu_dev)[: frame.nrow]
        yv = frame.vec(self.y)
        if self.family in ("binomial", "quasibinomial"):
            # as the device made them: `make` widens into its own buffer
            return ModelMetricsBinomial.make(np.asarray(yv.data), out)
        out = np.asarray(out, np.float64)
        if self.family == "multinomial":
            return ModelMetricsMultinomial.make(np.asarray(yv.data), out)
        return ModelMetricsRegression.make(yv.numeric_np(), out)


class H2OGeneralizedLinearEstimator(H2OEstimator):
    algo = "glm"
    _param_defaults = dict(
        family="AUTO",
        solver="AUTO",
        alpha=None,
        lambda_=None,
        lambda_search=False,
        nlambdas=-1,
        lambda_min_ratio=-1.0,
        standardize=True,
        intercept=True,
        non_negative=False,
        max_iterations=-1,
        beta_epsilon=1e-4,
        objective_epsilon=-1.0,
        gradient_epsilon=-1.0,
        link="family_default",
        tweedie_variance_power=0.0,
        tweedie_link_power=1.0,
        theta=1e-10,
        missing_values_handling="MeanImputation",
        compute_p_values=False,
        remove_collinear_columns=False,
        balance_classes=False,
        class_sampling_factors=None,
        max_after_balance_size=5.0,
        prior=-1.0,
        cold_start=False,
        interactions=None,
        beta_constraints=None,
    )

    def _fit(self, x, y, train: Frame, valid: Optional[Frame]) -> GLMModel:
        p = self._parms
        yvec = train.vec(y)
        problem, nclass, domain = response_info(yvec)
        family = p.get("family", "AUTO")
        if family not in FAMILIES:
            raise ValueError(f"family {family!r}: expected one of {FAMILIES}")
        for av in np.atleast_1d(np.asarray(p.get("alpha")
                                           if p.get("alpha") is not None
                                           else 0.5, np.float64)):
            if not (0.0 <= av <= 1.0):
                raise ValueError(f"alpha must be in [0, 1], got {av}")
        lam = p.get("lambda_")
        if lam is not None:
            for lv in np.atleast_1d(np.asarray(lam, np.float64)):
                if lv < 0:
                    raise ValueError(f"lambda must be >= 0, got {lv}")
        if family == "AUTO":
            family = {"binomial": "binomial", "multinomial": "multinomial"}.get(
                problem, "gaussian"
            )
        std_flag = bool(p.get("standardize", True))
        n = train.nrow
        with tracing.span("fit.response", kind="fit"):
            w = (train.vec(p["weights_column"]).numeric_np().astype(np.float32)
                 if p.get("weights_column") else np.ones(n, np.float32))

            if family in ("binomial", "quasibinomial", "fractionalbinomial"):
                yarr = np.asarray(yvec.data, np.float32) if yvec.type == "enum" else yvec.numeric_np().astype(np.float32)
            elif family == "multinomial":
                yarr = np.asarray(yvec.data, np.int32)
            else:
                yarr = yvec.numeric_np().astype(np.float32)
            yd = jnp.asarray(yarr if family != "multinomial" else yarr.astype(np.float32))
            wd = jnp.asarray(w)

        alpha = p.get("alpha")
        alpha = float(alpha[0] if isinstance(alpha, (list, tuple)) else (alpha if alpha is not None else 0.5))
        lam = p.get("lambda_")
        lambda_search = bool(p.get("lambda_search"))
        tweedie_p = float(p.get("tweedie_variance_power") or 1.5)
        max_iter = int(p.get("max_iterations", -1))
        if max_iter <= 0:
            max_iter = 50
        beta_eps = float(p.get("beta_epsilon", 1e-4))

        cloud = cloudlib.cloud()
        multiproc = distdata.multiprocess()
        # -- estimator-engine dispatch (ISSUE 15 / ISSUE 18) ------------------
        # engine on: cached standardized design (one upload per sweep) +
        # fused whole-fit IRLS; gated off for the exotic corners — legacy
        # comparator and the mesh path for multinomial / degenerate row
        # counts. Multi-process clouds run the pod mesh lane (ISSUE 18:
        # canonical global layout, blocked Gram fold over the pod mesh)
        # for plain single-λ fits; lambda_search and multinomial keep the
        # pre-engine multi-process paths.
        engine_on = not _est.legacy() and not multiproc
        shard_mode, n_shards = (_est.shard_plan(cloud.size, multiproc)
                                if (engine_on or multiproc) else ("off", 0))
        n_glob = n
        if multiproc:
            n_glob = int(getattr(train, "dist").global_nrow
                         if getattr(train, "dist", None) else
                         distdata.global_sum(np.asarray([n]))[0])
        if shard_mode == "mesh" and (n_glob < cloud.size
                                     or family == "multinomial"
                                     or (multiproc and lambda_search)):
            shard_mode, n_shards = "off", 0
        pod = multiproc and shard_mode == "mesh"
        use_cached_design = engine_on and (cloud.size == 1
                                           or shard_mode == "mesh")
        y_host_fit, w_host_fit = yarr, w
        cache0 = None
        if use_cached_design:
            from . import dataset_cache as _dc

            cache0 = _dc.snapshot()
        if multiproc:
            with tracing.span("fit.design", kind="fit", cache="off"):
                dinfo = DataInfo(train, x, standardize=std_flag)
                # multi-host cloud: this process holds only its ingest shard —
                # assemble global row-sharded arrays homed where the data was
                # parsed (MRTask compute-where-the-chunks-live), zero-weight
                # padding balancing unequal byte ranges
                X = dinfo.fit_transform(train)      # standardization stats are
                #                                     global (DataInfo collective)
                Xi = np.concatenate([X, np.ones((n, 1), np.float32)], axis=1)
                y_f32 = np.asarray(yarr, np.float32)
                if pod:
                    # ISSUE 18 pod lane: relayout the ingest shards onto the
                    # CANONICAL padded grid the 1-device forced-shard
                    # comparator uses (pad_rows(n_global, S), all pad at the
                    # global tail), so the blocked Gram fold groups identical
                    # f32 partials in the identical order — bit-identical β.
                    # Rows move only at slice boundaries (exchange_rows); no
                    # rank ever materializes the global design matrix.
                    _counts = distdata.row_counts(n)
                    npad = _est.pad_rows(n_glob, n_shards)
                    quota = npad // jax.process_count()
                    Xd = distdata.global_row_array(
                        distdata.to_canonical(Xi.astype(np.float32), npad,
                                              counts=_counts), quota, cloud)
                    yd = distdata.global_row_array(
                        distdata.to_canonical(y_f32, npad, counts=_counts),
                        quota, cloud)
                    wd = distdata.global_row_array(
                        distdata.to_canonical(w, npad, counts=_counts),
                        quota, cloud)
                    # exact global response/weight columns (rank order =
                    # global ingest order) for the host f64 β₀ init sums — a
                    # psum of per-rank partials would not be bitwise the
                    # comparator's single np.sum
                    y_host_fit = distdata.allgather_rows(y_f32)
                    w_host_fit = distdata.allgather_rows(w)
                else:
                    quota = distdata.local_quota(n)
                    Xd = distdata.global_row_array(
                        Xi.astype(np.float32), quota, cloud)
                    yd = distdata.global_row_array(y_f32, quota, cloud)
                    wd = distdata.global_row_array(w, quota, cloud)
                n = n_glob
        elif use_cached_design:
            ndev_eff = cloud.size if shard_mode == "mesh" else 1
            dinfo, Xd = _est.design_matrix(
                train, x, standardize=std_flag, add_intercept=True,
                n_shards=n_shards, n_devices=ndev_eff)
            npad = int(Xd.shape[0])
            if npad != n or ndev_eff > 1:
                # the response and the weights on the design's grid: zero
                # weight on the pad rows, a lane's rows on its own device
                with tracing.span("fit.response", kind="fit"):
                    ypad, wpad = np.asarray(yarr, np.float32), w
                    if npad != n:
                        tail = np.zeros(npad - n, np.float32)
                        ypad = np.concatenate([ypad, tail])
                        wpad = np.concatenate([wpad, tail])
                    if ndev_eff > 1:
                        rs = cloud.row_sharding()
                        yd = jax.device_put(ypad, rs)
                        wd = jax.device_put(wpad, rs)
                    else:
                        yd, wd = jnp.asarray(ypad), jnp.asarray(wpad)
        else:
            with tracing.span("fit.design", kind="fit", cache="off"):
                if cloud.size > 1 and n >= cloud.size:
                    dinfo = DataInfo(train, x, standardize=std_flag)
                    X = dinfo.fit_transform(train)
                    Xi = np.concatenate([X, np.ones((n, 1), np.float32)], axis=1)
                    npad = cloudlib.pad_to_multiple(n, cloud.size)
                    padn = npad - n
                    Xd = jnp.asarray(np.concatenate([Xi, np.zeros((padn, Xi.shape[1]), np.float32)]))
                    yd = jnp.asarray(np.concatenate([np.asarray(yd), np.zeros(padn, np.float32)]))
                    wd = jnp.asarray(np.concatenate([w, np.zeros(padn, np.float32)]))
                    rs = cloud.row_sharding()
                    Xd, yd, wd = jax.device_put(Xd, rs), jax.device_put(yd, rs), jax.device_put(wd, rs)
                else:
                    # compact upload + on-device one-hot expansion (the dense design
                    # matrix never crosses the host↔device link)
                    dinfo = DataInfo(train, x, standardize=std_flag)
                    Xd = dinfo.device_design(train, fit=True, add_intercept=True)
        nfeat = len(dinfo.coef_names)
        fitplan: Dict[str, object] = dict(path="legacy")

        full_path = None
        stderr = None
        cov = None
        if family == "multinomial":
            beta = self._fit_multinomial(Xd, yarr, wd, nclass, alpha,
                                         lam or 0.0, max_iter, n_global=n)
            lam_best = lam or 0.0
        else:
            if lambda_search:
                vdata = None
                if valid is not None:
                    Xv = dinfo.transform(valid)
                    Xvi = np.concatenate(
                        [Xv, np.ones((Xv.shape[0], 1), np.float32)], axis=1)
                    yvv = valid.vec(y)
                    if yvv.type == "enum":
                        codes_v = np.asarray(yvv.data, np.int64)
                        if yvv.domain != domain and yvv.domain:
                            # remap to the TRAINING response domain
                            lookup = {d: i for i, d in enumerate(domain or [])}
                            remap = np.asarray(
                                [lookup.get(d, -1) for d in yvv.domain], np.int64)
                            codes_v = np.where(codes_v >= 0,
                                               remap[np.maximum(codes_v, 0)], -1)
                        yva = codes_v.astype(np.float32)
                    else:
                        yva = yvv.numeric_np().astype(np.float32)
                    wv = (valid.vec(p["weights_column"]).numeric_np()
                          if p.get("weights_column")
                          and p["weights_column"] in valid.names
                          else np.ones(Xv.shape[0])).astype(np.float32)
                    if distdata.multiprocess():
                        # each process holds its valid shard; zero-weight
                        # pads drop out of the (jit-global) deviance sums,
                        # so lambda selection is consistent on every rank
                        quota_v = distdata.local_quota(Xv.shape[0])
                        vdata = (
                            distdata.global_row_array(
                                Xvi.astype(np.float32), quota_v, cloud),
                            distdata.global_row_array(
                                yva.astype(np.float32), quota_v, cloud),
                            distdata.global_row_array(
                                wv.astype(np.float32), quota_v, cloud),
                        )
                    else:
                        vdata = (jnp.asarray(Xvi), jnp.asarray(yva),
                                 jnp.asarray(wv))
                beta, lam_best, full_path = self._lambda_path(
                    Xd, yd, wd, family, alpha, n, nfeat, max_iter, beta_eps,
                    tweedie_p, p, vdata=vdata, fitplan=fitplan,
                )
            else:
                lam_v = float(lam[0] if isinstance(lam, (list, tuple)) else (lam or 0.0))
                if engine_on or pod:
                    beta = self._irls_fused(
                        Xd, yd, wd, family, lam_v, alpha, max_iter,
                        beta_eps, tweedie_p, cloud, shard_mode, n_shards,
                        fitplan, y_host=y_host_fit, w_host=w_host_fit)
                else:
                    beta = self._irls(Xd, yd, wd, family, lam_v, alpha, max_iter, beta_eps, tweedie_p)
                lam_best = lam_v
            if p.get("compute_p_values") and (lam_best == 0):
                gram, _ = _gram_step(Xd, yd, wd, jnp.asarray(beta), family, tweedie_p)
                try:
                    # the Gram comes out of the jit replicated on every rank,
                    # so the inverse/dispersion below agree across processes
                    cov = np.linalg.inv(np.asarray(gram, np.float64))
                    # dispersion: Pearson X²/(n−p) for the families whose
                    # variance is estimated (gaussian/gamma/tweedie); fixed
                    # at 1 for binomial/poisson (GLM dispersion_estimated)
                    if family in ("gaussian", "gamma", "tweedie") \
                            and distdata.multiprocess():
                        # jit-global Pearson sums — the sharded Xd never
                        # reaches the host; f32 accumulation, like the Gram
                        x2, wsum = _pearson_sums(
                            Xd, yd, wd, jnp.asarray(beta, jnp.float32),
                            family, float(tweedie_p))
                        dof = max(float(wsum) - Xd.shape[1], 1.0)
                        dispersion = float(x2) / dof
                    elif family in ("gaussian", "gamma", "tweedie"):
                        eta = np.asarray(Xd @ jnp.asarray(beta, jnp.float32), np.float64)
                        mu = np.asarray(_linkinv(family, jnp.asarray(eta)), np.float64)
                        yv_ = np.asarray(yd, np.float64)
                        wv_ = np.asarray(wd, np.float64)
                        vfun = {"gaussian": np.ones_like(mu),
                                "gamma": np.maximum(mu, 1e-12) ** 2,
                                "tweedie": np.maximum(mu, 1e-12) ** tweedie_p}[family]
                        dof = max(float(wv_.sum()) - Xd.shape[1], 1.0)
                        dispersion = float(np.sum(wv_ * (yv_ - mu) ** 2 / vfun) / dof)
                    else:
                        dispersion = 1.0
                    cov = cov * dispersion
                    stderr = np.sqrt(np.maximum(np.diag(cov), 0.0))
                except np.linalg.LinAlgError:
                    cov = None
                    stderr = None

        _est.record_fit(
            "glm", str(fitplan.get("path", "legacy")),
            iterations=fitplan.get("iterations"),
            converged=fitplan.get("converged"),
            matrix_cache=(_est.matrix_cache_state(cache0)
                          if cache0 is not None else None),
            # the λ-path program ("fused_path") runs plain full-row
            # einsums — only the blocked IRLS paths really sharded
            n_shards=n_shards if fitplan.get("path") in (
                "fused", "fused_blocks", "fused_mesh") else 0,
            n_devices=cloud.size if shard_mode == "mesh" else 1,
            family=family,
            **{k: fitplan[k] for k in ("rows_per_device", "local_blocks",
                                       "fold_bytes") if k in fitplan})
        model = GLMModel(self, x, y, dinfo, family, beta, domain,
                         lambda_best=lam_best, stderr=stderr, full_path=full_path)
        model.covmat = cov  # (p+1)² dispersion-scaled covariance (p-values)
        return attach_linear_artifacts(model, train, valid, Xd, cloud.size, n)

    @staticmethod
    def _beta_from_sums(wy: float, n_obs: float, family: str,
                        pdim: int) -> np.ndarray:
        """β₀ with the family's intercept warm start from (Σw·y, Σw) — the
        ONE copy of the formula; host-loop and fused inits both call it so
        they can never desynchronize."""
        beta = np.zeros(pdim, np.float64)
        if family in ("binomial", "quasibinomial", "fractionalbinomial"):
            mu0 = wy / (n_obs + 1e-12)
            mu0 = min(max(mu0, 1e-6), 1 - 1e-6)
            beta[-1] = np.log(mu0 / (1 - mu0))
        elif family in ("poisson", "gamma", "tweedie"):
            beta[-1] = np.log(max(wy / (n_obs + 1e-12), 1e-6))
        return beta

    def _beta_init(self, yd, wd, family, pdim) -> Tuple[np.ndarray, float]:
        """(β₀, Σw) with the sums reduced ON DEVICE — global + replicated
        under a multi-host mesh, where a host np.asarray of the sharded
        arrays would not be."""
        n_obs, wy = (float(v) for v in _wsums(yd, wd))
        return self._beta_from_sums(wy, n_obs, family, pdim), n_obs

    def _irls_fused(self, Xd, yd, wd, family, lam, alpha, max_iter,
                    beta_eps, tweedie_p, cloud, shard_mode, n_shards,
                    fitplan, y_host=None, w_host=None):
        """Fused whole-fit IRLS (ISSUE 15): one device program, convergence
        on device, host reads final state only. Falls back to the f64 host
        loop if the f32 program diverged (separation-shaped data)."""
        pdim = int(Xd.shape[1])
        with tracing.span("fit.init", kind="fit"):
            if y_host is not None and w_host is not None:
                # HOST init sums: a device jnp.sum over a row-sharded array
                # reduces in psum order, which would break the blocks==mesh
                # bit-identity contract at the very first β
                wts = np.asarray(w_host, np.float64)
                n_obs = float(wts.sum())
                wy = float((wts * np.asarray(y_host, np.float64)).sum())
                beta0 = self._beta_from_sums(wy, n_obs, family, pdim)
            else:
                beta0, n_obs = self._beta_init(yd, wd, family, pdim)
            one_step = (family == "gaussian" and lam >= 0
                        and alpha * lam == 0)
            fn = _irls_device_fn(cloud, shard_mode, n_shards, family,
                                 bool(self._parms.get("non_negative")),
                                 one_step)
        with _est.iter_phase() as sp:
            # segmented dispatch under QoS (one_step stays a single solve);
            # the β carry round-trips on device between bounded segments
            beta_d = jnp.asarray(beta0, jnp.float32)
            it_d = jnp.int32(0)
            delta_d = jnp.float32(jnp.inf)
            stops = [max_iter] if one_step else _est.segment_stops(max_iter)
            # mid-fit carry snapshots (ISSUE 20): β/it/δ at a segment
            # boundary ARE the whole fit state — a killed fit resumes at
            # the last completed segment, bit-identical (exact f32 carry).
            # λ is in the fingerprint, so every lambda-path solve keeps its
            # own snapshot line.
            ck_fp = _est.segment_fingerprint(
                "glm", rows=int(Xd.shape[0]), p=int(pdim),
                family=str(family), lam=float(lam), alpha=float(alpha),
                max_iter=int(max_iter), beta_eps=float(beta_eps),
                tweedie_p=float(tweedie_p), n_shards=int(n_shards),
                shard_mode=str(shard_mode)) if len(stops) > 1 else None
            rest = _est.segment_carry_restore("glm", ck_fp)
            if rest is not None:
                s0, (beta_d, it_d, delta_d) = rest
                stops = [s for s in stops if s > s0] or [max_iter]
            for stop in stops:
                beta_d, it_d, delta_d = fn(
                    Xd, yd, wd, beta_d, it_d, delta_d,
                    jnp.float32(lam), jnp.float32(alpha),
                    jnp.float32(n_obs), jnp.int32(max_iter),
                    jnp.int32(stop), jnp.float32(beta_eps),
                    jnp.float32(tweedie_p))
                if stop < max_iter:
                    if int(it_d) >= max_iter or float(delta_d) < beta_eps:
                        break
                    _est.segment_carry_save("glm", ck_fp, stop,
                                            (beta_d, it_d, delta_d))
                    _qos.yield_point("est_segment", compensate="est_iter")
            cloudlib.collective_fence(beta_d)
            beta = np.asarray(beta_d, np.float64)
            iters = int(it_d)
            sp.annotate(iterations=iters, segments=len(stops))
        if not np.isfinite(beta).all():
            # f32 divergence — the robust host loop is the answer, and the
            # plan records that the fused program did not stick
            fitplan.update(path="host_fallback")
            return self._irls(Xd, yd, wd, family, lam, alpha, max_iter,
                              beta_eps, tweedie_p)
        # how the fit was laid out: a lane's rows, the ordered blocks it
        # sums, and what the fold gathers onto every lane an iteration
        # (all n_shards (P, P+1) float32 partials; nothing on one device)
        local_blocks, axis = _est.local_plan(cloud, shard_mode, n_shards)
        lanes = cloud.size if axis is not None else 1
        fitplan.update(
            path={"mesh": "fused_mesh", "blocks": "fused_blocks"}.get(
                shard_mode, "fused"),
            rows_per_device=int(Xd.shape[0]) // lanes,
            local_blocks=int(local_blocks),
            fold_bytes=(int(n_shards) * pdim * (pdim + 1) * 4
                        if axis is not None else 0),
            iterations=iters,
            converged=bool(one_step or float(delta_d) < beta_eps
                           or iters < max_iter))
        return beta

    def _irls(self, Xd, yd, wd, family, lam, alpha, max_iter, beta_eps, tweedie_p):
        pdim = Xd.shape[1]
        beta, n_obs = self._beta_init(yd, wd, family, pdim)
        for it in range(max_iter):
            gram, xy = _gram_step(Xd, yd, wd, jnp.asarray(beta, jnp.float32), family, tweedie_p)
            new_beta = _solve_penalized(
                np.asarray(gram, np.float64), np.asarray(xy, np.float64),
                lam, alpha, n_obs, pdim - 1, beta,
                non_negative=bool(self._parms.get("non_negative")),
            )
            delta = np.max(np.abs(new_beta - beta))
            beta = new_beta
            if delta < beta_eps:
                break
            if family == "gaussian" and lam >= 0 and alpha * lam == 0:
                break  # gaussian ridge/OLS is exact in one step
        return beta

    def _lambda_path(self, Xd, yd, wd, family, alpha, n, nfeat, max_iter,
                     beta_eps, tweedie_p, p, vdata=None, fitplan=None):
        """lambda_search: geometric path from lambda_max down, warm starts
        (hex/glm/GLM.java regularization path). `lambda_best` is chosen by
        VALIDATION deviance when a validation_frame was given (the reference
        selects on held-out deviance; training deviance otherwise, which
        favours the smallest lambda)."""
        fitplan = fitplan if fitplan is not None else {}
        gram0, xy0 = _gram_step(
            Xd, yd, wd, jnp.zeros(Xd.shape[1], jnp.float32), family, tweedie_p
        )
        lam_max = float(np.max(np.abs(np.asarray(xy0)[:-1])) / max(n * max(alpha, 1e-3), 1e-12))
        nlam = int(p.get("nlambdas", -1))
        if nlam <= 0:
            nlam = 30
        ratio = float(p.get("lambda_min_ratio", -1))
        if ratio <= 0:
            ratio = 1e-4 if n > nfeat else 1e-2
        lams = lam_max * np.power(ratio, np.linspace(0, 1, nlam))
        from ..parallel import mesh as cloudlib

        if cloudlib.cloud().size == 1 and not _est.legacy():
            # the whole path runs as ONE device program (f32); the chosen λ
            # is then re-solved on host in f64 for the reported coefficients.
            # H2O3_EST_LEGACY=1 takes the host IRLS loop below instead (the
            # per-λ gram-D2H + host-solve shape, the engine comparator)
            Xe, ye, we = vdata if vdata is not None else (Xd, yd, wd)
            with _est.iter_phase():
                betas, devs = _glm_path_device(
                    Xd, jnp.asarray(yd, jnp.float32), jnp.asarray(wd, jnp.float32),
                    Xe, jnp.asarray(ye, jnp.float32), jnp.asarray(we, jnp.float32),
                    jnp.asarray(lams, jnp.float32), float(alpha),
                    float(np.asarray(wd).sum()),
                    jnp.zeros(Xd.shape[1], jnp.float32), float(beta_eps),
                    float(tweedie_p), family=family, max_iter=int(max_iter),
                    non_negative=bool(self._parms.get("non_negative")),
                )
                betas = np.asarray(betas, np.float64)
                devs = np.asarray(devs, np.float64)
            finite = np.isfinite(devs)
            if finite.any():
                path = [(float(lv), betas[i]) for i, lv in enumerate(lams)]
                best_i = int(np.argmin(np.where(finite, devs, np.inf)))
                lam_best = float(lams[best_i])
                beta = self._irls_warm(Xd, yd, wd, family, lam_best, alpha,
                                       max_iter, beta_eps, tweedie_p,
                                       betas[best_i].copy())
                fitplan.update(path="fused_path", converged=True,
                               iterations=len(lams))
                return beta, lam_best, path
            # every λ diverged in f32 — fall through to the robust host loop

        # host path: multi-host mesh (the fused device path's closure-
        # captured group tensors would embed non-addressable arrays in the
        # HLO; vdata itself is row-sharded and fine), the H2O3_EST_LEGACY
        # comparator, or f32 divergence
        beta = np.zeros(Xd.shape[1], np.float64)
        path = []
        best = (None, np.inf, 0.0)
        for lv in lams:
            beta = self._irls_warm(Xd, yd, wd, family, float(lv), alpha,
                                   max_iter, beta_eps, tweedie_p, beta)
            if vdata is not None:
                dev = self._deviance(vdata[0], vdata[1], vdata[2], family,
                                     beta, tweedie_p)
            else:
                dev = self._deviance(Xd, yd, wd, family, beta, tweedie_p)
            path.append((float(lv), beta.copy()))
            if dev < best[1]:
                best = (beta.copy(), dev, float(lv))
        return best[0], best[2], path

    def _irls_warm(self, Xd, yd, wd, family, lam, alpha, max_iter, beta_eps, tweedie_p, beta0):
        beta = beta0.copy()
        n_obs = float(_wsums(yd, wd)[0])
        for it in range(max_iter):
            gram, xy = _gram_step(Xd, yd, wd, jnp.asarray(beta, jnp.float32), family, tweedie_p)
            new_beta = _solve_penalized(
                np.asarray(gram, np.float64), np.asarray(xy, np.float64),
                lam, alpha, n_obs, Xd.shape[1] - 1, beta,
                non_negative=bool(self._parms.get("non_negative")),
            )
            delta = np.max(np.abs(new_beta - beta))
            beta = new_beta
            if delta < beta_eps:
                break
        return beta

    def _deviance(self, Xd, yd, wd, family, beta, tweedie_p=1.5):
        if distdata.multiprocess():
            # sharded inputs never reach the host; the jitted sum is global
            return float(_deviance_device(
                Xd, yd, wd, jnp.asarray(beta, jnp.float32), family,
                float(tweedie_p)))
        eta = np.asarray(Xd @ jnp.asarray(beta, jnp.float32), np.float64)
        y = np.asarray(yd, np.float64)
        w = np.asarray(wd, np.float64)
        mu = np.asarray(_linkinv(family, jnp.asarray(eta)), np.float64)
        return float(_family_deviance_sum(family, y, mu, w, tweedie_p, xp=np))

    def _fit_multinomial(self, Xd, ycodes, wd, K, alpha, lam, max_iter,
                         n_global=None):
        """Softmax GLM via optax L-BFGS (the reference's multinomial L_BFGS).

        Works unchanged on a multi-host cloud: `Xd`/`wd` arrive row-sharded,
        the local one-hot responses are assembled into a matching global
        array (zero rows in the pad tail carry wd=0), and every reduction
        in `loss` is a jit-global sum."""
        import optax

        pdim = Xd.shape[1]
        n = len(ycodes)
        if distdata.multiprocess():
            Y = np.zeros((n, K), np.float32)
            Y[np.arange(n), ycodes] = 1.0
            from ..parallel import mesh as cloudlib

            Yd = distdata.global_row_array(
                Y, Xd.shape[0] // jax.process_count(), cloudlib.cloud())
        else:
            Y = np.zeros((Xd.shape[0], K), np.float32)
            Y[np.arange(n), ycodes] = 1.0
            Yd = jnp.asarray(Y)
        n_eff = float(n_global if n_global is not None else n)
        lam_v = float(lam[0] if isinstance(lam, (list, tuple)) else (lam or 0.0))

        # data arrays are ARGUMENTS, not closure captures: a jit may not
        # close over process-spanning (multi-host) arrays
        def loss(B, Xd, Yd, wd):
            logits = Xd @ B.T  # (n, K)
            lse = jax.scipy.special.logsumexp(logits, axis=1)
            ll = (jnp.sum(logits * Yd, axis=1) - lse) * wd
            ridge = 0.5 * lam_v * (1 - alpha) * jnp.sum(B[:, :-1] ** 2)
            # sum/n_eff (not mean): the padded global row count must not
            # rescale the data term against the ridge
            return -jnp.sum(ll) / n_eff + ridge

        B = jnp.zeros((K, pdim), jnp.float32)
        try:
            opt = optax.lbfgs()
            state = opt.init(B)

            @jax.jit
            def step(B, state, Xd, Yd, wd):
                def f(b):
                    return loss(b, Xd, Yd, wd)

                v, g = jax.value_and_grad(f)(B)
                updates, state2 = opt.update(g, state, B, value=v, grad=g,
                                             value_fn=f)
                return optax.apply_updates(B, updates), state2, v

            prev = np.inf
            for it in range(max(100, max_iter * 4)):
                B, state, v = step(B, state, Xd, Yd, wd)
                v = float(v)
                if abs(prev - v) < 1e-9:
                    break
                prev = v
        except (AttributeError, TypeError):
            opt = optax.adam(0.1)
            state = opt.init(B)
            vg = jax.jit(jax.value_and_grad(loss))
            for it in range(500):
                v, g = vg(B, Xd, Yd, wd)
                updates, state = opt.update(g, state)
                B = optax.apply_updates(B, updates)
        return np.asarray(B, np.float64)

    def _cv_predict(self, model: GLMModel, frame: Frame) -> np.ndarray:
        out = model._score(frame)
        return out

    # h2o-py convenience
    @staticmethod
    def getGLMRegularizationPath(model):
        m = model.model if isinstance(model, H2OGeneralizedLinearEstimator) else model
        if m.full_path is None:
            return {"lambdas": [m.lambda_best], "coefficients": [m.coef()]}
        return {
            "lambdas": [l for l, _ in m.full_path],
            "coefficients": [dict(zip(m._names(), b)) for _, b in m.full_path],
        }

    def coef(self):
        return self.model.coef()

    def coef_norm(self):
        return self.model.coef_norm()


GLM = H2OGeneralizedLinearEstimator
