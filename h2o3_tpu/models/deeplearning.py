"""H2ODeepLearningEstimator — multilayer perceptron.

Reference parity: `h2o-algos/src/main/java/hex/deeplearning/DeepLearning.java`,
`DeepLearningTask.java` (per-row fwd/bwd with **Hogwild!** lock-free weight
races + inter-node model averaging in `reduce()`), `Neurons.java` (rectifier/
tanh/maxout fwd/bwd, dropout), `DeepLearningModelInfo.java` (flat weights),
and the estimator surface `h2o-py/h2o/estimators/deeplearning.py`
(MNIST-rectifier is a BASELINE.json headline config).

Deliberate semantic change (SURVEY.md §2.4): Hogwild's benign races and
per-node model averaging are replaced by **synchronous data-parallel
minibatch SGD** — batch rows sharded over the ``hosts`` mesh axis, gradients
averaged by XLA-inserted `psum` (the MRTask.reduce of DeepLearningTask,
compiled). Results become deterministic; accuracy targets must match, the
trajectory will not. `train_samples_per_iteration` survives as the scoring/
early-stopping cadence, matching the reference's sync-interval meaning.

Optimizers mirror the reference: ADADELTA (`adaptive_rate=true`, rho/epsilon)
or annealed-momentum SGD (`rate`, `rate_annealing`, `momentum_start/ramp/
stable` — Nesterov).
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..frame.frame import Frame
from ..parallel import distdata
from ..parallel import mesh as cloudlib
from .metrics import (
    ModelMetricsBinomial,
    ModelMetricsMultinomial,
    ModelMetricsRegression,
)
from .model_base import (DataInfo, H2OEstimator, H2OModel, ScoreKeeper,
                         ScoringHistory, response_info)

ACTIVATIONS = (
    "Rectifier", "Tanh", "Maxout",
    "RectifierWithDropout", "TanhWithDropout", "MaxoutWithDropout",
)


def _act(name: str, x, k2=None, dropout=0.0):
    base = name.replace("WithDropout", "")
    if base == "Rectifier":
        h = jax.nn.relu(x)
    elif base == "Tanh":
        h = jnp.tanh(x)
    elif base == "Maxout":
        # Neurons.Maxout: pairs of units, max over the pair (channel dim 2)
        h = jnp.max(x.reshape(x.shape[0], -1, 2), axis=2)
    else:
        raise ValueError(f"unknown activation {name}")
    if dropout > 0.0 and k2 is not None:
        keep = jax.random.bernoulli(k2, 1 - dropout, h.shape)
        h = jnp.where(keep, h / (1 - dropout), 0.0)
    return h


def _init_params(key, sizes: List[int], activation: str, seed_dist="UniformAdaptive"):
    """DeepLearningModelInfo.randomizeWeights — uniform-adaptive init."""
    params = []
    maxout = activation.startswith("Maxout")
    for i in range(len(sizes) - 1):
        fan_in, fan_out = sizes[i], sizes[i + 1]
        hidden = i < len(sizes) - 2
        out_dim = fan_out * 2 if (maxout and hidden) else fan_out
        key, sub = jax.random.split(key)
        limit = jnp.sqrt(6.0 / (fan_in + out_dim))
        W = jax.random.uniform(sub, (fan_in, out_dim), jnp.float32, -limit, limit)
        b = jnp.zeros(out_dim, jnp.float32)
        params.append((W, b))
    return params


def _forward(params, X, activation, hidden_dropout, input_dropout, key, train: bool):
    h = X
    if train and input_dropout > 0:
        key, sub = jax.random.split(key)
        keep = jax.random.bernoulli(sub, 1 - input_dropout, h.shape)
        h = jnp.where(keep, h / (1 - input_dropout), 0.0)
    L = len(params)
    for i, (W, b) in enumerate(params):
        z = h @ W + b
        if i < L - 1:
            dr = hidden_dropout[i] if train and hidden_dropout else 0.0
            if train and dr > 0:
                key, sub = jax.random.split(key)
            else:
                sub = None
            h = _act(activation, z, sub, dr if train else 0.0)
        else:
            h = z  # output layer linear; link applied in the loss/score
    return h


class DeepLearningModel(H2OModel):
    algo = "deeplearning"

    def __init__(self, params_est, x, y, dinfo, problem, nclass, domain,
                 net_params, activation, distribution):
        super().__init__(params_est)
        self.x = list(x)
        self.y = y
        self.dinfo = dinfo
        self.problem = problem
        self.nclass = nclass
        self.domain = domain
        self.net_params = net_params
        self.activation = activation
        self.distribution = distribution

    def _score(self, frame: Frame, X_pre=None) -> np.ndarray:
        """X_pre: optional pre-transformed (and possibly device-resident)
        design matrix — the training loop passes its HBM copy so scoring
        events skip the host re-expansion and the big re-upload."""
        X = X_pre if X_pre is not None else jnp.asarray(self.dinfo.transform(frame))
        out = _forward(self.net_params, X, self.activation, None, 0.0, None, False)
        if self.problem == "autoencoder":
            return np.asarray(out, np.float64)  # reconstruction
        if self.problem in ("binomial", "multinomial"):
            return np.asarray(jax.nn.softmax(out, axis=1), np.float64)
        if self.distribution in ("poisson", "gamma", "tweedie"):
            return np.asarray(jnp.exp(out[:, 0]), np.float64)[:, None]
        return np.asarray(out[:, :1], np.float64)

    def anomaly(self, frame: Frame) -> Frame:
        """Per-row reconstruction MSE (`h2o.anomaly` on an autoencoder)."""
        if self.problem != "autoencoder":
            raise ValueError("anomaly() requires autoencoder=True")
        X = self.dinfo.transform(frame)  # one expansion, reused for both
        rec = np.asarray(_forward(self.net_params, jnp.asarray(X),
                                  self.activation, None, 0.0, None, False),
                         np.float64)
        return Frame.from_dict(
            {"Reconstruction.MSE": np.mean((rec - X) ** 2, axis=1)})

    def predict(self, test_data: Frame) -> Frame:
        out = self._score(test_data)
        if self.problem == "autoencoder":
            # reconstructed inputs in the expanded coefficient space
            return Frame.from_dict(
                {f"reconstr_{n}": out[:, i]
                 for i, n in enumerate(self.dinfo.coef_names)})
        if self.problem in ("binomial", "multinomial"):
            lab = out.argmax(axis=1)
            d = {"predict": np.asarray(self.domain, dtype=object)[lab]}
            for i, cls in enumerate(self.domain):
                d[str(cls)] = out[:, i]
            return Frame.from_dict(d, column_types={"predict": "enum"})
        return Frame.from_dict({"predict": out[:, 0]})

    def _make_metrics(self, frame: Frame, X_pre=None):
        out = self._score(frame, X_pre=X_pre)
        if self.problem == "autoencoder":
            X = (np.asarray(X_pre) if X_pre is not None
                 else self.dinfo.transform(frame))
            mse = float(np.mean((out - X) ** 2))
            m = ModelMetricsRegression(mse=mse, rmse=float(np.sqrt(mse)),
                                       nobs=frame.nrow,
                                       description="autoencoder reconstruction")
            return m
        yv = frame.vec(self.y)
        if self.problem == "binomial":
            return ModelMetricsBinomial.make(np.asarray(yv.data), out[:, 1])
        if self.problem == "multinomial":
            return ModelMetricsMultinomial.make(np.asarray(yv.data), out)
        return ModelMetricsRegression.make(yv.numeric_np(), out[:, 0])


class H2ODeepLearningEstimator(H2OEstimator):
    algo = "deeplearning"
    _param_defaults = dict(
        activation="Rectifier",
        hidden=[200, 200],
        epochs=10.0,
        train_samples_per_iteration=-2,
        mini_batch_size=32,           # reference default is 1 (per-row Hogwild);
                                      # sync-DP wants real batches — documented delta
        adaptive_rate=True,
        rho=0.99,
        epsilon=1e-8,
        rate=0.005,
        rate_annealing=1e-6,
        rate_decay=1.0,
        momentum_start=0.0,
        momentum_ramp=1e6,
        momentum_stable=0.0,
        nesterov_accelerated_gradient=True,
        input_dropout_ratio=0.0,
        hidden_dropout_ratios=None,
        l1=0.0,
        l2=0.0,
        max_w2=float("inf"),
        initial_weight_distribution="UniformAdaptive",
        initial_weight_scale=1.0,
        loss="Automatic",
        distribution="AUTO",
        score_interval=5.0,
        score_training_samples=10000,
        score_validation_samples=0,
        score_duty_cycle=0.1,
        overwrite_with_best_model=True,
        standardize=True,
        use_all_factor_levels=True,
        shuffle_training_data=False,
        reproducible=False,
        variable_importances=True,
        export_weights_and_biases=False,
        elastic_averaging=False,
        autoencoder=False,
    )

    def _is_supervised(self) -> bool:  # autoencoder trains without a response
        return not self._parms.get("autoencoder", False)

    def _fit(self, x, y, train: Frame, valid: Optional[Frame]) -> DeepLearningModel:
        p = self._parms
        seed = p["_actual_seed"]
        autoenc = bool(p.get("autoencoder", False))
        if autoenc:
            problem, nclass, domain = "autoencoder", 0, None
            dist = "gaussian"
        else:
            yvec = train.vec(y)
            problem, nclass, domain = response_info(yvec)
            dist = p.get("distribution", "AUTO")
            if dist == "AUTO":
                dist = {"binomial": "bernoulli", "multinomial": "multinomial"}.get(
                    problem, "gaussian"
                )
        dinfo = DataInfo(
            train, x,
            standardize=bool(p.get("standardize", True)),
            use_all_factor_levels=bool(p.get("use_all_factor_levels", True)),
        )
        _max_runtime = float(p.get("max_runtime_secs", 0) or 0)
        multiproc = distdata.multiprocess()
        cloud = cloudlib.cloud()
        # ONE scan-path decision reused by the design-matrix choice and the
        # training loop below (a second copy of this predicate diverging
        # would read X_dev_pre=None inside the loop)
        use_scan = not (_max_runtime > 0) or multiproc
        if use_scan and not multiproc and cloud.size == 1:
            # device-resident training path: build the design matrix ON
            # device from compact columns (small-range integer features
            # travel as 1–2 bytes/value — MNIST-style pixel data is 4×
            # fewer H2D bytes than the dense f32 upload, losslessly).
            # Single-device only: a multi-device mesh needs the
            # shard-straight-from-host upload so no unsharded intermediate
            # lands on device 0. The artifact rides the dataset cache's
            # std layer (ISSUE 15): a sweep's DL candidates (and AutoML's
            # three DeepLearning steps) expand + upload ONCE per frame.
            X = None
            from . import estimator_engine as _est

            if _est.cache_enabled():
                dinfo, X_dev_pre = _est.design_matrix(
                    train, x,
                    standardize=bool(p.get("standardize", True)),
                    use_all=bool(p.get("use_all_factor_levels", True)))
            else:
                X_dev_pre = dinfo.device_design(train, fit=True)
            n, nfeat = train.nrow, int(X_dev_pre.shape[1])
        else:
            X = dinfo.fit_transform(train)
            n, nfeat = X.shape
            X_dev_pre = None
        raw_hidden = p.get("hidden")
        if raw_hidden is not None:
            raw_hidden = list(raw_hidden)     # materialize once (iterables)
            if not raw_hidden:
                raise ValueError("hidden must be a non-empty list of layer "
                                 "sizes (got [])")
        hidden = list(raw_hidden if raw_hidden is not None else [200, 200])
        if any((not float(h).is_integer()) or h < 1 for h in hidden):
            raise ValueError(
                f"hidden must be a non-empty list of positive layer sizes, "
                f"got {raw_hidden}")
        hidden = [int(h) for h in hidden]
        if float(p.get("epochs", 10.0)) <= 0:
            raise ValueError(f"epochs must be > 0, got {p.get('epochs')}")
        if int(p.get("mini_batch_size", 32)) < 1:
            raise ValueError("mini_batch_size must be >= 1, got "
                             f"{p.get('mini_batch_size')}")
        for k in ("input_dropout_ratio", "rho"):
            v = p.get(k)
            if v is not None and not (0.0 <= float(v) < 1.0):
                raise ValueError(f"{k} must be in [0, 1), got {v}")
        eps_v = p.get("epsilon")
        if eps_v is not None and not (0.0 < float(eps_v) <= 1.0):
            raise ValueError(f"epsilon must be in (0, 1], got {eps_v}")
        activation = p.get("activation", "Rectifier")
        if activation not in ACTIVATIONS:
            raise ValueError(f"activation {activation!r} not in {ACTIVATIONS}")
        if autoenc:
            K = nfeat  # reconstruct the (expanded, standardized) inputs
        else:
            K = nclass if problem in ("binomial", "multinomial") else 1
        sizes = [nfeat] + hidden + [K]

        if autoenc:
            yarr = np.zeros(n, np.float32)  # unused placeholder
        elif problem in ("binomial", "multinomial"):
            yarr = np.asarray(yvec.data, np.int32)
        else:
            yarr = yvec.numeric_np().astype(np.float32)
        w = (
            train.vec(p["weights_column"]).numeric_np()
            if p.get("weights_column")
            else np.ones(n)
        ).astype(np.float32)

        if multiproc:
            # early stopping / time budget use a global any-rank-stops vote
            # at every scoring event, so host control flow stays aligned
            n_global = int(distdata.global_sum(np.asarray([n]))[0])
        else:
            n_global = n
        batch = int(p.get("mini_batch_size", 32))
        batch = max(batch, cloud.size)
        batch = cloudlib.pad_to_multiple(batch, cloud.size)

        key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
        key, kinit = jax.random.split(key)
        params = _init_params(kinit, sizes, activation)

        hidden_dropout = p.get("hidden_dropout_ratios")
        if hidden_dropout is None and activation.endswith("WithDropout"):
            hidden_dropout = [0.5] * len(hidden)
        hidden_dropout = tuple(hidden_dropout) if hidden_dropout else None
        input_dropout = float(p.get("input_dropout_ratio", 0.0))
        l1 = float(p.get("l1", 0.0))
        l2 = float(p.get("l2", 0.0))
        max_w2 = float(p.get("max_w2", float("inf")))
        adaptive = bool(p.get("adaptive_rate", True))
        rho = float(p.get("rho", 0.99))
        eps = float(p.get("epsilon", 1e-8))
        rate0 = float(p.get("rate", 0.005))
        rate_annealing = float(p.get("rate_annealing", 1e-6))
        mom_start = float(p.get("momentum_start", 0.0))
        mom_ramp = max(float(p.get("momentum_ramp", 1e6)), 1.0)
        mom_stable = float(p.get("momentum_stable", 0.0))

        def loss_fn(params, xb, yb, wb, key):
            out = _forward(params, xb, activation, hidden_dropout, input_dropout, key, True)
            if autoenc:
                nll = jnp.mean((out - xb) ** 2, axis=1)
            elif problem in ("binomial", "multinomial"):
                logp = jax.nn.log_softmax(out, axis=1)
                nll = -jnp.take_along_axis(logp, yb[:, None].astype(jnp.int32), axis=1)[:, 0]
            elif dist == "poisson":
                nll = jnp.exp(out[:, 0]) - yb * out[:, 0]
            else:
                nll = 0.5 * (out[:, 0] - yb) ** 2
            loss = jnp.sum(nll * wb) / jnp.maximum(jnp.sum(wb), 1e-12)
            if l2 > 0:
                loss = loss + l2 * sum(jnp.sum(W * W) for W, _ in params)
            if l1 > 0:
                loss = loss + l1 * sum(jnp.sum(jnp.abs(W)) for W, _ in params)
            return loss

        # ADADELTA state: (E[g²], E[Δ²]) per tensor (Neurons ADADELTA impl).
        # Only the per-batch (max_runtime) path uses the structured layout;
        # the scan path carries the fused flat state (oflat below).
        if use_scan:
            opt_state = None
        elif adaptive:
            opt_state = [
                (jnp.zeros_like(W), jnp.zeros_like(W), jnp.zeros_like(b), jnp.zeros_like(b))
                for W, b in params
            ]
        else:
            opt_state = [(jnp.zeros_like(W), jnp.zeros_like(b)) for W, b in params]

        def _update(params, opt_state, grads, it):
            """One optimizer update (ADADELTA per Neurons.java, or
            momentum/annealed-rate SGD) — shared by the per-batch step and
            the device-resident scan."""
            new_params, new_state = [], []
            if adaptive:
                for (W, b), (Eg2W, Ed2W, Eg2b, Ed2b), (gW, gb) in zip(params, opt_state, grads):
                    Eg2W = rho * Eg2W + (1 - rho) * gW * gW
                    dW = -jnp.sqrt(Ed2W + eps) / jnp.sqrt(Eg2W + eps) * gW
                    Ed2W = rho * Ed2W + (1 - rho) * dW * dW
                    Eg2b = rho * Eg2b + (1 - rho) * gb * gb
                    db = -jnp.sqrt(Ed2b + eps) / jnp.sqrt(Eg2b + eps) * gb
                    Ed2b = rho * Ed2b + (1 - rho) * db * db
                    W2, b2 = W + dW, b + db
                    if np.isfinite(max_w2):
                        norms = jnp.sum(W2 * W2, axis=0, keepdims=True)
                        scale = jnp.sqrt(jnp.minimum(max_w2 / jnp.maximum(norms, 1e-12), 1.0))
                        W2 = W2 * scale
                    new_params.append((W2, b2))
                    new_state.append((Eg2W, Ed2W, Eg2b, Ed2b))
            else:
                rate = rate0 / (1.0 + rate_annealing * it)
                mom = jnp.minimum(
                    mom_start + (mom_stable - mom_start) * it / mom_ramp,
                    jnp.maximum(mom_stable, mom_start),
                ) if mom_ramp > 0 else mom_stable
                for (W, b), (vW, vb), (gW, gb) in zip(params, opt_state, grads):
                    vW2 = mom * vW - rate * gW
                    vb2 = mom * vb - rate * gb
                    new_params.append((W + vW2, b + vb2))
                    new_state.append((vW2, vb2))
            return new_params, new_state

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def train_step(params, opt_state, xb, yb, wb, key, it):
            grads = jax.grad(loss_fn)(params, xb, yb, wb, key)
            return _update(params, opt_state, grads, it)

        # ---- flat-parameter scan path ----------------------------------
        # The per-tensor optimizer updates are ~200 tiny elementwise ops
        # per step; inside lax.scan that op overhead dominates a small
        # MLP's step time (~430 µs/step measured). Flattening params and
        # optimizer state into single vectors fuses ADADELTA into a
        # handful of full-vector ops — identical math, elementwise either
        # way. The flat layout exists only inside the scan; boundaries
        # (scoring, model export) see the per-layer (W, b) list.
        _seg_shapes = []
        _seg_offs = []
        _off = 0
        for W0, b0 in params:                 # actual shapes (maxout widens)
            for t in (W0, b0):
                _seg_shapes.append(tuple(t.shape))
                _seg_offs.append(_off)
                _off += int(np.prod(t.shape))
        _flat_n = _off

        def _flatten(ps):
            return jnp.concatenate([jnp.ravel(t) for W, b in ps
                                    for t in (W, b)])

        def _unflatten(v):
            out = []
            for i in range(0, len(_seg_shapes), 2):
                W = jax.lax.dynamic_slice(
                    v, (_seg_offs[i],),
                    (int(np.prod(_seg_shapes[i])),)).reshape(_seg_shapes[i])
                b = jax.lax.dynamic_slice(
                    v, (_seg_offs[i + 1],),
                    (int(np.prod(_seg_shapes[i + 1])),)
                ).reshape(_seg_shapes[i + 1])
                out.append((W, b))
            return out

        def _clamp_w2(v):
            """Per-layer max_w2 column-norm clamp on the flat vector
            (only traced when the non-default max_w2 is set)."""
            for i in range(0, len(_seg_shapes), 2):
                shp = _seg_shapes[i]
                W = jax.lax.dynamic_slice(
                    v, (_seg_offs[i],), (int(np.prod(shp)),)).reshape(shp)
                norms = jnp.sum(W * W, axis=0, keepdims=True)
                scale = jnp.sqrt(jnp.minimum(
                    max_w2 / jnp.maximum(norms, 1e-12), 1.0))
                v = jax.lax.dynamic_update_slice(
                    v, (W * scale).ravel(), (_seg_offs[i],))
            return v

        def _flat_update(pv, ov, gv, it):
            if adaptive:
                eg2, ed2 = ov
                eg2 = rho * eg2 + (1 - rho) * gv * gv
                d = -jnp.sqrt(ed2 + eps) / jnp.sqrt(eg2 + eps) * gv
                ed2 = rho * ed2 + (1 - rho) * d * d
                pv = pv + d
                if np.isfinite(max_w2):
                    pv = _clamp_w2(pv)
                return pv, (eg2, ed2)
            rate = rate0 / (1.0 + rate_annealing * it)
            mom = jnp.minimum(
                mom_start + (mom_stable - mom_start) * it / mom_ramp,
                jnp.maximum(mom_stable, mom_start),
            ) if mom_ramp > 0 else mom_stable
            (vel,) = ov
            vel = mom * vel - rate * gv
            return pv + vel, (vel,)

        @functools.partial(jax.jit, donate_argnums=(0, 1),
                           static_argnames=("nsteps",))
        def train_chunk(pflat, oflat, X_d, y_d, w_d, key, it0, nsteps):
            """nsteps minibatch updates as ONE device program (lax.scan):
            the training set lives in HBM; one random permutation per chunk
            re-batches it into (nsteps, batch, ·) slices that scan consumes
            directly — no per-step gathers, no per-batch host→device uploads
            (either would dominate the step time over the host↔device
            link). Replaces the reference's per-row Hogwild loop
            (DeepLearningTask.map) with compiled minibatch SGD; the
            per-chunk reshuffle matches `shuffle_training_data` semantics."""
            kperm, kdrop = jax.random.split(key)
            need = nsteps * batch
            nrows = X_d.shape[0]            # global padded rows on a mesh
            perm = jax.random.permutation(kperm, nrows)
            reps = -(-need // nrows)                   # ceil: allow short n
            sel = jnp.tile(perm, reps)[:need]
            xs = (X_d[sel].reshape(nsteps, batch, -1),
                  y_d[sel].reshape((nsteps, batch) + y_d.shape[1:]),
                  w_d[sel].reshape(nsteps, batch),
                  jax.random.split(kdrop, nsteps))

            def flat_loss(pv, xb, yb, wb, k):
                return loss_fn(_unflatten(pv), xb, yb, wb, k)

            def body(carry, xb_yb_wb_k):
                pv, ov, it = carry
                xb, yb, wb, k = xb_yb_wb_k
                gv = jax.grad(flat_loss)(pv, xb, yb, wb, k)
                pv, ov = _flat_update(pv, ov, gv, it)
                return (pv, ov, it + 1.0), None

            (pflat, oflat, _), _ = jax.lax.scan(
                body, (pflat, oflat, jnp.float32(it0)), xs)
            return pflat, oflat

        # sync-DP: batches row-sharded over the mesh; params replicated —
        # XLA inserts the gradient psum (the Hogwild replacement)
        rs = cloud.row_sharding() if cloud.size > 1 else None
        epochs = float(p.get("epochs", 10.0))
        tspi = int(p.get("train_samples_per_iteration", -2))
        score_every = tspi if tspi > 0 else max(n_global, batch)
        stopper = (
            ScoreKeeper(int(p.get("stopping_rounds", 0)),
                        "logloss" if problem != "regression" else "deviance",
                        float(p.get("stopping_tolerance", 1e-3)))
            if int(p.get("stopping_rounds", 0)) > 0 else None
        )

        rng = np.random.default_rng(seed)
        total = int(epochs * n_global)
        seen = 0
        it = 0
        next_score = score_every
        history: List[Dict] = []
        t0 = time.time()
        max_runtime = _max_runtime
        model = DeepLearningModel(self, x, y, dinfo, problem, nclass, domain,
                                  params, activation, dist)
        # device-resident fast path: data in HBM (row-sharded on a mesh),
        # scan over steps; GSPMD turns the per-chunk permutation gather into
        # collectives and psums the sharded-batch gradients automatically.
        # max_runtime keeps the per-batch path (its wall check needs host
        # control between steps) — EXCEPT on multi-process clouds, where the
        # per-batch path would draw rank-divergent local batches; there the
        # scan path stays and the budget is checked (with the clock-
        # consensus vote) at scoring boundaries instead.
        if use_scan:
            if multiproc:
                # each process contributes its ingest shard as COMPACT
                # packs (uint8/int16 integer columns, int32 codes) expanded
                # on device — the same byte-compressed transfer the single-
                # chip path gets; zero-weight padding balances unequal byte
                # ranges (loss is Σw-normalized so padded rows are exact
                # no-ops). Stats were fitted by fit_transform's global
                # collectives, so the design ≡ the dense f32 upload.
                quota = distdata.local_quota(n)
                X_dev = dinfo.device_design(train, fit=False, cloud=cloud,
                                            quota=quota)
                y_dev = distdata.global_row_array(yarr, quota, cloud)
                w_dev = distdata.global_row_array(w, quota, cloud)
            elif rs is not None:
                # shard straight from host — an unsharded intermediate on
                # device 0 would defeat row sharding for data that only
                # fits when split across the mesh; rows pad to the mesh
                # multiple with zero weight
                quota = cloudlib.pad_to_multiple(n, cloud.size)
                X_dev = dinfo.device_design(train, fit=False, cloud=cloud,
                                            quota=quota)
                y_dev = distdata.global_row_array(yarr, quota, cloud)
                w_dev = distdata.global_row_array(w, quota, cloud)
            else:
                X_dev = X_dev_pre
                y_dev = jnp.asarray(yarr)
                w_dev = jnp.asarray(w)
            # scoring reuses the HBM copy — except on a multi-process mesh,
            # where fetching a cross-process-sharded eager result raises.
            # Quota-padded rows would corrupt training metrics, so scoring
            # gets a one-time device-side slice of the real rows.
            if jax.process_count() != 1:
                X_score = None
            elif int(X_dev.shape[0]) == n:
                X_score = X_dev
            else:
                X_score = X_dev[:n]
        else:
            # max_runtime path: no persistent device copy; scoring falls
            # back to the transient per-event transform
            X_score = None
        # mesh/quota padding adds zero-weight rows the permutation covers
        # too — discount them so `epochs` counts REAL samples (1.0 when
        # unpadded)
        real_frac = (n_global / float(X_dev.shape[0]) if use_scan else 1.0)
        if use_scan:
            pflat = _flatten(params)
            oflat = (tuple(jnp.zeros(_flat_n, jnp.float32)
                           for _ in range(2)) if adaptive
                     else (jnp.zeros(_flat_n, jnp.float32),))
        _score_time = 0.0
        while seen < total:
            # REST job cancellation (single-process: a per-rank host
            # decision would diverge a multi-process cloud)
            if self.job is not None and jax.process_count() == 1:
                self.job.check_cancelled()
            if use_scan:
                upto = min(next_score, total)
                eff_batch = max(batch * real_frac, 1e-9)
                steps = max(1, -(-int(upto - seen) // int(max(eff_batch, 1))))
                key, sub = jax.random.split(key)
                pflat, oflat = train_chunk(
                    pflat, oflat, X_dev, y_dev, w_dev, sub,
                    float(it), int(steps))
                # CPU mesh: serialize collective executables (see
                # parallel.mesh.collective_fence)
                cloudlib.collective_fence(pflat)
                seen += max(int(steps * eff_batch), 1)
                it += steps
            else:
                idx = rng.integers(0, n, batch)
                xb = jnp.asarray(X[idx])
                yb = jnp.asarray(yarr[idx])
                wb = jnp.asarray(w[idx])
                if rs is not None:
                    xb, yb, wb = (jax.device_put(a, rs) for a in (xb, yb, wb))
                key, sub = jax.random.split(key)
                params, opt_state = train_step(params, opt_state, xb, yb, wb,
                                               sub, jnp.float32(it))
                cloudlib.collective_fence(params[0][0])
                seen += batch
                it += 1
            if seen >= next_score or seen >= total:
                next_score += score_every
                # train_samples_per_iteration=-2 (auto-tune): cap the wall
                # share spent scoring at score_duty_cycle, like the
                # reference's computeSamplesPerIteration duty-cycle target.
                # Early stopping keeps every event (scoring IS its signal),
                # as does the final event and score_each_iteration.
                if (seen < total and stopper is None and tspi == -2
                        and not max_runtime
                        and not p.get("score_each_iteration")):
                    want_skip = _score_time > float(
                        p.get("score_duty_cycle", 0.1) or 0.1) * max(
                        time.time() - t0, 1e-9)
                    # per-rank clocks diverge; one rank skipping while
                    # another scores would desync the scoring path's
                    # collectives — skip only on a UNANIMOUS vote
                    want_skip = distdata.global_all(want_skip)
                    if want_skip:
                        if self.job:
                            self.job.update(min(seen / total, 1.0))
                        continue
                _t_sc = time.time()
                if use_scan:
                    params = _unflatten(pflat)
                model.net_params = params
                sm = model._make_metrics(train, X_pre=X_score)
                ev = {
                    "epochs": seen / n_global, "iterations": it,
                    "samples": seen, "timestamp": time.time(),
                }
                if problem in ("regression", "autoencoder"):
                    ev["deviance"] = sm.mse
                    metric_val = sm.mse
                else:
                    ev["logloss"] = sm.logloss
                    metric_val = sm.logloss
                history.append(ev)
                stop = stopper is not None and stopper.record(metric_val)
                # metrics are local-shard here, so ranks may disagree — a
                # global any-rank-stops vote keeps the remaining collective
                # programs aligned across processes
                stop = distdata.global_any(stop)
                _score_time += time.time() - _t_sc
                if stop:
                    break
            if max_runtime:
                hit = distdata.global_any(time.time() - t0 > max_runtime)
                if hit:
                    break
            if self.job:
                self.job.update(min(seen / total, 1.0))

        if use_scan:
            params = _unflatten(pflat)
        model.net_params = params
        model.scoring_history = ScoringHistory(history)
        model.training_metrics = model._make_metrics(train, X_pre=X_score)
        if valid is not None:
            model.validation_metrics = model._make_metrics(valid)
        return model

    def _cv_predict(self, model: DeepLearningModel, frame: Frame) -> np.ndarray:
        out = model._score(frame)
        if model.problem == "binomial":
            return out[:, 1]
        if model.problem == "multinomial":
            return out
        return out[:, 0]


def _dryrun_dp_step(cloud, n_devices: int):
    """One sharded DP train step for __graft_entry__.dryrun_multichip."""
    rng = np.random.default_rng(0)
    n, f, k = 16 * n_devices, 8, 3
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = rng.integers(0, k, n).astype(np.int32)
    key = jax.random.PRNGKey(0)
    params = _init_params(key, [f, 16, k], "Rectifier")
    rs = cloud.row_sharding()
    Xj = jax.device_put(jnp.asarray(X), rs)
    yj = jax.device_put(jnp.asarray(y), rs)

    @jax.jit
    def step(params, X, y):
        def loss(params):
            out = _forward(params, X, "Rectifier", None, 0.0, None, False)
            logp = jax.nn.log_softmax(out, axis=1)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

        grads = jax.grad(loss)(params)
        return jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)

    out = step(params, Xj, yj)
    jax.block_until_ready(out)


DeepLearning = H2ODeepLearningEstimator
