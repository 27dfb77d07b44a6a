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
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..frame.frame import Frame
from ..parallel import distdata
from ..parallel import mesh as cloudlib
from ..runtime import tracing
from . import estimator_engine as _est
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


# The step's and the scorer's matmuls: float32 at HIGHEST. On a TPU the
# DEFAULT precision is one bfloat16 pass, which a 56,641-step epoch does not
# forgive; the matmuls are a small part of a step's time (PERF.md).
_PRECISION = jax.lax.Precision.HIGHEST


def _act(name: str, x, key=None, dropout=None):
    """Activation, then inverted dropout at ratio `dropout` when a key is
    given (a traced ratio of 0 keeps every unit, exactly)."""
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
    if dropout is not None and key is not None:
        keep = jax.random.bernoulli(key, 1 - dropout, h.shape)
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


def _forward(params, X, activation, key=None, input_dropout=None,
             hidden_dropout=None, precision=None):
    """The network's output layer (linear; the link is the loss's or the
    scorer's). Dropout only in training, where a key is given: at ratio
    `input_dropout` on the inputs and `hidden_dropout[i]` after hidden
    layer i, each None where the network has none. The matmuls run at
    `precision`, `_PRECISION` where none is given."""
    precision = _PRECISION if precision is None else precision
    h = X
    if key is not None and input_dropout is not None:
        key, sub = jax.random.split(key)
        keep = jax.random.bernoulli(sub, 1 - input_dropout, h.shape)
        h = jnp.where(keep, h / (1 - input_dropout), 0.0)
    L = len(params)
    for i, (W, b) in enumerate(params):
        z = jnp.dot(h, W, precision=precision) + b
        if i == L - 1:
            return z
        sub = dr = None
        if key is not None and hidden_dropout is not None:
            key, sub = jax.random.split(key)
            dr = hidden_dropout[i]
        h = _act(activation, z, sub, dr)
    return h


@functools.partial(jax.jit, static_argnames=("activation", "precision"))
def _scorer(params, X, activation, precision):
    """The scorer's forward pass as ONE program: each bias and activation
    fuses into its matmul, so a scoring event holds the output beside the
    design and not every layer's activations of every row."""
    return _forward(params, X, activation, precision=precision)


# -- the compiled training step ------------------------------------------------
# ONE program a structure (`_Net`, plus the steps a chunk), kept by
# `estimator_engine.cached_program`: every hyperparameter that changes no
# shape is an operand (`_hyper`), so the candidates of a grid search, or
# AutoML's DeepLearning steps, on one frame trace and compile the step once.
# Parameters and optimizer state ride the scan as the per-layer tensors
# themselves, not as one flat vector sliced into layers every step: to cut
# a (200, 2) output layer from such a vector a TPU lays the whole vector out
# as a (P/2, 2) array of 128-lane tiles, 64 times its bytes (72 of a 104 us
# step on a v5e at 803-200-200-2, where the step is 22 us without it).

class _Net(NamedTuple):
    """What the compiled step is specialised on: its shapes and structure."""
    shapes: Tuple[Tuple[int, ...], ...]   # W, b of every layer, flat order
    activation: str
    loss: str              # "autoencoder" | "softmax" | "poisson" | "gaussian"
    batch: int
    adaptive: bool         # ADADELTA, else annealed-momentum SGD
    input_dropout: bool
    hidden_dropout: bool
    clamp: bool            # ADADELTA with a finite max_w2


def _hyper(p: dict, n_hidden: int) -> dict:
    """The fit's hyperparameters as float32 operands of the step."""
    hd = p.get("hidden_dropout_ratios")
    if hd is None and p.get("activation", "Rectifier").endswith("WithDropout"):
        hd = [0.5] * n_hidden
    f32 = lambda v: np.float32(v)
    return dict(
        rho=f32(p.get("rho", 0.99)), epsilon=f32(p.get("epsilon", 1e-8)),
        rate=f32(p.get("rate", 0.005)),
        rate_annealing=f32(p.get("rate_annealing", 1e-6)),
        momentum_start=f32(p.get("momentum_start", 0.0)),
        momentum_ramp=f32(max(float(p.get("momentum_ramp", 1e6)), 1.0)),
        momentum_stable=f32(p.get("momentum_stable", 0.0)),
        l1=f32(p.get("l1", 0.0)), l2=f32(p.get("l2", 0.0)),
        max_w2=f32(p.get("max_w2", float("inf"))),
        input_dropout=f32(p.get("input_dropout_ratio", 0.0)),
        hidden_dropout=np.asarray(list(hd) if hd else [], np.float32))


def _clamp_w2(W, max_w2):
    """max_w2: each unit's incoming squared weights summed at most max_w2."""
    norms = jnp.sum(W * W, axis=0, keepdims=True)
    return W * jnp.sqrt(jnp.minimum(max_w2 / jnp.maximum(norms, 1e-12), 1.0))


def _loss(net: _Net, params, xb, yb, wb, key, hp):
    out = _forward(params, xb, net.activation, key,
                   hp["input_dropout"] if net.input_dropout else None,
                   hp["hidden_dropout"] if net.hidden_dropout else None)
    if net.loss == "autoencoder":
        nll = jnp.mean((out - xb) ** 2, axis=1)
    elif net.loss == "softmax":
        logp = jax.nn.log_softmax(out, axis=1)
        nll = -jnp.take_along_axis(logp, yb[:, None].astype(jnp.int32),
                                   axis=1)[:, 0]
    elif net.loss == "poisson":
        nll = jnp.exp(out[:, 0]) - yb * out[:, 0]
    else:
        nll = 0.5 * (out[:, 0] - yb) ** 2
    loss = jnp.sum(nll * wb) / jnp.maximum(jnp.sum(wb), 1e-12)
    # both penalties are always in the program: a coefficient of 0 adds
    # exactly 0 to the loss and to every gradient
    Ws = [W for W, _ in params]
    return (loss + hp["l2"] * sum(jnp.sum(W * W) for W in Ws)
            + hp["l1"] * sum(jnp.sum(jnp.abs(W)) for W in Ws))


def _step(net: _Net, carry, xb, yb, wb, key, hp):
    """One minibatch update (ADADELTA per Neurons.java, or annealed-rate
    momentum SGD) of the per-layer parameters and optimizer state."""
    params, state, it = carry
    g = jax.grad(lambda ps: _loss(net, ps, xb, yb, wb, key, hp))(params)
    tmap = jax.tree.map
    if net.adaptive:
        rho, eps = hp["rho"], hp["epsilon"]
        eg2, ed2 = state
        eg2 = tmap(lambda e, gg: rho * e + (1 - rho) * gg * gg, eg2, g)
        d = tmap(lambda e, s, gg: -jnp.sqrt(s + eps) / jnp.sqrt(e + eps) * gg,
                 eg2, ed2, g)
        ed2 = tmap(lambda s, dd: rho * s + (1 - rho) * dd * dd, ed2, d)
        params = tmap(jnp.add, params, d)
        if net.clamp:
            params = [(_clamp_w2(W, hp["max_w2"]), b) for W, b in params]
        return params, (eg2, ed2), it + 1.0
    rate = hp["rate"] / (1.0 + hp["rate_annealing"] * it)
    ms, mst = hp["momentum_start"], hp["momentum_stable"]
    mom = jnp.minimum(ms + (mst - ms) * it / hp["momentum_ramp"],
                      jnp.maximum(mst, ms))
    (vel,) = state
    vel = tmap(lambda v, gg: mom * v - rate * gg, vel, g)
    return tmap(jnp.add, params, vel), (vel,), it + 1.0


def _chunk_rows(key, nrows: int, nsteps: int, batch: int):
    """A chunk's rows, (nsteps, batch), and a dropout key a step: ONE
    permutation of all rows in order, repeated from its start where the
    chunk asks for more rows than there are (the last step of an epoch
    whose rows are not a multiple of the batch, a short frame)."""
    kperm, kdrop = jax.random.split(key)
    perm = jax.random.permutation(kperm, nrows)
    need = nsteps * batch
    rows = jnp.tile(perm, -(-need // nrows))[:need].reshape(nsteps, batch)
    return rows, jax.random.split(kdrop, nsteps)


def _train_chunk_fn(cloud, net: _Net, nsteps: int):
    """`nsteps` minibatch updates as ONE device program (lax.scan) over the
    design resident in HBM. Each step gathers its own batch's rows from
    the design: no per-batch host→device upload, and no permuted copy of
    the whole design beside it. Replaces the reference's per-row Hogwild
    loop (DeepLearningTask.map) with compiled minibatch SGD; the per-chunk
    reshuffle matches `shuffle_training_data` semantics. On a mesh each
    batch is row-sharded and XLA inserts the gradient psum (the Hogwild
    replacement)."""
    rows_sharding = cloud.row_sharding() if cloud.size > 1 else None

    def build():
        def dl_train_chunk(params, state, X_d, y_d, w_d, key, it0, hp):
            rows, keys = _chunk_rows(key, X_d.shape[0], nsteps, net.batch)

            def body(carry, step):
                idx, k = step
                xb, yb, wb = X_d[idx], y_d[idx], w_d[idx]
                if rows_sharding is not None:
                    xb, yb, wb = (jax.lax.with_sharding_constraint(
                        a, rows_sharding) for a in (xb, yb, wb))
                return _step(net, carry, xb, yb, wb, k, hp), None

            (params, state, _), _ = jax.lax.scan(
                body, (params, state, it0), (rows, keys))
            return params, state

        return jax.jit(dl_train_chunk, donate_argnums=(0, 1))

    return _est.cached_program(cloud, ("dl_train_chunk", net, int(nsteps)),
                               build)


def _train_batch_fn(cloud, net: _Net):
    """One update on a batch the host drew (the `max_runtime_secs` lane)."""
    def build():
        def dl_train_batch(params, state, xb, yb, wb, key, it, hp):
            params, state, _ = _step(net, (params, state, it), xb, yb, wb,
                                     key, hp)
            return params, state

        return jax.jit(dl_train_batch, donate_argnums=(0, 1))

    return _est.cached_program(cloud, ("dl_train_batch", net), build)


_STEPS: list = []


def _steps_counter():
    """`h2o3_dl_steps_total`: minibatch updates run by the step programs
    (memoized: counting must not take the registry's registration lock)."""
    if not _STEPS:
        from ..runtime import metrics_registry as _reg

        _STEPS.append(_reg.counter(
            "h2o3_dl_steps",
            "DeepLearning minibatch updates run by the compiled step "
            "programs"))
    return _STEPS[0]


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
        out = _scorer(self.net_params, X, self.activation, _PRECISION)
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
        rec = np.asarray(_scorer(self.net_params, jnp.asarray(X),
                                 self.activation, _PRECISION), np.float64)
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
        # H2O-3's default is 1, one row a Hogwild update on a CPU core; a
        # compiled synchronous step of one row would be its latency alone,
        # so a step here is 32 rows gathered from the resident design
        mini_batch_size=32,
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
        matrix_cache = "off"
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
            if _est.cache_enabled():
                from . import dataset_cache

                cache0 = dataset_cache.snapshot()
                dinfo, X_dev_pre = _est.design_matrix(
                    train, x,
                    standardize=bool(p.get("standardize", True)),
                    use_all=bool(p.get("use_all_factor_levels", True)))
                matrix_cache = _est.matrix_cache_state(cache0)
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

        hp = _hyper(p, len(hidden))
        adaptive = bool(p.get("adaptive_rate", True))
        net = _Net(
            shapes=tuple(tuple(t.shape) for W, b in params for t in (W, b)),
            activation=activation,
            loss=("autoencoder" if autoenc
                  else "softmax" if problem in ("binomial", "multinomial")
                  else "poisson" if dist == "poisson" else "gaussian"),
            batch=batch, adaptive=adaptive,
            input_dropout=float(hp["input_dropout"]) > 0,
            hidden_dropout=hp["hidden_dropout"].size > 0,
            clamp=adaptive and bool(np.isfinite(hp["max_w2"])))
        n_params = sum(int(np.prod(s)) for s in net.shapes)
        # the keys the fit drew: its initial weights', and each chunk's (the
        # chunk's row order and dropout masks, `_chunk_rows`), with the
        # chunk's first iteration and steps: what replays the fit
        rng_record = {"init_key": kinit, "chunks": []}

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
        # scan over steps; GSPMD turns each step's gather into collectives
        # and psums the sharded-batch gradients automatically.
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
        state = tuple(jax.tree.map(jnp.zeros_like, params)
                      for _ in range(2 if adaptive else 1))
        steps_counter = _steps_counter()
        sm, scored_at, chunk_steps = None, None, set()
        _score_time = 0.0
        with _est.iter_phase() as it_span:
            while seen < total:
                # REST job cancellation (single-process: a per-rank host
                # decision would diverge a multi-process cloud)
                if self.job is not None and jax.process_count() == 1:
                    self.job.check_cancelled()
                if use_scan:
                    upto = min(next_score, total)
                    eff_batch = max(batch * real_frac, 1e-9)
                    steps = max(1, -(-int(upto - seen)
                                     // int(max(eff_batch, 1))))
                    key, sub = jax.random.split(key)
                    rng_record["chunks"].append((sub, it, steps))
                    with tracing.span("iterate.chunk", kind="fit",
                                      steps=steps, batch=batch):
                        params, state = _train_chunk_fn(cloud, net, steps)(
                            params, state, X_dev, y_dev, w_dev, sub,
                            np.float32(it), hp)
                        # CPU mesh: serialize collective executables (see
                        # parallel.mesh.collective_fence)
                        cloudlib.collective_fence(params[0][0])
                    chunk_steps.add(steps)
                    seen += max(int(steps * eff_batch), 1)
                else:
                    steps = 1
                    idx = rng.integers(0, n, batch)
                    xb = jnp.asarray(X[idx])
                    yb = jnp.asarray(yarr[idx])
                    wb = jnp.asarray(w[idx])
                    if rs is not None:
                        xb, yb, wb = (jax.device_put(a, rs)
                                      for a in (xb, yb, wb))
                    key, sub = jax.random.split(key)
                    params, state = _train_batch_fn(cloud, net)(
                        params, state, xb, yb, wb, sub, np.float32(it), hp)
                    cloudlib.collective_fence(params[0][0])
                    seen += batch
                it += steps
                steps_counter.inc(steps)
                if seen >= next_score or seen >= total:
                    next_score += score_every
                    # train_samples_per_iteration=-2 (auto-tune): cap the
                    # wall share spent scoring at score_duty_cycle, like the
                    # reference's computeSamplesPerIteration duty-cycle
                    # target. Early stopping keeps every event (scoring IS
                    # its signal), as does the final event and
                    # score_each_iteration.
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
                    with tracing.span("iterate.score", kind="fit"):
                        model.net_params = params
                        sm = model._make_metrics(train, X_pre=X_score)
                    scored_at = it
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
                    # metrics are local-shard here, so ranks may disagree —
                    # a global any-rank-stops vote keeps the remaining
                    # collective programs aligned across processes
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
            it_span.annotate(iterations=it)

        with tracing.span("fit.metrics", kind="fit"):
            model.net_params = params
            model.scoring_history = ScoringHistory(history)
            # the last scoring event scored these very weights: its
            # metrics are the training metrics
            model.training_metrics = (
                sm if scored_at == it
                else model._make_metrics(train, X_pre=X_score))
            if valid is not None:
                model.validation_metrics = model._make_metrics(valid)
        model.rng_record = rng_record
        # a step reads its batch's rows (features, response, weight); the
        # parameters, the gradient and each optimizer state vector are read
        # and written once
        bytes_per_step = (4 * batch * (nfeat + 2)
                          + 2 * 4 * n_params * (2 + len(state)))
        _est.record_fit(
            "deeplearning", "fused" if use_scan else "batch",
            iterations=it, matrix_cache=matrix_cache, n_devices=cloud.size,
            params=n_params,
            batch=batch, steps_per_chunk=sorted(chunk_steps),
            chunks=len(rng_record["chunks"]),
            bytes_per_step=bytes_per_step,
            flops_per_step=6 * batch * sum(
                int(np.prod(s)) for s in net.shapes[0::2]),
            gather="per_step" if use_scan else "host_batch")
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
            out = _forward(params, X, "Rectifier")
            logp = jax.nn.log_softmax(out, axis=1)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

        grads = jax.grad(loss)(params)
        return jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)

    out = step(params, Xj, yj)
    jax.block_until_ready(out)


DeepLearning = H2ODeepLearningEstimator
