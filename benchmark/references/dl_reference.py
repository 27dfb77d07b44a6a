"""Plain reference for the `dl` cells, and the comparison that decides
`correct`. Imports nothing of h2o3_tpu and takes nothing it made but the
result under test: the final weights of every layer, the reported training
metrics, and (through the result's `replay`) the fit's initial weights and the
order in which its steps visited the rows.

The estimator, as the configuration states it: a feed-forward network on the
standardized one-hot design — every level of each factor (use_all_factor_
levels) as (1[code == level] - p) / sqrt(p (1 - p)) with p the level's share
of the rows, the numeric columns as (x - mean) / sd, a level or column whose
sd is below 1e-10 divided by 1 (this system's encoding: H2O-3 standardizes
the numerics alone and feeds the levels as 0/1) — hidden layers of the
configuration's widths and activation, two outputs under a softmax, mean
cross-entropy over each minibatch plus l2 * sum(W^2) + l1 * sum(|W|) over the weight matrices, and
ADADELTA on every tensor: E[g^2] <- rho E[g^2] + (1 - rho) g^2, the update
-sqrt(E[d^2] + eps) / sqrt(E[g^2] + eps) g, E[d^2] <- rho E[d^2] + (1 - rho)
d^2, both accumulators from zero.

Before it runs, the reference holds what the fit recorded to the
configuration: the initial weights have the shapes [width] + hidden + [2],
lie within +-sqrt(6 / (fan_in + fan_out)) and start with zero biases, the
final weights have the same shapes, and the row order is exactly the
configuration's steps times its batch (epochs x rows samples in whole
steps), each epoch a permutation of all rows followed by the wrap-around of
its own first rows that fills the epoch's last step (a last, partial epoch
distinct rows). Each departure counts once in `replay_departures`: a fit that stopped
early, drew only part of the rows or was built at other widths would be
replayed as it ran and read 0 on the numbers below.

The reference then runs every step of the fit from the same initial weights
over the same rows in the same order, in float32 with its matmuls at
`highest`, each batch's rows built from the raw codes, and reads

  weight_gap    the worst tensor (a layer's weights or biases): the distance
                of the weights under test from the reference's, against how
                far the reference's moved from their start (Frobenius norms)
  prob_gap      the largest |p_under_test - p_reference| over all rows, both
                networks scored by the reference's forward pass
  logloss_gap   the reported logloss against the reference network's, relative
  auc_gap       the reported AUC against the reference network's exact AUC

`fit(...)` also puts the reference in the program's place: as it stands (it
then passes its own comparison), one precision below the stated (`control`:
every matmul's inputs rounded to bfloat16, as the chip's DEFAULT precision
does), or with one of FAULTS planted."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from refmath import auc_exact, block_rows, rounded

HI = jax.lax.Precision.HIGHEST
FAULTS = ("state_unchanged", "half_batch", "altered_answer", "rho_swapped",
          "half_epoch")
CUTS = {"exact": None, "bf16_inputs": jnp.bfloat16}


class Prepared:
    """The data as the reference holds it: raw codes and numerics on the
    device, the standardization's float32 shifts and scales, the response."""

    def __init__(self, cfg: dict, data: dict):
        self.factors = list(data["codes"])
        self.numerics = list(data["numeric"])
        self.levels = tuple(len(data["domains"][k]) for k in self.factors)
        self.n = len(data["y"])
        self.y_host = data["y"].astype(np.float64)
        mean, sd = [], []
        for k, lv in zip(self.factors, self.levels):
            p = np.bincount(data["codes"][k], minlength=lv) / self.n
            mean.append(p)
            sd.append(np.sqrt(p * (1 - p)))
        for k in self.numerics:
            v = data["numeric"][k].astype(np.float64)
            mean.append([v.mean()])
            sd.append([v.std()])
        sd = np.concatenate(sd)
        self.mean = jnp.asarray(np.concatenate(mean), jnp.float32)
        self.sd = jnp.asarray(np.where(sd < 1e-10, 1.0, sd), jnp.float32)
        self.width = int(self.mean.shape[0])
        self.block = block_rows(self.n)
        self.npad = -(-self.n // self.block) * self.block
        pad = self.npad - self.n
        self.codes = jnp.asarray(np.pad(np.stack(
            [data["codes"][k] for k in self.factors], axis=1),
            ((0, pad), (0, 0))))
        self.nums = jnp.asarray(np.pad(np.stack(
            [data["numeric"][k] for k in self.numerics], axis=1
        ).astype(np.float32), ((0, pad), (0, 0))))
        self.y = jnp.asarray(data["y"].astype(np.int32))
        est = cfg["estimator"]
        self.hidden = [int(h) for h in est["hidden"]]
        self.activation = est["activation"]
        self.batch = int(est["mini_batch_size"])
        # epochs x n samples in whole steps; epoch k ends at the step that
        # reaches sample min((k + 1) n, total), its last step filled from
        # the start of its own permutation
        total = int(float(est["epochs"]) * self.n)
        self.steps = -(-total // self.batch)
        self.epoch_steps = [-(-min(k * self.n, total) // self.batch)
                            for k in range(-(-total // self.n) + 1)]


def _rows(codes, nums, mean, sd, levels):
    """(R, width) rows of the standardized design."""
    parts = [(codes[:, i:i + 1] == jnp.arange(lv, dtype=jnp.int32)[None, :]
              ).astype(jnp.float32) for i, lv in enumerate(levels)]
    parts.append(nums)
    return (jnp.concatenate(parts, axis=1) - mean) / sd


def _net(params, x, activation, cut=None):
    h = x
    for i, (W, b) in enumerate(params):
        z = jnp.dot(rounded(h, cut), rounded(W, cut), precision=HI) + b
        if i == len(params) - 1:
            return z
        h = jax.nn.relu(z) if activation == "Rectifier" else jnp.tanh(z)


@functools.partial(jax.jit, static_argnames=(
    "levels", "activation", "cut", "fault"))
def _train(params, codes, nums, y, order, mean, sd, rho, eps, l1, l2,
           levels, activation, cut, fault):
    """Every step of the fit: `order` is (steps, batch) rows."""
    a, b = (1 - rho, rho) if fault == "rho_swapped" else (rho, 1 - rho)

    def loss(ps, xb, yb):
        logp = jax.nn.log_softmax(_net(ps, xb, activation, cut), axis=1)
        nll = -jnp.take_along_axis(logp, yb[:, None], axis=1)[:, 0]
        return (jnp.mean(nll) + l2 * sum(jnp.sum(W * W) for W, _ in ps)
                + l1 * sum(jnp.sum(jnp.abs(W)) for W, _ in ps))

    def step(carry, idx):
        ps, eg2, ed2 = carry
        g = jax.grad(loss)(ps, _rows(codes[idx], nums[idx], mean, sd,
                                     levels), y[idx])
        eg2 = jax.tree.map(lambda e, gg: a * e + b * gg * gg, eg2, g)
        d = jax.tree.map(lambda e, s, gg: -jnp.sqrt(s + eps)
                         / jnp.sqrt(e + eps) * gg, eg2, ed2, g)
        ed2 = jax.tree.map(lambda s, dd: a * s + b * dd * dd, ed2, d)
        if fault != "state_unchanged":
            ps = jax.tree.map(jnp.add, ps, d)
        return (ps, eg2, ed2), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (params, _, _), _ = jax.lax.scan(step, (params, zeros, zeros), order)
    return params


@functools.partial(jax.jit, static_argnames=("levels", "activation", "block"))
def _probs(params, codes, nums, mean, sd, levels, activation, block: int):
    """P(class 1) for every (padded) row, a block of rows at a time."""
    nb = codes.shape[0] // block

    def one(args):
        c, m = args
        out = _net(params, _rows(c, m, mean, sd, levels), activation)
        return jax.nn.softmax(out, axis=1)[:, 1]

    return jax.lax.map(one, (codes.reshape(nb, block, -1),
                             nums.reshape(nb, block, -1))).reshape(-1)


def fit(prep: Prepared, params: dict, init, order, precision: str = "exact",
        fault: str | None = None):
    """The reference's weights after the fit, [(W, b)] as numpy."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    order = jnp.asarray(np.asarray(order, np.int32).reshape(-1, prep.batch))
    if fault == "half_batch":
        order = order[0::2]
    f32 = lambda k, d: jnp.float32(params.get(k, d))
    out = _train([(jnp.asarray(W), jnp.asarray(b)) for W, b in init],
                 prep.codes, prep.nums, prep.y, order, prep.mean, prep.sd,
                 f32("rho", 0.99), f32("epsilon", 1e-8), f32("l1", 0.0),
                 f32("l2", 0.0), prep.levels, prep.activation,
                 CUTS[precision], fault)
    out = [(np.asarray(W), np.asarray(b)) for W, b in out]
    if fault == "altered_answer":
        out[-1] = (out[-1][0] * np.float32(1.01), out[-1][1])
    return out


def probs(prep: Prepared, weights) -> np.ndarray:
    p = _probs([(jnp.asarray(W), jnp.asarray(b)) for W, b in weights],
               prep.codes, prep.nums, prep.mean, prep.sd, prep.levels,
               prep.activation, prep.block)
    return np.asarray(p, np.float64)[:prep.n]


def logloss(p: np.ndarray, y: np.ndarray) -> float:
    p = np.clip(p, 1e-15, 1 - 1e-15)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))


def weight_gap(got, ref, init) -> float:
    gaps = []
    for g_l, r_l, i_l in zip(got, ref, init):
        for g, r, i in zip(g_l, r_l, i_l):
            moved = np.linalg.norm(np.asarray(r, np.float64) - i)
            gaps.append(np.linalg.norm(np.asarray(g, np.float64) - r)
                        / max(moved, 1e-30))
    return float(max(gaps))


def _sizes(prep: Prepared) -> list:
    return [prep.width] + prep.hidden + [2]


def own_replay(prep: Prepared, params: dict):
    """Initial weights (uniform-adaptive: U(-sqrt(6 / (fan_in + fan_out)),
    +...), zero biases) and a row order (one permutation an epoch, repeated
    from its start to fill the epoch's last step) drawn from the fit's seed,
    for the reference put in the program's place."""
    rng = np.random.default_rng(int(params.get("seed", 0)))
    sizes = _sizes(prep)
    init = []
    for fi, fo in zip(sizes, sizes[1:]):
        lim = np.sqrt(6.0 / (fi + fo))
        init.append((rng.uniform(-lim, lim, (fi, fo)).astype(np.float32),
                     np.zeros(fo, np.float32)))
    order = []
    for s0, s1 in zip(prep.epoch_steps, prep.epoch_steps[1:]):
        rows = (s1 - s0) * prep.batch
        order.append(np.tile(rng.permutation(prep.n), -(-rows // prep.n))
                     [:rows])
    return init, np.concatenate(order)


def _shapes_depart(prep: Prepared, weights) -> int:
    """Layers whose weights or biases are not at the configuration's
    widths."""
    sizes = _sizes(prep)
    if len(weights) != len(sizes) - 1:
        return 1
    return sum(np.shape(W) != (fi, fo) or np.shape(b) != (fo,)
               for (W, b), fi, fo in zip(weights, sizes, sizes[1:]))


def replay_departures(prep: Prepared, init, order, weights) -> int:
    """How far the fit's recorded inputs depart from the configuration's:
    each layer at other widths (initial or final), each initial weight
    matrix outside its uniform-adaptive range, each bias not started at
    zero, a row order of another length, and each epoch whose rows are not
    one permutation of all rows with its own wrap-around tail."""
    out = _shapes_depart(prep, init) + _shapes_depart(prep, weights)
    if out:
        return out
    sizes = _sizes(prep)
    for (W, b), fi, fo in zip(init, sizes, sizes[1:]):
        lim = np.sqrt(6.0 / (fi + fo)) * (1 + 1e-6)   # float32's limit
        out += int(not np.all(np.abs(np.asarray(W)) <= lim))
        out += int(np.any(np.asarray(b) != 0))
    order = np.asarray(order).reshape(-1)
    if order.size != prep.steps * prep.batch:
        return out + 1
    every = np.arange(prep.n)
    for s0, s1 in zip(prep.epoch_steps, prep.epoch_steps[1:]):
        ep = order[s0 * prep.batch:s1 * prep.batch]
        head, tail = ep[:prep.n], ep[prep.n:]
        if head.size < prep.n:      # a last, partial epoch: distinct rows
            out += int(np.unique(head).size != head.size
                       or head.min() < 0 or head.max() >= prep.n)
            continue
        out += int(not np.array_equal(np.sort(head), every))
        out += int(not np.array_equal(tail, head[:tail.size]))
    return out


def prepare(cfg: dict, data: dict) -> Prepared:
    return Prepared(cfg, data)


def compare(cfg: dict, prep: Prepared, result: dict) -> dict:
    init, order = result["replay"]()
    departs = replay_departures(prep, init, order, result["weights"])
    if departs:
        inf = float("inf")
        return {"replay_departures": float(departs), "weight_gap": inf,
                "prob_gap": inf, "logloss_gap": inf, "auc_gap": inf}
    ref = fit(prep, result["params"], init, order)
    p_ref = probs(prep, ref)
    ll = logloss(p_ref, prep.y_host)
    return {
        "replay_departures": 0.0,
        "weight_gap": weight_gap(result["weights"], ref, init),
        "prob_gap": float(np.max(np.abs(probs(prep, result["weights"])
                                        - p_ref))),
        "logloss_gap": abs(result["logloss"] - ll) / ll,
        "auc_gap": abs(result["auc"] - auc_exact(p_ref, prep.y_host)),
    }


def control(cfg: dict, prep: Prepared, params: dict,
            precision: str = "bf16_inputs", fault: str | None = None) -> dict:
    """The reference in the program's place, one precision below the stated.
    `half_epoch`: the fit stops after half its steps and records only
    those."""
    init, order = own_replay(prep, params)
    if fault == "half_epoch":
        order = order[:prep.steps // 2 * prep.batch]
    weights = fit(prep, params, init, order, precision, fault)
    p = probs(prep, weights)
    return {"params": dict(params), "weights": weights,
            "logloss": logloss(p, prep.y_host),
            "auc": auc_exact(p, prep.y_host),
            "replay": lambda: (init, order)}


def faulty(cfg: dict, prep: Prepared, params: dict, fault: str) -> dict:
    """The reference in the program's place with one fault planted."""
    return control(cfg, prep, params, "exact", fault)
