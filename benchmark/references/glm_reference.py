"""Plain reference for the `glm` cells, and the comparison that decides
`correct`. Imports nothing of h2o3_tpu and takes nothing it made but the
result under test: the coefficients by name and the reported metrics.

The estimator, as the configuration states it: the unpenalized binomial
maximum-likelihood fit (IRLS, lambda 0) of the response on the one-hot
design — every factor level but the first of each factor, the numeric
columns, an intercept. The reference builds that design itself from the raw
codes, in row blocks on the device (plain jax.numpy, `highest`), adds the
blocks' Gram partials in float64 on the host, solves in float64, and iterates
from zero until the coefficients stop moving (a start from a copy of
chip_smoke.py's `_logistic_mle_f64`). Fitted probabilities are float64 table
look-ups on the host, for the reference's coefficients and for the
coefficients under test alike, so no parametrisation has to match.

`fit(..., precision="bf16_inputs")` is the same reference with its matmuls
as the chip runs a float32 matmul at its DEFAULT precision: one bfloat16 pass,
the inputs rounded to bfloat16 to nearest, float32 results. On the chip a dot
at `Precision.DEFAULT` ("default" below) gives the same bits as this emulation
(PERF.md section 4), which also runs where there is no chip, so it is the
control of the tests. "high" is three-pass bfloat16. In blocks of 65,536 rows
with float64 across them, neither can be told from the program at the cell's
own size: there the control is the program itself with its IRLS matmuls at
DEFAULT (algos/glm.py `lower_precision`); PERF.md has the readings of all."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from refmath import auc_exact, block_rows, rounded

PRECISIONS = {
    # name: (what becomes of a matmul's float32 inputs, matmul passes)
    "exact": (lambda a: a, jax.lax.Precision.HIGHEST),
    "high": (lambda a: a, jax.lax.Precision.HIGH),
    "bf16_inputs": (lambda a: rounded(a, jnp.bfloat16),
                    jax.lax.Precision.HIGHEST),
    "default": (lambda a: a, jax.lax.Precision.DEFAULT),
}


def _design(codes, nums, levels):
    """(R, P) block of the design: levels 1.. of each factor, the
    standardized numerics, an intercept."""
    parts = [(codes[i][:, None] == jnp.arange(1, lv, dtype=jnp.int32)[None, :]
              ).astype(jnp.float32) for i, lv in enumerate(levels)]
    parts.append(nums.T)
    parts.append(jnp.ones((codes.shape[1], 1), jnp.float32))
    return jnp.concatenate(parts, axis=1)


@functools.partial(jax.jit, static_argnames=("levels", "block", "precision"))
def _gram_blocks(codes, nums, y, w, beta, levels, block: int, precision: str):
    """(blocks, P, P+1): per block X'W[X | z] at the current beta."""
    cut, prec = PRECISIONS[precision]
    nb = y.shape[0] // block

    def one_block(args):
        c, m, yb, wb = args
        x = _design(c, m, levels)
        eta = jnp.dot(cut(x), cut(beta), precision=prec)
        mu = jax.nn.sigmoid(eta)
        v = jnp.maximum(mu * (1 - mu), 1e-10)
        z = eta + (yb - mu) / v
        xw = x * (v * wb)[:, None]
        xz = jnp.concatenate([x, z[:, None]], axis=1)
        return jnp.dot(cut(xw).T, cut(xz), precision=prec)

    split = lambda a: a.reshape(a.shape[0], nb, block).transpose(1, 0, 2)
    return jax.lax.map(one_block, (split(codes), split(nums),
                                   y.reshape(nb, block), w.reshape(nb, block)))


@functools.partial(jax.jit, static_argnames=("levels", "block"))
def _score_blocks(codes, nums, resid, levels, block: int):
    """(blocks, P): per block X'(y - mu)."""
    nb = resid.shape[0] // block

    def one_block(args):
        c, m, r = args
        return jnp.dot(r, _design(c, m, levels),
                       precision=jax.lax.Precision.HIGHEST)

    split = lambda a: a.reshape(a.shape[0], nb, block).transpose(1, 0, 2)
    return jax.lax.map(one_block, (split(codes), split(nums),
                                   resid.reshape(nb, block)))


def logloss(eta: np.ndarray, y: np.ndarray) -> float:
    return float(np.mean(np.logaddexp(0.0, eta) - y * eta))


class Prepared:
    """The data as the reference holds it: raw codes and standardized
    numerics on the device, the float64 copies on the host."""

    def __init__(self, data: dict):
        self.factors = list(data["codes"])
        self.numerics = list(data["numeric"])
        self.names = {k: list(data["domains"][k]) for k in self.factors}
        self.levels = tuple(len(self.names[k]) for k in self.factors)
        self.n = len(data["y"])
        self.block = block_rows(self.n)
        self.npad = -(-self.n // self.block) * self.block
        pad = self.npad - self.n
        self.y_host = data["y"].astype(np.float64)
        self.codes_host = [data["codes"][k] for k in self.factors]
        raw = [data["numeric"][k].astype(np.float64) for k in self.numerics]
        self.mean = np.asarray([v.mean() for v in raw])
        self.sd = np.asarray([v.std() for v in raw])
        self.nums_host = [(v - m) / s for v, m, s in
                          zip(raw, self.mean, self.sd)]
        self.codes = jnp.asarray(np.pad(np.stack(self.codes_host),
                                        ((0, 0), (0, pad))))
        self.nums = jnp.asarray(np.pad(
            np.stack(self.nums_host).astype(np.float32), ((0, 0), (0, pad))))
        self.y = jnp.asarray(np.pad(data["y"].astype(np.float32), (0, pad)))
        self.w = jnp.asarray(np.pad(np.ones(self.n, np.float32), (0, pad)))
        self.p = sum(lv - 1 for lv in self.levels) + len(self.numerics) + 1

    def eta(self, beta: np.ndarray) -> np.ndarray:
        """Float64 linear predictor for coefficients in the reference's own
        order."""
        out = np.full(self.n, beta[-1], np.float64)
        at = 0
        for c, lv in zip(self.codes_host, self.levels):
            out += np.concatenate([[0.0], beta[at:at + lv - 1]])[c]
            at += lv - 1
        for v in self.nums_host:
            out += beta[at] * v
            at += 1
        return out

    def from_named(self, coef: dict) -> np.ndarray:
        """Coefficients by the program's names (`Column.Level`, raw-scale
        numerics, `Intercept`) → the reference's order and scaling. A factor
        level that has no name carries 0, whichever level that is."""
        beta = np.zeros(self.p)
        icpt = float(coef.get("Intercept", 0.0))
        at = 0
        for k, lv in zip(self.factors, self.levels):
            t = np.asarray([float(coef.get(f"{k}.{nm}", 0.0))
                            for nm in self.names[k]])
            beta[at:at + lv - 1] = t[1:] - t[0]
            icpt += t[0]
            at += lv - 1
        for k, m, s in zip(self.numerics, self.mean, self.sd):
            b = float(coef.get(k, 0.0))
            beta[at] = b * s
            icpt += b * m
            at += 1
        beta[-1] = icpt
        unknown = set(coef) - {"Intercept", *self.numerics} - {
            f"{k}.{nm}" for k in self.factors for nm in self.names[k]}
        if unknown:
            raise ValueError(f"coefficients the design has no column for: "
                             f"{sorted(unknown)[:5]}")
        return beta


def _solve(gram: np.ndarray, xy: np.ndarray) -> np.ndarray:
    try:
        return np.linalg.solve(gram, xy)
    except np.linalg.LinAlgError:
        return np.linalg.lstsq(gram, xy, rcond=None)[0]


FAULTS = ("state_unchanged", "half_batch", "altered_answer")


def fit(prep: Prepared, max_iterations: int, precision: str = "exact",
        fault: str | None = None):
    """IRLS from zero. Returns (beta, Gram diagonal at beta, iterations).
    `fault` plants one of FAULTS, for the tests and the readings that show
    the comparison fails them: the solve returns its coefficients unchanged;
    every second row left out of the Gram; the largest coefficient altered
    by 1%."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    beta = np.zeros(prep.p)
    diag = np.ones(prep.p)
    w = prep.w
    if fault == "half_batch":
        w = w * (jnp.arange(prep.npad) % 2 == 0)
    for it in range(1, max_iterations + 1):
        part = _gram_blocks(prep.codes, prep.nums, prep.y, w,
                            jnp.asarray(beta, jnp.float32), prep.levels,
                            prep.block, precision)
        gz = np.asarray(part, np.float64).sum(axis=0)
        new = beta if fault == "state_unchanged" else \
            _solve(gz[:, :-1], gz[:, -1])
        diag = np.diag(gz[:, :-1]).copy()
        delta = float(np.max(np.abs(new - beta)))
        beta = new
        if delta < 1e-6:
            break
    if fault == "altered_answer":
        beta[np.argmax(np.abs(beta[:-1]))] *= 1.01
    return beta, diag, it


def named(prep: Prepared, beta: np.ndarray) -> dict:
    """The reference's coefficients under the program's names (for putting
    the reference in the program's place)."""
    out, at = {}, 0
    for k, lv in zip(prep.factors, prep.levels):
        for nm, b in zip(prep.names[k][1:], beta[at:at + lv - 1]):
            out[f"{k}.{nm}"] = float(b)
        at += lv - 1
    icpt = float(beta[-1])
    for k, m, s in zip(prep.numerics, prep.mean, prep.sd):
        out[k] = float(beta[at] / s)
        icpt -= out[k] * m
        at += 1
    out["Intercept"] = icpt
    return out


def check(prep: Prepared, result: dict, max_iterations: int) -> dict:
    beta_ref, diag, _ = fit(prep, max_iterations, "exact")
    beta_got = prep.from_named(result["coef"])
    eta_ref, eta_got = prep.eta(beta_ref), prep.eta(beta_got)
    p_ref = 1 / (1 + np.exp(-eta_ref))
    p_got = 1 / (1 + np.exp(-eta_got))
    resid = np.pad((prep.y_host - p_got).astype(np.float32),
                   (0, prep.npad - prep.n))
    score = np.asarray(_score_blocks(prep.codes, prep.nums, jnp.asarray(resid),
                                     prep.levels, prep.block),
                       np.float64).sum(axis=0)
    ll = logloss(eta_ref, prep.y_host)
    scale = np.maximum(np.abs(beta_ref), np.median(np.abs(beta_ref)))
    return {
        "prob_gap": float(np.max(np.abs(p_got - p_ref))),
        "coef_gap": float(np.max(np.abs(beta_got - beta_ref) / scale)),
        "score_gap": float(np.max(np.abs(score) / np.sqrt(diag))),
        "logloss_gap": abs(result["logloss"] - ll) / ll,
        "auc_gap": abs(result["auc"] - auc_exact(eta_ref, prep.y_host)),
    }


def prepare(cfg: dict, data: dict) -> Prepared:
    return Prepared(data)


def compare(cfg: dict, prep: Prepared, result: dict) -> dict:
    return check(prep, result, int(cfg["reference"]["max_iterations"]))


def control(cfg: dict, prep: Prepared, params: dict,
            precision: str = "bf16_inputs", fault: str | None = None) -> dict:
    """The reference in the program's place, one precision below the stated."""
    beta, _, its = fit(prep, int(cfg["reference"]["max_iterations"]),
                       precision, fault)
    eta = prep.eta(beta)
    return {"params": dict(params), "coef": named(prep, beta),
            "logloss": logloss(eta, prep.y_host),
            "auc": auc_exact(eta, prep.y_host), "iterations": its}


def faulty(cfg: dict, prep: Prepared, params: dict, fault: str) -> dict:
    """The reference in the program's place with one fault planted."""
    return control(cfg, prep, params, "exact", fault)
