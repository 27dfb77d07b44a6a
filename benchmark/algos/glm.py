"""Adapter: a `glm` configuration → H2OGeneralizedLinearEstimator.

Data stands in for the Airlines on-time set at its categorical widths: the
columns and level counts are the configuration's, the values are drawn from
the seed (skewed levels for the airports, a logistic response with effects
drawn from the seed). The original generator is bench.py's `bench_glm`."""

from __future__ import annotations

import contextlib

import numpy as np

REFERENCE = "glm_reference"
RESPONSE = "IsDepDelayed"


def make_data(cfg: dict, seed: int) -> dict:
    n = int(cfg["rows"])
    cats = cfg["categorical_levels"]
    nums = cfg["numeric_columns"]
    streams = np.random.SeedSequence(int(seed)).spawn(len(cats) + len(nums) + 1)
    eta = np.zeros(n, np.float32)
    codes, numeric = {}, {}
    for s, (name, levels) in zip(streams, cats.items()):
        rng = np.random.default_rng(s)
        p = 1.0 / np.arange(1, levels + 1) ** float(cfg["level_skew"])
        c = rng.choice(levels, size=n, p=p / p.sum()).astype(np.int32)
        eta += rng.normal(0, 0.3, levels).astype(np.float32)[c]
        codes[name] = c
    for s, (name, spec) in zip(streams[len(cats):], nums.items()):
        rng = np.random.default_rng(s)
        if spec["kind"] == "uniform_int":
            v = rng.integers(spec["lo"], spec["hi"], n).astype(np.float32)
        else:
            v = np.abs(rng.normal(spec["mean"], spec["sd"], n)
                       ).astype(np.float32)
        eta += np.float32(spec["effect"]) * (v - np.float32(spec["center"]))
        numeric[name] = v
    u = np.random.default_rng(streams[-1]).random(n, dtype=np.float32)
    y = (u < 1 / (1 + np.exp(-eta))).astype(np.int32)
    return {"codes": codes, "numeric": numeric, "y": y,
            "domains": {k: [f"{k[:2]}{i:03d}" for i in range(int(v))]
                        for k, v in cats.items()}}


def make_columns(data: dict) -> dict:
    from h2o3_tpu.frame.vec import Vec

    cols = {k: Vec(c, "enum", domain=data["domains"][k])
            for k, c in data["codes"].items()}
    cols.update({k: Vec(v, "real") for k, v in data["numeric"].items()})
    cols[RESPONSE] = Vec(data["y"], "enum", domain=["NO", "YES"])
    return cols


def make_frame(columns: dict):
    from h2o3_tpu.frame.frame import Frame

    return Frame(dict(columns))


def make_estimator(cfg: dict, overrides: dict):
    from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator

    return H2OGeneralizedLinearEstimator(**{**cfg["estimator"], **overrides})


def train(est, frame) -> None:
    est.train(y=RESPONSE, training_frame=frame)


@contextlib.contextmanager
def lower_precision():
    """The control at the cell's own size: the program itself with every IRLS
    matmul of models/glm.py one precision below the stated, the chip's DEFAULT
    (one bfloat16 pass) in place of HIGHEST. The program has no option for
    it, so the module's `_HI` is set for as long as this lasts and the fused
    programs traced with the other value are dropped before and after. Where
    there is no chip DEFAULT and HIGHEST are the same arithmetic: the tests'
    control is the reference's emulation (glm_reference.py)."""
    import jax

    from h2o3_tpu.models import glm
    from h2o3_tpu.parallel import mesh

    def drop_traced():
        mesh.cloud().__dict__.pop("_est_fns_cache", None)

    stated = glm._HI
    if stated != jax.lax.Precision.HIGHEST:
        raise RuntimeError(f"models/glm.py states {stated}, not HIGHEST: the "
                           "configuration's precision is out of date")
    glm._HI = jax.lax.Precision.DEFAULT
    drop_traced()
    try:
        yield
    finally:
        glm._HI = stated
        drop_traced()


def _last_plan() -> dict:
    from h2o3_tpu.models import estimator_engine

    plans = [p for p in estimator_engine.est_stats()["plans"]
             if p.get("algo") == "glm"]
    return plans[-1] if plans else {}


def steps(est) -> int:
    return int(_last_plan().get("iterations", 0))


def result(cfg: dict, est, overrides: dict) -> dict:
    return {"params": {**cfg["estimator"], **overrides},
            "coef": {k: float(v) for k, v in est.coef().items()},
            "logloss": float(est.logloss()), "auc": float(est.auc()),
            "iterations": steps(est)}


def shapes(cfg: dict, est) -> dict:
    return {"rows": int(cfg["rows"]), "coefficients": len(est.coef()),
            "steps_per_fit": steps(est)}


def info_lines(est) -> list:
    plan = _last_plan()
    return [f"glm plan: path={plan.get('path')} iterations="
            f"{plan.get('iterations')} converged={plan.get('converged')} "
            f"matrix_cache={plan.get('matrix_cache')}",
            f"auc={est.auc():.6f} logloss={est.logloss():.6f} "
            f"coefficients={len(est.coef())}"]


# What the trace prints today for the fused IRLS program (`inner` inside
# glm._irls_device_fn; stable scopes are the tracing issue's).
TRACE_STEP_PROGRAM = r"^jit_inner\("
