"""Adapter: a `dl` configuration → H2ODeepLearningEstimator.

Data, columns and frame are algos/glm.py's own, by reference: the Airlines
stand-in at its categorical widths, drawn from the seed. What this adapter
adds:

- the program must state the precision the configuration states (its
  matmuls float32 at HIGHEST, `models/deeplearning.py` `_PRECISION`) and
  record the keys a fit drew (`rng_record`, `_chunk_rows`); a program that
  does neither cannot be compared with the reference, so set-up refuses it at
  once and says why;
- `result` hands the reference the fit's final weights and, through
  `replay`, its initial weights and its row order, made from the keys the
  fit recorded by the program's own `_init_params` and `_chunk_rows`, when
  the comparison asks for them (after the window); the reference holds
  both to the configuration before it replays them;
- `steps` is the minibatch updates of the fit, read from the program's
  counter `h2o3_dl_steps_total` around `train()`."""

from __future__ import annotations

import contextlib

import numpy as np

import manifest

_glm = manifest.load_module("algos", "glm")

REFERENCE = "dl_reference"
RESPONSE = _glm.RESPONSE
make_columns = _glm.make_columns
make_frame = _glm.make_frame

# the compiled step's program, as the trace names it
TRACE_STEP_PROGRAM = r"^jit_dl_train_chunk\("


def _program():
    import jax

    from h2o3_tpu.models import deeplearning

    stated = getattr(deeplearning, "_PRECISION", None)
    if stated != jax.lax.Precision.HIGHEST or not hasattr(
            deeplearning, "_chunk_rows"):
        raise RuntimeError(
            "models/deeplearning.py states no HIGHEST matmul precision or "
            "records no keys of its fits: this configuration states float32 "
            "at HIGHEST and is compared step by step, which this program "
            "cannot run")
    return deeplearning


def make_data(cfg: dict, seed: int) -> dict:
    _program()
    return _glm.make_data(cfg, seed)


def make_estimator(cfg: dict, overrides: dict):
    from h2o3_tpu.models.deeplearning import H2ODeepLearningEstimator

    return H2ODeepLearningEstimator(**{**cfg["estimator"], **overrides})


def _steps_total() -> float:
    from h2o3_tpu.runtime import metrics_registry

    c = metrics_registry.get("h2o3_dl_steps")
    return c.value() if c is not None else 0.0


def train(est, frame) -> None:
    before = _steps_total()
    est.train(y=RESPONSE, training_frame=frame)
    est.bench_steps = int(_steps_total() - before)


def steps(est) -> int:
    return int(getattr(est, "bench_steps", 0))


def _layers(est) -> list:
    return [int(W.shape[0]) for W, _ in est.model.net_params] + [
        int(est.model.net_params[-1][0].shape[1])]


def _replay(record: dict, rows: int, batch: int, sizes: list,
            activation: str):
    dl = _program()
    init = [(np.asarray(W), np.asarray(b))
            for W, b in dl._init_params(record["init_key"], sizes, activation)]
    order = np.concatenate([
        np.asarray(dl._chunk_rows(key, rows, n, batch)[0]).reshape(-1)
        for key, _, n in record["chunks"]])
    return init, order


def result(cfg: dict, est, overrides: dict) -> dict:
    """The fit as the reference reads it. The weights stay device arrays
    until the comparison reads them, and `replay` makes the initial weights
    and the row order only when it is called: nothing here waits for the
    device."""
    model = est.model
    record, layers, act = model.rng_record, _layers(est), model.activation
    rows, batch = int(cfg["rows"]), int(cfg["estimator"]["mini_batch_size"])
    return {"params": {**cfg["estimator"], **overrides},
            "weights": list(model.net_params),
            "logloss": float(est.logloss()), "auc": float(est.auc()),
            "replay": lambda: _replay(record, rows, batch, layers, act)}


@contextlib.contextmanager
def lower_precision():
    """The control at the cell's own size: the program itself with every
    matmul of models/deeplearning.py one precision below the stated, the
    chip's DEFAULT (one bfloat16 pass) in place of HIGHEST. The program has
    no option for it, so the module's `_PRECISION` is set for as long as
    this lasts and the step programs traced with the other value are dropped
    before and after."""
    import jax

    from h2o3_tpu.parallel import mesh

    dl = _program()

    def drop_traced():
        mesh.cloud().__dict__.pop("_est_fns_cache", None)

    dl._PRECISION = jax.lax.Precision.DEFAULT
    drop_traced()
    try:
        yield
    finally:
        dl._PRECISION = jax.lax.Precision.HIGHEST
        drop_traced()


def _last_plan() -> dict:
    from h2o3_tpu.models import estimator_engine

    plans = [p for p in estimator_engine.est_stats()["plans"]
             if p.get("algo") == "deeplearning"]
    return plans[-1] if plans else {}


def shapes(cfg: dict, est) -> dict:
    """What counts/dl.py counts from: the layer widths from the inputs to
    the outputs, the batch, the steps a fit; and the steps ONE run of the
    step program makes, where every run of the fit made as many (else 0)."""
    plan = _last_plan()
    per_run = plan.get("steps_per_chunk") or []
    return {"layers": _layers(est), "batch": int(plan.get("batch", 0)),
            "steps_per_fit": steps(est),
            "steps_per_chunk": int(per_run[0]) if len(per_run) == 1 else 0}


def info_lines(est) -> list:
    plan = _last_plan()
    return [f"dl plan: path={plan.get('path')} params={plan.get('params')} "
            f"batch={plan.get('batch')} steps_per_chunk="
            f"{plan.get('steps_per_chunk')} chunks={plan.get('chunks')} "
            f"gather={plan.get('gather')} bytes_per_step="
            f"{plan.get('bytes_per_step')} flops_per_step="
            f"{plan.get('flops_per_step')} matrix_cache="
            f"{plan.get('matrix_cache')}",
            f"auc={est.auc():.6f} logloss={est.logloss():.6f} "
            f"steps={steps(est)}"]
