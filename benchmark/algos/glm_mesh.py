"""Adapter: a `glm` configuration whose design no single chip holds → the
same H2OGeneralizedLinearEstimator on the `hosts` mesh of the chips jax holds.

Data, frame, estimator, result, control and trace names are algos/glm.py's
own, by reference. What differs, because the rows are four chips' worth:

- every train() runs with `DataInfo._expand` refused. That function builds
  the dense float64 one-hot design on the HOST (46 GB a copy at 7,250,000 x
  796, several copies live): a program that reaches it from the mesh lane
  cannot end inside a run, so the cell fails at once and says why, where it
  would otherwise hang until it is killed;
- `info_lines` and `shapes` say how the fit was laid out over the chips,
  from the fit plan (`rows_per_device`, `local_blocks`, `fold_bytes`)."""

from __future__ import annotations

import contextlib

import manifest

_glm = manifest.load_module("algos", "glm")

REFERENCE = _glm.REFERENCE
RESPONSE = _glm.RESPONSE
TRACE_STEP_PROGRAM = _glm.TRACE_STEP_PROGRAM
make_data = _glm.make_data
make_columns = _glm.make_columns
make_frame = _glm.make_frame
make_estimator = _glm.make_estimator
lower_precision = _glm.lower_precision
steps = _glm.steps
result = _glm.result

LAYOUT = ("n_devices", "n_shards", "rows_per_device", "local_blocks",
          "fold_bytes")


@contextlib.contextmanager
def no_dense_host_design():
    from h2o3_tpu.models.model_base import DataInfo

    def refuse(self, frame, fit):
        raise RuntimeError(
            "the fit reached DataInfo._expand, the dense float64 one-hot "
            "design on the host: at this configuration's rows that is tens "
            "of GB a copy, so the cell refuses it")

    stated = DataInfo._expand
    DataInfo._expand = refuse
    try:
        yield
    finally:
        DataInfo._expand = stated


def train(est, frame) -> None:
    with no_dense_host_design():
        _glm.train(est, frame)


def shapes(cfg: dict, est) -> dict:
    plan = _glm._last_plan()
    return {**_glm.shapes(cfg, est),
            **{k: int(plan[k]) for k in LAYOUT if k in plan}}


def info_lines(est) -> list:
    plan = _glm._last_plan()
    return _glm.info_lines(est) + [
        "glm layout: " + " ".join(f"{k}={plan.get(k)}" for k in LAYOUT)]
