"""Adapter: a `gbm` configuration → H2OGradientBoostingEstimator.

Data stands in for HIGGS (11,000,000 x 28): the columns are the
configuration's, one by one with HIGGS's kinds of marginals — transverse
momenta, missing energy and masses positive with long right tails,
pseudorapidities symmetric, azimuths uniform, b-tags three-valued — and the
response is a non-linear function of masses and momenta with noise, all drawn
from the seed. The long tails are kept: over them `UniformAdaptive` leaves
tail bins of a handful of rows, which is what the file does to the split
search."""

from __future__ import annotations

import numpy as np

REFERENCE = "gbm_reference"
RESPONSE = "signal"


def _column(spec: dict, rng, n: int) -> np.ndarray:
    kind = spec["kind"]
    if kind == "lognormal":
        return np.exp(np.float32(spec["sigma"])
                      * rng.standard_normal(n, dtype=np.float32))
    if kind == "gamma":
        return (rng.standard_gamma(spec["shape"], n)
                / spec["shape"]).astype(np.float32)
    if kind == "normal":
        v = np.float32(spec["sd"]) * rng.standard_normal(n, dtype=np.float32)
        return np.clip(v, -spec["clip"], spec["clip"], out=v)
    if kind == "uniform":
        hw = np.float32(spec["half_width"])
        return (rng.random(n, dtype=np.float32) * 2 - 1) * hw
    if kind == "three_valued":
        edges = np.cumsum(spec["probs"])[:-1].astype(np.float32)
        pick = np.searchsorted(edges, rng.random(n, dtype=np.float32))
        return np.asarray(spec["values"], np.float32)[pick]
    raise ValueError(f"column kind {kind!r}")


def make_data(cfg: dict, seed: int) -> dict:
    n = int(cfg["rows"])
    specs = cfg["columns"]
    streams = np.random.SeedSequence(int(seed)).spawn(len(specs) + 1)
    cols, logs = {}, {}
    resp = cfg["response"]
    eta = np.full(n, np.float32(resp["bias"]), np.float32)
    for s, spec in zip(streams, specs):
        v = _column(spec, np.random.default_rng(s), n)
        cols[spec["name"]] = v
        if spec["kind"] in ("lognormal", "gamma"):
            logs[spec["name"]] = np.log(v)
        if spec["effect"]:
            eta += np.float32(spec["effect"]) * logs.get(spec["name"], v)
    a, b, c = resp["abs_diff"]
    eta += np.float32(c) * np.abs(logs[a] - logs[b])
    a, c = resp["square"]
    eta += np.float32(c) * logs[a] * logs[a]
    a, b, c = resp["product"]
    eta += np.float32(c) * logs[a] * logs[b]
    u = np.random.default_rng(streams[-1]).random(n, dtype=np.float32)
    y = (u < 1 / (1 + np.exp(-eta))).astype(np.int32)
    return {"names": [s["name"] for s in specs], "columns": cols, "y": y}


def make_columns(data: dict) -> dict:
    from h2o3_tpu.frame.vec import Vec

    cols = {k: Vec(data["columns"][k], "real") for k in data["names"]}
    cols[RESPONSE] = Vec(data["y"], "enum", domain=["background", "signal"])
    return cols


def make_frame(columns: dict):
    from h2o3_tpu.frame.frame import Frame

    return Frame(dict(columns))


def make_estimator(cfg: dict, overrides: dict):
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    return H2OGradientBoostingEstimator(**{**cfg["estimator"], **overrides})


def train(est, frame) -> None:
    est.train(y=RESPONSE, training_frame=frame)


def steps(est) -> int:
    """Trees built: one run of the per-tree program each."""
    return int(est.model.ntrees_built)


def result(cfg: dict, est, overrides: dict) -> dict:
    """The fit as plain numpy: the forest in its heap layout (children of
    node i are 2i+1 and 2i+2; `bin` b sends codes <= b left), the grid the
    program quantized on, and the training metrics it reported."""
    model = est.model
    forest = model.forest[0]
    return {"params": {**cfg["estimator"], **overrides},
            "f0": float(model.f0),
            "feat": np.asarray(forest.feat, np.int32),
            "bin": np.asarray(forest.bin, np.int32),
            "is_split": np.asarray(forest.is_split, bool),
            "value": np.asarray(forest.value, np.float32),
            "edges": [np.asarray(e, np.float64) for e in model.bm.edges],
            "names": list(model.x),
            "logloss": float(est.logloss()), "auc": float(est.auc())}


def _last_plan() -> dict:
    from h2o3_tpu.ops.histogram import kernel_stats

    plans = kernel_stats()["plans"]
    return plans[-1] if plans else {}


def shapes(cfg: dict, est) -> dict:
    """What counts/gbm.py counts from: the padded rows the program runs over
    (rows in its kernel plan where it says so), features, bins with the NA
    bin, depth, bits of a packed code, trees a fit."""
    model = est.model
    return {"rows": int(getattr(model, "_npad", cfg["rows"])),
            "features": len(model.x), "bins": int(model.bm.nbins),
            "depth": int(model.max_depth),
            "code_bits": int(_last_plan().get("pack_bits") or 8),
            "steps_per_fit": steps(est)}


def info_lines(est) -> list:
    plan = _last_plan()
    levels = ", ".join(
        f"{lv.get('level')}:{lv.get('method')}x{lv.get('n_nodes')}"
        for lv in plan.get("levels", []))
    return [f"gbm kernel plan {plan.get('tag')}: pack_bits="
            f"{plan.get('pack_bits')} shards={plan.get('n_shards')} "
            f"devices={plan.get('n_devices')} levels [{levels}]",
            f"auc={est.auc():.6f} logloss={est.logloss():.6f} "
            f"trees={steps(est)}"]


# The per-tree program (`tree_jit` inside shared_tree._build_tree_step_fns)
# and the Pallas histogram calls inside it, as the trace names them: the
# kernel's own `name=` since this configuration came, the enclosing jit's
# name before it.
TRACE_STEP_PROGRAM = r"^jit_tree_jit\("
TRACE_HIST_OPS = r"^%(tree_hist|build_histograms_pallas)"
