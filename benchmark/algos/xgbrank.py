"""Adapter: an `xgbrank` configuration → H2OXGBoostEstimator with
`objective="rank:ndcg"` and a `group_column`.

Data stands in for MSLR-WEB30K Fold1's training split (2,270,296 x 136 in
18,919 queries): the query sizes and every column come from the seed, with
the file's kinds of columns. A query has a length in terms and a shift of its
own; a document has three latent factors (text match, authority, clicks) that
drive its relevance and, through each stream's own match, the 25 kinds of
text features of the five streams (body, anchor, title, url, whole document):
integer counts with long right tails and many zeros, ratios with a handful of
values, per-query constants (IDF), continuous scores (BM25, the three language
models), then the 11 columns of the document itself. Columns are laid out as
in the file, kind-major: features 1-5 are the covered query terms of the five
streams, 6-10 their ratio, and so on to 126-136, the document's own. Rows of a
query are contiguous and `qid` ascends."""

from __future__ import annotations

import numpy as np

REFERENCE = "xgbrank_reference"
RESPONSE, GROUP = "rel", "qid"
TF_KINDS = ("sum", "min", "max", "mean", "variance")


def query_sizes(spec: dict, queries: int, rows: int, rng) -> np.ndarray:
    """`queries` sizes from a lognormal law, fixed up so that the smallest is
    `min`, the largest exactly `max` and the sum exactly `rows`."""
    lo, hi = int(spec["min"]), int(spec["max"])
    raw = np.exp(spec["sigma"] * rng.standard_normal(queries))
    sizes = np.clip(np.rint(raw * rows / raw.sum()), lo, hi).astype(np.int64)
    sizes[np.argmax(sizes)], sizes[np.argmin(sizes)] = hi, lo
    while sizes.sum() != rows:
        diff = int(rows - sizes.sum())
        free = np.flatnonzero((sizes > lo + 1) & (sizes < hi - 1))
        sizes[rng.choice(free, min(abs(diff), len(free)), replace=False)] \
            += np.sign(diff)
    return sizes


def _f32(rng, n):
    return rng.standard_normal(n, dtype=np.float32)


def _count(x):
    """A non-negative integer count as float32."""
    return np.floor(np.maximum(x, 0)).astype(np.float32)


def _stream_columns(st: dict, rng, text, qlen, idf, whole_len):
    """The 25 columns of one stream, in the file's order of kinds."""
    n = len(text)
    here = (rng.random(n, dtype=np.float32) >= np.float32(st["empty"])
            ).astype(np.float32)
    m = np.float32(st["match"])
    match = m * text + np.sqrt(1 - m * m) * _f32(rng, n)
    if whole_len is None:
        length = here * (1 + _count(np.exp(
            np.float32(st["length_mu"])
            + np.float32(st["length_sigma"]) * _f32(rng, n))))
    else:
        length = whole_len
    p = 1 / (1 + np.exp(-(np.float32(st["cover"]) + 1.1 * match)))
    covered = here * np.minimum(_count(qlen * p + rng.random(
        n, dtype=np.float32)), qlen)
    tf = {"sum": covered * _count(np.exp(0.4 + 0.5 * match
                                         + 0.8 * _f32(rng, n))
                                  * np.maximum(length, 1) ** 0.25)}
    tf["min"] = (covered == qlen) * _count(
        tf["sum"] / qlen * rng.random(n, dtype=np.float32) ** 2)
    tf["max"] = np.ceil(tf["sum"] * (0.35 + 0.65 * rng.random(
        n, dtype=np.float32)) / np.maximum(covered, 1)).astype(np.float32)
    tf["mean"] = tf["sum"] / qlen
    tf["variance"] = (tf["max"] - tf["mean"]) ** 2 * rng.random(
        n, dtype=np.float32)
    safe_len = np.maximum(length, 1)
    cols = [covered, covered / qlen, length, idf]
    cols += [tf[k] for k in TF_KINDS]
    cols += [tf[k] / (safe_len ** (2 if k == "variance" else 1))
             for k in TF_KINDS]
    cols += [tf[k] * (idf ** (2 if k == "variance" else 1)) for k in TF_KINDS]
    cols.append((covered == qlen).astype(np.float32) * here)
    cols.append(here / (1 + np.exp(-(match + 0.5 * _f32(rng, n)))))
    sat = tf["sum"] * 2.2 / (tf["sum"] + 1.2 * (
        0.25 + 0.75 * safe_len / np.float32(st["mean_length"])))
    cols.append(idf * sat * (1 + 0.1 * _f32(rng, n)) * here)
    for smooth in (0.6, 0.8, 1.0):          # LMIR.ABS, LMIR.DIR, LMIR.JM
        cols.append(-qlen * (np.float32(st["lm_base"]) - smooth * match
                             + 0.4 * _f32(rng, n)) * here
                    - (1 - here) * qlen * np.float32(st["lm_empty"]))
    return cols, length


def _document_column(spec: dict, rng, latent: dict, n: int) -> np.ndarray:
    z = (np.float32(spec.get("effect", 0.0)) * latent[spec.get("on", "authority")]
         + np.float32(spec["sigma"]) * _f32(rng, n))
    kind = spec["kind"]
    if kind == "count":
        return _count(np.exp(np.float32(spec["mu"]) + z)
                      - np.float32(spec.get("minus", 0.0)))
    if kind == "rare_count":
        on = rng.random(n, dtype=np.float32) < np.float32(spec["share"]) \
            * np.exp(0.8 * latent[spec["on"]])
        return on * (1 + _count(np.exp(np.float32(spec["mu"]) + z)))
    if kind == "byte":
        return np.clip(np.rint(np.float32(spec["mu"]) + 40 * z), 0, 255
                       ).astype(np.float32)
    raise ValueError(f"document column kind {kind!r}")


def column_names(cfg: dict) -> list:
    streams = [s["name"] for s in cfg["streams"]]
    kinds = (["covered_query_term_number", "covered_query_term_ratio",
              "stream_length", "idf"]
             + [f"{k}_of_term_frequency" for k in TF_KINDS]
             + [f"{k}_of_length_normalized_term_frequency" for k in TF_KINDS]
             + [f"{k}_of_tfidf" for k in TF_KINDS]
             + ["boolean_model", "vector_space_model", "bm25", "lmir_abs",
                "lmir_dir", "lmir_jm"])
    return ([f"{k}.{s}" for k in kinds for s in streams]
            + [d["name"] for d in cfg["document"]])


def _require_ranking_plan() -> None:
    """`shapes` reads the ranking objective's plan off the program's fit
    plan. A checkout from before the program recorded one cannot run this
    cell: say so at once, not after a fit."""
    import inspect

    from h2o3_tpu.ops.histogram import record_fit_plan

    if "rank" not in inspect.signature(record_fit_plan).parameters:
        raise SystemExit("benchmark: this checkout's fit plan holds no "
                         "`rank` entry; the xgbrank cells cannot run on it")


def make_data(cfg: dict, seed: int) -> dict:
    _require_ranking_plan()
    n, queries = int(cfg["rows"]), int(cfg["queries"])
    streams = cfg["streams"]
    seeds = np.random.SeedSequence(int(seed)).spawn(
        3 + len(streams) + len(cfg["document"]))
    rng = np.random.default_rng(seeds[0])
    sizes = query_sizes(cfg["query_sizes"], queries, n, rng)
    qid = np.repeat(1 + 15 * np.arange(queries, dtype=np.int64), sizes)
    of_query = np.repeat(np.arange(queries), sizes)
    qlen = (1 + np.minimum(rng.poisson(cfg["query_terms_mean"] - 1, queries), 7)
            ).astype(np.float32)[of_query]
    latent = {k: _f32(rng, n) for k in ("text", "authority", "clicks")}
    rel_spec = cfg["relevance"]
    score = (np.float32(rel_spec["query"]) * _f32(rng, queries)[of_query]
             + np.float32(rel_spec["noise"]) * _f32(rng, n))
    for k, v in latent.items():
        score += np.float32(rel_spec[k]) * v
    cuts = np.quantile(score, np.cumsum(rel_spec["shares"])[:-1])
    rel = np.searchsorted(cuts, score).astype(np.float32)

    per_stream, whole_len = [], 0
    for st, s in zip(streams, seeds[3:]):
        srng = np.random.default_rng(s)
        idf = (qlen * np.exp(np.float32(st["idf_mu"]) + 0.35 * _f32(
            srng, queries)[of_query])).astype(np.float32)
        whole = whole_len if st.get("whole") else None
        cols, length = _stream_columns(st, srng, latent["text"], qlen, idf,
                                       whole)
        whole_len = whole_len + length
        per_stream.append(cols)
    names = column_names(cfg)
    columns = {}
    for k in range(len(per_stream[0])):
        for s in range(len(streams)):
            columns[names[len(columns)]] = np.asarray(per_stream[s][k], np.float32)
    for spec, s in zip(cfg["document"], seeds[3 + len(streams):]):
        columns[spec["name"]] = _document_column(
            spec, np.random.default_rng(s), latent, n)
    return {"names": names, "columns": columns, "rel": rel, "qid": qid}


# What `result` needs besides its arguments (run.py hands it the estimator
# only): the training split's query ids and relevance.
_HELD = {}


def make_columns(data: dict) -> dict:
    from h2o3_tpu.frame.vec import Vec

    _HELD.clear()
    _HELD.update(qid=data["qid"], rel=data["rel"])
    cols = {k: Vec(data["columns"][k], "real") for k in data["names"]}
    cols[GROUP] = Vec(data["qid"].astype(np.float64), "real")
    cols[RESPONSE] = Vec(data["rel"], "real")
    return cols


def make_frame(columns: dict):
    from h2o3_tpu.frame.frame import Frame

    return Frame(dict(columns))


def make_estimator(cfg: dict, overrides: dict):
    from h2o3_tpu.models.xgboost import H2OXGBoostEstimator

    return H2OXGBoostEstimator(**{**cfg["estimator"], **overrides})


def train(est, frame) -> None:
    est.train(y=RESPONSE, x=[n for n in frame.names
                             if n not in (RESPONSE, GROUP)],
              training_frame=frame)


def steps(est) -> int:
    """Boosting rounds: one pairwise pass and one per-tree program each."""
    return int(est.model.ntrees_built)


def _program_pair_grads(qid, rel, k: int):
    """`margins -> (G, H)` of every training row by the program's own
    pairwise pass: `_make_lambdarank` as `H2OXGBoostEstimator._fit` calls it,
    set up once at the first call (after the window: nothing of it is timed),
    then the jitted `_lambdarank_pass` the fits of the window ran, on the same
    group tensors, at the margins the comparison hands it."""
    held = []

    def grads(margin):
        import jax.numpy as jnp

        from h2o3_tpu.models.xgboost import _make_lambdarank

        if not held:
            held.append(_make_lambdarank(np.asarray(qid, np.int64),
                                         np.asarray(rel, np.float64), k))
        g, h = held[0](jnp.asarray(margin, jnp.float32), None)
        return np.asarray(g, np.float64), np.asarray(h, np.float64)

    return grads


def result(cfg: dict, est, overrides: dict) -> dict:
    """The fit as plain numpy: the forest in its heap layout (children of
    node i are 2i+1 and 2i+2; `bin` b sends codes <= b left), the grid the
    program quantized on, the NDCG@k it reported, its checksum over its own
    final training margins (their root mean squared error against the
    relevance, from `training_metrics`), and `pair_grads`, the program's
    pairwise pass for the comparison to question."""
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
            "ndcg": float(est.ndcg()),
            "rmse": float(model.training_metrics.rmse),
            "pair_grads": _program_pair_grads(
                _HELD["qid"], _HELD["rel"],
                int(cfg["estimator"].get("ndcg_k", 10)))}


def _last_plan() -> dict:
    from h2o3_tpu.ops.histogram import kernel_stats

    plans = kernel_stats()["plans"]
    return plans[-1] if plans else {}


def shapes(cfg: dict, est) -> dict:
    """What counts/xgbrank.py counts from: the tree program's shapes as the
    GBM adapter gives them, and the ranking objective's own plan (queries,
    largest group, real ordered pairs, padded pair slots) from the program's
    fit plan."""
    model = est.model
    rank = _last_plan().get("rank") or {}
    return {"rows": int(getattr(model, "_npad", cfg["rows"])),
            "rank_rows": int(cfg["rows"]),
            "features": len(model.x), "bins": int(model.bm.nbins),
            "depth": int(model.max_depth),
            "code_bits": int(_last_plan().get("pack_bits") or 8),
            "steps_per_fit": steps(est),
            **{k: rank[k] for k in ("queries", "group_max", "pairs",
                                    "pair_slots") if k in rank}}


def info_lines(est) -> list:
    plan = _last_plan()
    levels = ", ".join(
        f"{lv.get('level')}:{lv.get('method')}x{lv.get('n_nodes')}"
        f"@{lv.get('row_chunk')}" for lv in plan.get("levels", []))
    return [f"xgbrank kernel plan {plan.get('tag')}: partition_read="
            f"{plan.get('partition_read')} pack_bits={plan.get('pack_bits')} "
            f"levels [{levels}]",
            f"rank plan {plan.get('rank')}",
            f"ndcg={est.ndcg():.6f} trees={steps(est)}"]


# The per-tree program of the custom-objective lane (`single_tree_jit` inside
# shared_tree._build_tree_step_fns), the Pallas histogram calls inside it, and
# the pairwise pass (`models/xgboost._lambdarank_pass`), as the trace names
# them.
TRACE_STEP_PROGRAM = r"^jit_single_tree_jit\("
TRACE_HIST_OPS = r"^%(tree_hist|build_histograms_pallas)"
TRACE_RANK_PROGRAM = r"^jit__lambdarank_pass\("
