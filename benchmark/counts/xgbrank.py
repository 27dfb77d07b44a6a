"""Required work of one boosting round of LambdaMART on a depthwise histogram
tree, from shapes: what the ALGORITHM has to touch, never what a kernel
chooses to do (the padding of every query to the largest, the one-hot
matmul's multiply-adds and sibling subtraction's saving are all the
implementation's).

One histogram pass (`hist`), as counts/gbm.py reckons it: every row's codes
once (features x code_bits / 8 bytes), its g, h and node id once (12 bytes),
and the scatter's three adds (w, g, h) per row and feature.

One pairwise pass (`pairs`): for every REAL ordered pair (r_i > r_j; the
fit plan's `pairs`, never its padded `pair_slots`) 12 operations — one
compare of the relevances, two differences (margins, discounts), one
sigmoid, four multiplies (gain difference x discount difference x 1/IDCG,
x rho, x (1 - rho)) and four adds (lambda into G_i and G_j, its curvature
into H_i and H_j) — and per row the margin read and (g, h) written once
(12 bytes; ranks and discounts are a sort a query, O(n log n), left out).

One round (`step`): one pairwise pass, one histogram pass a level, the
partition a level (the chosen feature's code read, the node id written: 5
bytes a row), the leaf totals (g, h and node id read, three adds a row) and
the margin update (read and write, one add)."""

from __future__ import annotations

PAIR_OPS = 12.0


def hist(shapes: dict) -> dict:
    n, f = shapes["rows"], shapes["features"]
    return {"ops": 3.0 * n * f,
            "bytes": n * f * shapes["code_bits"] / 8.0 + 12.0 * n}


def pairs(shapes: dict) -> dict:
    return {"ops": PAIR_OPS * shapes["pairs"],
            "bytes": 12.0 * shapes["rank_rows"]}


def step(shapes: dict) -> dict:
    n, d = shapes["rows"], shapes["depth"]
    one, rank = hist(shapes), pairs(shapes)
    return {"ops": rank["ops"] + d * one["ops"] + (3.0 + 1.0) * n,
            "bytes": rank["bytes"] + d * (one["bytes"] + 5.0 * n)
            + (12.0 + 8.0) * n}
