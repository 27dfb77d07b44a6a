"""Required work of one tree of a depthwise histogram GBM, from shapes: what
the ALGORITHM has to touch, never what a kernel chooses to do (the one-hot
matmul's multiply-adds, the widening of packed codes, sibling subtraction's
saving are all the implementation's).

One histogram pass (`hist`): every row's packed codes once (features x
code_bits / 8 bytes), its g, h and node id once (12 bytes), and the scatter's
three adds (w, g, h) per row and feature. One tree (`step`): gradients from
the margins (read margin and y, write g and h: 16 bytes and about 10
operations a row), one histogram pass a level, the partition a level (the
chosen feature's code read, the node id written: 5 bytes a row), the leaf
totals (g, h and node id read, three adds a row) and the margin update (read
and write, one add)."""

from __future__ import annotations


def hist(shapes: dict) -> dict:
    n, f = shapes["rows"], shapes["features"]
    return {"ops": 3.0 * n * f,
            "bytes": n * f * shapes["code_bits"] / 8.0 + 12.0 * n}


def step(shapes: dict) -> dict:
    n, d = shapes["rows"], shapes["depth"]
    one = hist(shapes)
    return {"ops": d * one["ops"] + (10.0 + 3.0 + 1.0) * n,
            "bytes": d * (one["bytes"] + 5.0 * n) + (16.0 + 12.0 + 8.0) * n}
