"""Required work of one IRLS iteration of a dense GLM, from shapes: the
linear predictor (2NP), the weights and working response (a few per row), the
Gram X'WX (2NP^2, counted in full: symmetry is the implementation's to use)
and X'Wz (2NP), over one read of the N x P float32 design."""

from __future__ import annotations


def step(shapes: dict) -> dict:
    n, p = shapes["rows"], shapes["coefficients"]
    return {"ops": 2.0 * n * p * p + 4.0 * n * p,
            "bytes": 4.0 * n * p + 3 * n * 4}
