"""Required work of one minibatch step of a feed-forward network trained
with ADADELTA, from shapes: what the ALGORITHM has to touch, never what the
program chooses to do (a gather's layout, the step's fusions).

For a batch of B rows over layer widths n_0 (the inputs) ... n_L (the
outputs), with W = sum n_i n_(i+1) weights and P = W + sum n_(i+1)
parameters: the forward pass (2BW), the weight gradients (2BW), the
gradients of every layer's inputs but the first's (2B(W - n_0 n_1)), and
ADADELTA's 15 operations a parameter (two accumulators of four each, two
square roots, a division, a product and the add). Bytes: the batch's rows
read once (n_0 features, the response and the weight, 4 bytes each), and
the parameters, the gradient and the two accumulators each read and written
once (8 x 4 bytes a parameter). Activations, at B x a few hundred, stay
beside the matmuls and are not counted."""

from __future__ import annotations

ADADELTA_OPS = 15.0


def step(shapes: dict) -> dict:
    sizes, b = shapes["layers"], shapes["batch"]
    weights = sum(a * c for a, c in zip(sizes, sizes[1:]))
    params = weights + sum(sizes[1:])
    return {"ops": 2.0 * b * (3 * weights - sizes[0] * sizes[1])
            + ADADELTA_OPS * params,
            "bytes": 4.0 * b * (sizes[0] + 2) + 32.0 * params}
