"""The warm-up fit's design build: its `fit.design` and, where the fit has
one, its `fit.objective` (first_fit.py)."""

import first_fit


def read(ctx):
    return first_fit.seconds(ctx, "fit.design", "fit.objective")
