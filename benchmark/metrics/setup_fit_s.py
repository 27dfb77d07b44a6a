"""The warm-up fit: the process's first `train` span, the program's whole
share of `setup_s` (the rest is `program.import` and the adapter's data and
columns). Read from the program's span ring (first_fit.py)."""

import first_fit


def read(ctx):
    got = first_fit.tree(ctx)
    return None if got is None else got[0]["duration_s"]
