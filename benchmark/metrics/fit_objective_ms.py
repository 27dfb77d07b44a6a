"""The ranking objective's set-up a fit: the `fit.objective` spans (grouping
the rows by query, the (Q, G) index, the ideal DCGs, the upload of the group
tensors)."""

import fit_spans


def read(ctx):
    return fit_spans.per_fit_ms(ctx["trace"], "fit.objective")
