"""The training metrics a fit: the `fit.metrics` spans (scoring program,
D2H of the fitted means, ModelMetrics on the host)."""

import fit_spans


def read(ctx):
    return fit_spans.per_fit_ms(ctx["trace"], "fit.metrics")
