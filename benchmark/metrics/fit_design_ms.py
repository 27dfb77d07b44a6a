"""The design build a fit: the `fit.design` spans (DataInfo statistics from
the codes, packing, the cache look-up, H2D, the expand program's dispatch)."""

import fit_spans


def read(ctx):
    return fit_spans.per_fit_ms(ctx["trace"], "fit.design")
