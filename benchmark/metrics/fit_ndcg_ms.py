"""The closing NDCG of a ranking fit: the `fit.ndcg` spans (the frame's
matrix extracted again, the forest scored page by page, NDCG@k by query on
the host)."""

import fit_spans


def read(ctx):
    return fit_spans.per_fit_ms(ctx["trace"], "fit.ndcg")
