"""The upload a fit: the `design.upload` spans (the cut of the compact packs
into one shard a chip, their transfer, the expand program's dispatch and the
read of its first element that ends the wait)."""

import fit_spans


def read(ctx):
    return fit_spans.per_fit_ms(ctx["trace"], "design.upload")
