"""What the dataset cache spares every later fit on a frame: the warm-up
fit's `design.matrix` (extraction), `design.bins` (quantile binning) and
`design.codes` (packing and upload) (first_fit.py)."""

import first_fit


def read(ctx):
    return first_fit.seconds(ctx, "design.matrix", "design.bins",
                             "design.codes")
