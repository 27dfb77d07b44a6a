"""What `H2OEstimator.train` spends outside `_fit`: the `train` spans less
their `train.fit` children (predictor screen, NA filter, job, publish)."""

import fit_spans


def read(ctx):
    fits = fit_spans.trains(ctx["trace"])
    inner = fit_spans.inside(ctx["trace"], "train.fit", fits)
    if not fits or not inner:
        return None
    return 1e3 * (fit_spans.seconds(fits) - fit_spans.seconds(inner)) / len(fits)
