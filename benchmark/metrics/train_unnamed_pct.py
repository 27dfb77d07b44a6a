"""The share of a fit that only the two containers cover: self time of the
`train` spans plus self time of their `train.fit` children, over the `train`
spans. It tells a later PR that its new code has no span."""

import fit_spans


def read(ctx):
    trace = ctx["trace"]
    fits = fit_spans.trains(trace)
    inner = fit_spans.inside(trace, "train.fit", fits)
    if not fits or not inner:
        return None
    return 100.0 * (fit_spans.self_seconds(trace, fits)
                    + fit_spans.self_seconds(trace, inner)
                    ) / fit_spans.seconds(fits)
