"""What the host adds around the compiled loop: the `fit.iterate` spans
(dispatch up to the read of the final state) less the device time of the
step program's events inside them."""

import fit_spans


def read(ctx):
    trace = ctx["trace"]
    fits = fit_spans.trains(trace)
    spans = fit_spans.inside(trace, "fit.iterate", fits)
    pattern = getattr(ctx["algo"], "TRACE_STEP_PROGRAM", None)
    if not fits or not spans or pattern is None:
        return None
    device = sum(max(0.0, min(s + d, hi) - max(s, lo))
                 for s, d in trace.program_events(pattern)
                 for _, lo, hi in spans)
    return 1e3 * (fit_spans.seconds(spans) - device) / len(fits)
