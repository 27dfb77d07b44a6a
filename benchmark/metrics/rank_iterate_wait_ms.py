"""What the host adds around the two programs of a boosting round: the
`fit.iterate` spans less the device time of the per-tree program's AND the
pairwise pass's events inside them (`fit_iterate_wait_ms` knows one program
and would count the pass as waiting)."""

import fit_spans


def read(ctx):
    trace = ctx["trace"]
    fits = fit_spans.trains(trace)
    spans = fit_spans.inside(trace, "fit.iterate", fits)
    patterns = [getattr(ctx["algo"], name, None)
                for name in ("TRACE_STEP_PROGRAM", "TRACE_RANK_PROGRAM")]
    if not fits or not spans or None in patterns:
        return None
    device = sum(max(0.0, min(s + d, hi) - max(s, lo))
                 for p in patterns for s, d in trace.program_events(p)
                 for _, lo, hi in spans)
    return 1e3 * (fit_spans.seconds(spans) - device) / len(fits)
