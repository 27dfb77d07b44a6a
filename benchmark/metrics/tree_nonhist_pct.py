"""The share of the per-tree program's device time outside the histogram
calls: split search, partition gathers, leaf totals, margin update."""


def read(ctx):
    step = getattr(ctx["algo"], "TRACE_STEP_PROGRAM", None)
    hist = getattr(ctx["algo"], "TRACE_HIST_OPS", None)
    if step is None or hist is None:
        return None
    program_s = sum(d for _, d in ctx["trace"].program_events(step))
    hist_s = ctx["trace"].op_seconds(hist)
    if not program_s or not hist_s:
        return None
    return 100.0 * (1.0 - hist_s / program_s)
