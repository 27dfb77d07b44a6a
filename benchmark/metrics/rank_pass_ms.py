"""Device duration of the pairwise pass (`jit__lambdarank_pass`) by the
boosting rounds it served: one pass a tree."""


def read(ctx):
    pattern = getattr(ctx["algo"], "TRACE_RANK_PROGRAM", None)
    if pattern is None or not ctx["steps"]:
        return None
    events = ctx["trace"].program_events(pattern)
    if not events:
        return None
    return 1e3 * sum(d for _, d in events) / ctx["steps"]
