"""Device duration of the per-tree program by the trees it built."""


def read(ctx):
    pattern = getattr(ctx["algo"], "TRACE_STEP_PROGRAM", None)
    if pattern is None or not ctx["steps"]:
        return None
    events = ctx["trace"].program_events(pattern)
    if not events:
        return None
    return 1e3 * sum(d for _, d in events) / ctx["steps"]
