"""Device duration of the compiled MLP step program by the minibatch steps
its runs in the trace made. Every run is one chunk of `steps_per_chunk`
steps (the fit plan, through algos/dl.py `shapes`), so the steps are the
runs the trace holds times that: a run whose events the profiler did not
keep costs neither its time nor its steps. Where a fit's runs differ in
length there is nothing to read."""


def read(ctx):
    pattern = getattr(ctx["algo"], "TRACE_STEP_PROGRAM", None)
    per_run = ctx["shapes"].get("steps_per_chunk")
    if pattern is None or not per_run:
        return None
    events = ctx["trace"].program_events(pattern)
    if not events:
        return None
    return (1e6 * sum(d for _, d in events) / (len(events) * per_run),
            f"({len(events)} runs of {per_run} steps; {ctx['fits']} fits)")
