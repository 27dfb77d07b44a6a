"""The MLP step's share of its roofline: the least time for the required
work of one step (counts/dl.py `step`) times the steps of the step
program's runs in the trace (dl_step_us says how they are counted), over
those runs' device time."""

import work_counts


def read(ctx):
    pattern = getattr(ctx["algo"], "TRACE_STEP_PROGRAM", None)
    per_run = ctx["shapes"].get("steps_per_chunk")
    if pattern is None or not per_run:
        return None
    events = ctx["trace"].program_events(pattern)
    device_s = sum(d for _, d in events)
    if not device_s:
        return None
    work = work_counts.counts(ctx["cfg"]["algo"]).step(ctx["shapes"])
    least = work_counts.least_time(work, ctx["device_kind"])
    return (100.0 * least["seconds"] * len(events) * per_run / device_s,
            f"(bound: {least['bound']})")
