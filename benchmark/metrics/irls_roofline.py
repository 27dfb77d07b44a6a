"""The IRLS program's share of its roofline: the least time for the required
work of its iterations (counts/glm.py `step`) over its device time."""

import work_counts


def read(ctx):
    pattern = getattr(ctx["algo"], "TRACE_STEP_PROGRAM", None)
    if pattern is None or not ctx["steps"]:
        return None
    device_s = sum(d for _, d in ctx["trace"].program_events(pattern))
    if not device_s:
        return None
    work = work_counts.counts(ctx["cfg"]["algo"]).step(ctx["shapes"])
    least = work_counts.least_time(work, ctx["device_kind"])
    return (100.0 * least["seconds"] * ctx["steps"] / device_s,
            f"(bound: {least['bound']})")
