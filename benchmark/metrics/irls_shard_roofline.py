"""A chip's share of ITS roofline under the row sharding: the least time for
the required work of the window's iterations over one chip's rows
(counts/<algo>.py `step` at rows / chips) over the first chip's device time
in the fused IRLS program. (`irls_roofline` sets ALL rows against one chip's
time, so on four chips it would read four times this.)"""

import work_counts


def read(ctx):
    pattern = getattr(ctx["algo"], "TRACE_STEP_PROGRAM", None)
    if pattern is None or not ctx["steps"] or not ctx["chips"]:
        return None
    device_s = sum(d for _, d in ctx["trace"].program_events(pattern))
    if not device_s:
        return None
    shard = dict(ctx["shapes"], rows=ctx["shapes"]["rows"] / ctx["chips"])
    work = work_counts.counts(ctx["cfg"]["algo"]).step(shard)
    least = work_counts.least_time(work, ctx["device_kind"])
    return (100.0 * least["seconds"] * ctx["steps"] / device_s,
            f"(bound: {least['bound']}; {shard['rows']:.0f} rows a chip)")
