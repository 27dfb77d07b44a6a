"""The pairwise pass's share of its roofline: the least time for the required
work of one pass (counts/xgbrank.py `pairs`: the real ordered pairs, never
the padded slots) times the passes the trace holds, over their device time."""

import work_counts


def read(ctx):
    pattern = getattr(ctx["algo"], "TRACE_RANK_PROGRAM", None)
    if pattern is None or "pairs" not in ctx["shapes"]:
        return None
    passes = [d for _, d in ctx["trace"].program_events(pattern)]
    if not passes or not sum(passes):
        return None
    work = work_counts.counts(ctx["cfg"]["algo"]).pairs(ctx["shapes"])
    least = work_counts.least_time(work, ctx["device_kind"])
    return (100.0 * least["seconds"] * len(passes) / sum(passes),
            f"(bound: {least['bound']}; {len(passes)} passes)")
