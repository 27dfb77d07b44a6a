"""The histogram kernel's share of its roofline: the least time for the
required work of one histogram pass (counts/gbm.py `hist`) times the passes
the trace holds, over the device time of the Pallas histogram calls."""

import re

import work_counts


def read(ctx):
    pattern = getattr(ctx["algo"], "TRACE_HIST_OPS", None)
    if pattern is None or not ctx["trace"].devices:
        return None
    rx = re.compile(pattern)
    calls = [d for n, _, d in ctx["trace"].devices[0]["ops"] if rx.search(n)]
    if not calls:
        return None
    work = work_counts.counts(ctx["cfg"]["algo"]).hist(ctx["shapes"])
    least = work_counts.least_time(work, ctx["device_kind"])
    return (100.0 * least["seconds"] * len(calls) / sum(calls),
            f"(bound: {least['bound']}; {len(calls)} passes)")
