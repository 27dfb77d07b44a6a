"""The whole step's share of the chip's peak: the least time the chip could
take for the REQUIRED work of the steps in the window (counts/<algo>.py
`step`, peaks.json) over the window's time. Host gaps count against it.
Nothing in it is read from the trace: the time is the host's clock over the
traced window's whole fits, the steps are the program's own count and the
work comes from shapes, so its source is `host_clock`."""

import work_counts


def read(ctx):
    if not ctx["steps"] or not ctx["window_s"]:
        return None
    work = work_counts.counts(ctx["cfg"]["algo"]).step(ctx["shapes"])
    least = work_counts.least_time(work, ctx["device_kind"])
    share = 100.0 * least["seconds"] * ctx["steps"] / (
        ctx["window_s"] * ctx["chips"])
    return share, f"(bound: {least['bound']})"
