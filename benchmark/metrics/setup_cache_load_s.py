"""Seconds of the warm-up fit's compile requests that the persistent cache
answered: its `xla.cache_load` spans (first_fit.py)."""

import first_fit


def read(ctx):
    found = first_fit.requests(ctx, "xla.cache_load")
    if found is None:
        return None
    return (sum(s["duration_s"] for s in found), f"({len(found)} programs)")
