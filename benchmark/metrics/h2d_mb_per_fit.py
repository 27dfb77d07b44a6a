"""Bytes the fits of the window sent to the device, a fit
(runtime/phases.py `accounted_h2d` counts them unconditionally)."""


def read(ctx):
    moved = ctx["counters"].get("phases", {}).get("bytes_h2d")
    if moved is None or not ctx["fits"]:
        return None
    return moved / 1e6 / ctx["fits"]
