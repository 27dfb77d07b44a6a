"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / traced window, averaged over the chips)."""


def read(ctx):
    busy = ctx["trace"].busy_seconds()
    if not busy or not ctx["traced_s"]:
        return None
    return 100.0 * (1.0 - busy / ctx["traced_s"])
