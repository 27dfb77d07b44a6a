"""Device time a fit of the binned training-metrics program
(`shared_tree._binom_binned_stats`): the device's share of `fit_metrics_ms`,
which the span `metrics.binned` brackets from the host."""

PROGRAM = r"^jit__binom_binned_stats\("


def read(ctx):
    events = ctx["trace"].program_events(PROGRAM)
    if not events or not ctx["fits"]:
        return None
    return 1e3 * sum(d for _, d in events) / ctx["fits"]
