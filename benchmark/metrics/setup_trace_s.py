"""Seconds jax spent tracing and lowering inside the warm-up fit: the
`xla_trace_s` attrs tallied on its spans (first_fit.py)."""

import first_fit


def read(ctx):
    return first_fit.tallied(ctx, "xla_trace_s")
