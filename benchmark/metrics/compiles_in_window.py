"""Compilations inside the window (runtime/phases.py `xla_counts`)."""


def read(ctx):
    return float(ctx["counters"]["xla"]["compiles"])
