"""Seconds of the warm-up fit's compile requests that the compiler answered:
its `xla.compile` spans (first_fit.py). 0 where the persistent cache held
every program; the note names the three longest."""

import first_fit


def read(ctx):
    found = first_fit.requests(ctx, "xla.compile")
    if found is None:
        return None
    longest = sorted(found, key=lambda s: -s["duration_s"])[:3]
    note = "".join(f"; {s['attrs'].get('program')}: {s['duration_s']:.3f}"
                   for s in longest)
    return (sum(s["duration_s"] for s in found),
            f"({len(found)} programs{note})")
