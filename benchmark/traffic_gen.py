"""The one traffic generator. A traffic mix is a data file under traffic/:

    frame                "fresh": every fit gets a NEW Frame over the same
                         host columns; "shared": one Frame for the whole run
    candidates           estimator options laid over the configuration's own,
                         one entry per fit, visited in an order drawn from
                         --seed and repeated for as long as the window lasts:
                         every seed sends the same set, in another order
    draw_estimator_seed  give each fit an estimator `seed` drawn from --seed
    trace_seconds        in a --trace 1 run the profiler covers whole fits for
                         this long (always one; a further fit only if it
                         would end inside): as few as give steady numbers

The loop is closed with one client: a data scientist's train() returns
before the next is sent, so there is no rate to fix and no queue to grow."""

from __future__ import annotations

import numpy as np


def check(traffic: dict) -> dict:
    if traffic.get("frame") not in ("fresh", "shared"):
        raise ValueError("traffic: frame must be fresh or shared")
    cands = traffic.get("candidates")
    if not isinstance(cands, list) or not cands \
            or not all(isinstance(c, dict) for c in cands):
        raise ValueError("traffic: candidates must be a list of option sets")
    if not float(traffic.get("trace_seconds", 0)) > 0:
        raise ValueError("traffic: trace_seconds must be above 0")
    return traffic


def fits(traffic: dict, seed: int):
    """Endless sequence of fit requests: {"fresh_frame", "overrides"}."""
    check(traffic)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x7AFF1C]))
    cands = traffic["candidates"]
    while True:
        for i in rng.permutation(len(cands)):
            over = dict(cands[int(i)])
            if traffic.get("draw_estimator_seed"):
                over["seed"] = int(rng.integers(1, 2 ** 31 - 1))
            yield {"fresh_frame": traffic["frame"] == "fresh",
                   "overrides": over}
