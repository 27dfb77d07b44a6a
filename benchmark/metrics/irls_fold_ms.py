"""Device time of the ordered fold's collective an IRLS iteration: the
all-gather of the chips' Gram partials (`%all-gather.N`, or its `-start` /
`-done` pair) inside the fused IRLS program's events, summed on each chip,
the LONGEST chip's sum over the iterations. A chip that arrives early waits
in its all-gather for the slowest, so this also reads the chips' skew."""

import re

COLLECTIVE = re.compile(r"^%all-gather")


def read(ctx):
    pattern = getattr(ctx["algo"], "TRACE_STEP_PROGRAM", None)
    if pattern is None or not ctx["steps"]:
        return None
    program = re.compile(pattern)
    longest, seen = 0.0, 0
    for dev in ctx["trace"].devices:
        inside = [(s, s + d) for n, s, d in dev["modules"]
                  if program.search(n)]
        found = [d for n, s, d in dev["ops"] if COLLECTIVE.match(n)
                 and any(lo <= s < hi for lo, hi in inside)]
        seen += len(found)
        longest = max(longest, sum(found))
    if not seen:
        return None
    return 1e3 * longest / ctx["steps"], f"({seen} collective events)"
