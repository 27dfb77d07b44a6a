"""Program code of the window that no `train()` covers (the fresh Frame,
the result reads of the adapter): the window event less its `train` spans."""

import fit_spans


def read(ctx):
    win = fit_spans.window(ctx["trace"])
    fits = fit_spans.trains(ctx["trace"])
    if win is None or not fits:
        return None
    return 1e3 * (win[1] - win[0] - fit_spans.seconds(fits)) / len(fits)
