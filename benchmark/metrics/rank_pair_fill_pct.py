"""The share of the pairwise pass's slots that are pairs of the objective:
`pairs` over `pair_slots` of the fit plan's `rank` entry (ordered pairs with
r_i > r_j against the Qpad x G x G slots a pass computes)."""


def read(ctx):
    shapes = ctx["shapes"]
    if not shapes.get("pair_slots") or "pairs" not in shapes:
        return None
    return 100.0 * shapes["pairs"] / shapes["pair_slots"]
