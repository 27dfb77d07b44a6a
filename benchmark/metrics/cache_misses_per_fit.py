"""Dataset-cache misses a fit over the window (models/dataset_cache.py
`snapshot`, every `*_misses` counter): matrix, bins, device codes, blocks.
Under a shared Frame every artifact was built in set-up and this reads 0."""


def read(ctx):
    cache = ctx["counters"].get("cache")
    if not cache or not ctx["fits"]:
        return None
    misses = [v for k, v in cache.items() if k.endswith("_misses")]
    if not misses:
        return None
    return sum(misses) / ctx["fits"]
