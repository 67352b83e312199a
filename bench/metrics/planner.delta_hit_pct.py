"""Exact-cache misses served by a delta splice, in % of those that tried
one (CacheStats delta_hits / (delta_hits + delta_misses)); the rest were
planned cold.  Nothing to read in a window where no miss tried one."""

from harness.readers import ratio


def read(window):
    hits = window.counters.get("cache.delta_hits")
    if hits is None:
        return None
    return ratio(hits, hits + window.counters["cache.delta_misses"], 100.0)
