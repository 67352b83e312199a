"""Plan-cache hits over lookups, in % (CacheStats hits / (hits +
misses))."""

from harness.readers import ratio


def read(window):
    hits = window.counters["cache.hits"]
    return ratio(hits, hits + window.counters["cache.misses"], 100.0)
