"""Requests whose plan-cache key came from the key memo, in % (CacheStats
key_memo_hits / (key_memo_hits + key_memo_misses)): repeats of a raw
request, keyed without decomposing its shapes.  Nothing to read where
the program keeps no key memo, or where no key was looked up."""

from harness.readers import ratio


def read(window):
    hits = window.counters.get("cache.key_memo_hits")
    if hits is None:
        return None
    return ratio(hits, hits + window.counters["cache.key_memo_misses"], 100.0)
