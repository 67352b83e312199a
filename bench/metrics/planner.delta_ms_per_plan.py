"""Mean time of a delta splice, in ms: an exact-cache miss served by
splicing a drifted neighbour's plan (``DeltaPlanner.splice`` through
``ShardedExtractionService._try_delta``, CacheStats delta_time_s /
delta_hits).  Nothing to read in a window with no splice."""

from harness.readers import ratio


def read(window):
    seconds = window.counters.get("cache.delta_time_s")
    if seconds is None:
        return None
    return ratio(seconds, window.counters["cache.delta_hits"], 1e3)
