"""The batch's plan lookup per admission window, in ms: canonical hash,
shard routing, cache get and any cold or delta plan, in
``ShardedExtractionService.submit_batch`` (CacheStats lookup_time_s /
AdmissionStats windows).  Nothing to read where the program keeps no
``lookup_time_s``."""

from harness.readers import ratio


def read(window):
    seconds = window.counters.get("cache.lookup_time_s")
    if seconds is None:
        return None
    return ratio(seconds, window.counters["admission.windows"], 1e3)
