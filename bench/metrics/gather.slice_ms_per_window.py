"""The slice stage of the served gather per admission window, in ms: per-
request searchsorted slices of the union and the answers' assignment
(CacheStats slice_time_s / AdmissionStats windows; shared_union_gather's
four stages sum to gather_time_s).  Nothing to read where the program
keeps no slice_time_s."""

from harness.readers import ratio


def read(window):
    seconds = window.counters.get("cache.slice_time_s")
    if seconds is None:
        return None
    return ratio(seconds, window.counters["admission.windows"], 1e3)
