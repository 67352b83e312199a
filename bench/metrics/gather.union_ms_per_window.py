"""The union stage of the served gather per admission window, in ms: host
union build (np.unique, coalesce_runs, the union plan) (CacheStats
union_time_s / AdmissionStats windows; shared_union_gather's four stages
sum to gather_time_s).  Nothing to read where the program keeps no
union_time_s."""

from harness.readers import ratio


def read(window):
    seconds = window.counters.get("cache.union_time_s")
    if seconds is None:
        return None
    return ratio(seconds, window.counters["admission.windows"], 1e3)
