"""The copy stage of the served gather per admission window, in ms:
np.asarray of the gathered union: waiting for the device and the copy
back (CacheStats copy_time_s / AdmissionStats windows;
shared_union_gather's four stages sum to gather_time_s).  Nothing to
read where the program keeps no copy_time_s."""

from harness.readers import ratio


def read(window):
    seconds = window.counters.get("cache.copy_time_s")
    if seconds is None:
        return None
    return ratio(seconds, window.counters["admission.windows"], 1e3)
