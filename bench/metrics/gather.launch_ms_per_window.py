"""The launch stage of the served gather per admission window, in ms: the
gather call until it returns: int32 cast, jnp.take trace, compile on a
new size, dispatch (CacheStats launch_time_s / AdmissionStats windows;
shared_union_gather's four stages sum to gather_time_s).  Nothing to
read where the program keeps no launch_time_s."""

from harness.readers import ratio


def read(window):
    seconds = window.counters.get("cache.launch_time_s")
    if seconds is None:
        return None
    return ratio(seconds, window.counters["admission.windows"], 1e3)
