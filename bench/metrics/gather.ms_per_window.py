"""Union build, device gather and copy back per admission window, in ms
(CacheStats gather_time_s / AdmissionStats windows)."""

from harness.readers import ratio


def read(window):
    return ratio(window.counters["cache.gather_time_s"],
                 window.counters["admission.windows"], 1e3)
