"""Mean wait of a request in the admission queue, in ms: from
``AdmissionQueue.submit`` to the drain of its window (AdmissionStats
wait_s / submitted, both counted at the drain).  Nothing to read where
the program keeps no ``wait_s``."""

from harness.readers import ratio


def read(window):
    wait = window.counters.get("admission.wait_s")
    if wait is None:
        return None
    return ratio(wait, window.counters["admission.submitted"], 1e3)
