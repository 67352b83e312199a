"""Requests served per drained admission window (AdmissionStats
served / windows)."""

from harness.readers import ratio


def read(window):
    return ratio(window.counters["admission.served"],
                 window.counters["admission.windows"])
