"""Median latency (ms), from each request's due time to its answer, over
every answered request of the window (host clock)."""


def read(window):
    return window.latency.get("latency_p50_ms")
