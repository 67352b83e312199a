"""Percentiles and rates are taken over every request of the window."""

import numpy as np
import pytest

from harness import window
from harness.window import Record


def records(latencies_ms, t0=100.0):
    out = []
    for i, lat in enumerate(latencies_ms):
        r = Record(desc={}, due=t0 + i * 0.01)
        r.sent = r.due
        r.done = r.due + lat / 1e3
        r.values = np.zeros(1, np.float32)
        out.append(r)
    return out


def test_percentiles_are_over_all_requests_not_chunk_medians():
    lat = np.concatenate([np.full(90, 1.0), np.linspace(100, 1000, 10)])
    st = window.latency_stats(records(lat))
    assert st["answered"] == 100
    assert st["latency_p50_ms"] == pytest.approx(np.percentile(lat, 50))
    assert st["latency_p95_ms"] == pytest.approx(np.percentile(lat, 95))
    # medians of ten chunks of ten would hide the tail entirely
    chunk = np.median(np.median(lat.reshape(10, 10), axis=1))
    assert st["latency_p95_ms"] > 100 * chunk


def test_failed_requests_are_left_out_of_latency():
    recs = records([5.0, 6.0, 7.0])
    recs[1].error = RuntimeError("boom")
    st = window.latency_stats(recs)
    assert st["answered"] == 2
    assert st["latency_p50_ms"] == pytest.approx(6.0)


def test_throughput_counts_answers_inside_the_window_over_its_length():
    recs = records([10.0] * 50)         # due 100.00 .. 100.49
    recs[-1].done = 103.0               # answered after the window
    assert window.throughput(recs, 100.0, 102.0) == 49 / 2.0
