"""The open-loop schedule, the stratified draw and the latency clock."""

import json
import threading
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import DATA
from harness import spec, window
from harness.traffic import (Traffic, draw_probabilities,
                             stratified_counts)

BM = spec.load_benchmark()
# mixes of the generator's other streams, on tiny stand-in grids
TEST_MIXES = {"drift": "n12-tiny", "random": "o32-tiny", "closed": "n12-tiny"}


def traffic(name):
    if name in TEST_MIXES:
        config = json.loads((DATA / f"{TEST_MIXES[name]}.json").read_text())
        mix = json.loads((DATA / "mixes" / f"{name}.json").read_text())
    else:
        _, config, mix = spec.resolve(BM, name)
    return Traffic.load(mix, config)


@pytest.mark.parametrize("name", ["o1280-hot-open", "drift", "random"])
def test_schedule_is_a_fixed_amount_of_work_in_seeded_order(name):
    rate = traffic(name).loop["rate_per_s"]
    due1, d1 = traffic(name).open_loop(seed=5, seconds=20.0)
    due1b, d1b = traffic(name).open_loop(seed=5, seconds=20.0)
    due2, d2 = traffic(name).open_loop(seed=2 ** 33 + 9, seconds=20.0)
    n = round(rate * 20.0)
    assert len(due1) == len(due2) == n
    np.testing.assert_array_equal(due1, due1b)
    assert d1 == d1b
    assert due1[0] == 0.0 and np.all(np.diff(due1) > 0) and due1[-1] < 20.0
    # the same gaps, in another order (the last one closes the window)
    def gaps(due):
        return np.sort(np.append(np.diff(due), 20.0 - due[-1]))
    np.testing.assert_allclose(gaps(due1), gaps(due2), rtol=1e-9,
                               atol=1e-12)
    assert not np.array_equal(due1, due2)


def test_gaps_are_exponential_at_the_rate():
    due, _ = traffic("o1280-hot-open").open_loop(seed=1, seconds=100.0)
    gaps = np.diff(due)
    rate = traffic("o1280-hot-open").loop["rate_per_s"]
    assert abs(gaps.mean() * rate - 1) < 0.02
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05   # exponential: cv 1


def test_stratified_zipf_matches_numpy_zipf():
    p = draw_probabilities({"kind": "zipf", "s": 1.3}, 28)
    assert p.sum() == pytest.approx(1.0)
    rng = np.random.default_rng(0)
    ranks = np.minimum(rng.zipf(1.3, size=400_000) - 1, 27)
    freq = np.bincount(ranks, minlength=28) / len(ranks)
    np.testing.assert_allclose(freq, p, atol=3e-3)
    counts = stratified_counts(p, 1000)
    assert counts.sum() == 1000 and np.all(np.abs(counts - 1000 * p) < 1)


def test_drift_moves_by_whole_steps():
    t = traffic("drift")
    rng = np.random.default_rng(3)
    s = t.streams[0]
    first, second = s.draw(rng, t.lead_values), s.draw(rng, t.lead_values)
    step = (second["horiz"][1][1] - first["horiz"][1][1]) / 0.28125
    assert round(step) == step and 1 <= step <= 3


ANSWER = SimpleNamespace(plan=None, values=np.zeros(1, np.float32))


class SlowQueue:
    """Answers each request ``delay`` s after it is submitted; ``submit``
    itself blocks ``block`` s, as a stalled server front end would."""

    def __init__(self, delay, block=0.0):
        self.delay, self.block = delay, block

    def submit(self, req):
        time.sleep(self.block)
        fut = Future()
        threading.Timer(self.delay, fut.set_result, args=(req,)).start()
        return fut


def test_latency_runs_from_due_time_not_send_time():
    due = np.array([0.0, 0.01, 0.02, 0.03])
    recs, t0, end = window.run_open(SlowQueue(0.05, block=0.1),
                                    lambda desc: ANSWER, [{}] * 4, due, 0.5)
    lat = np.array([r.latency_s for r in recs])
    late = np.array([r.sent - r.due for r in recs])
    # each blocked submit delays every later one: latency counts it
    assert np.all(np.diff(late) > 0.05)
    np.testing.assert_allclose(lat, late + 0.1 + 0.05, atol=0.03)
    assert all(r.done - r.sent < lat[i] for i, r in enumerate(recs[1:], 1))


def test_unanswered_requests_count_as_failed():
    class Never:
        def submit(self, req):
            return Future()

    window.ANSWER_WAIT_S, saved = 0.1, window.ANSWER_WAIT_S
    try:
        recs, _, _ = window.run_open(Never(), lambda desc: desc, [{}, {}],
                                     np.array([0.0, 0.01]), 0.05)
    finally:
        window.ANSWER_WAIT_S = saved
    assert not any(r.answered for r in recs)


def test_closed_loop_clients_wait_for_each_answer():
    class Slow:
        def extract(self, req, timeout=None):
            time.sleep(0.02)
            return ANSWER

    t = traffic("closed")
    cycles = [t.client_cycle(5, i) for i in range(3)]
    recs, t0, end = window.run_closed(Slow(), lambda desc: desc, cycles, 0.3)
    assert end - t0 == pytest.approx(0.3)
    assert all(r.answered and r.done - r.sent >= 0.02 for r in recs)
    # three clients, one request out each: about 0.3 / 0.02 per client
    assert 30 <= len(recs) <= 3 * 16
    assert window.throughput(recs, t0, end) <= 3 / 0.02
