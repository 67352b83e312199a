"""The delta planner's metrics: their readers on synthetic windows, and
a traced run of the drift cell on its stand-in, where both read."""

from types import SimpleNamespace

import pytest

from conftest import DATA, run_cell
from harness import spec

CELL = "n320-drift-open"
METRICS = ("planner.delta_ms_per_plan", "planner.delta_hit_pct")


def window(**counters):
    return SimpleNamespace(counters={f"cache.{k}": v
                                     for k, v in counters.items()})


@pytest.mark.parametrize("name", METRICS)
def test_nothing_to_read_without_a_delta_try(name):
    read = spec.reader(name)
    assert read(window(delta_hits=0, delta_misses=0,
                       delta_time_s=0.0)) is None
    # a program that keeps no delta counters
    assert read(window(hits=3, misses=1)) is None


def test_readers_on_a_window_of_splices():
    w = window(delta_hits=40, delta_misses=10, delta_time_s=0.2)
    assert spec.reader("planner.delta_ms_per_plan")(w) == pytest.approx(5.0)
    assert spec.reader("planner.delta_hit_pct")(w) == pytest.approx(80.0)


def test_misses_alone_read_a_share_and_no_time():
    w = window(delta_hits=0, delta_misses=6, delta_time_s=0.0)
    assert spec.reader("planner.delta_ms_per_plan")(w) is None
    assert spec.reader("planner.delta_hit_pct")(w) == 0.0


def test_traced_drift_cell_reads_both(tiny_root, monkeypatch):
    """The CPU has no device plane, so the reduction reads the recorded
    TPU trace; the counters are the run's own."""
    from harness import trace as tr

    monkeypatch.setattr(tr, "find_xplane",
                        lambda d: str(DATA / "take.xplane.pb"))
    rc, line, _ = run_cell(tiny_root, CELL, seed=2 ** 31 + 11,
                           seconds=1.0, trace=True)
    assert rc == 0
    assert line["correct"] is True, line["checks"]
    for name in METRICS:
        assert line["metrics"][name]["value"] is not None
    assert line["metrics"]["planner.delta_ms_per_plan"]["value"] > 0
    assert 0 < line["metrics"]["planner.delta_hit_pct"]["value"] <= 100
