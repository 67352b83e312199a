"""Every cell, run end to end at a tiny size on the CPU."""

import pytest

from conftest import run_cell
from harness import spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
FIXTURE = __import__("pathlib").Path(__file__).parent / "data" / \
    "take.xplane.pb"


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct(tiny_root, workload):
    rc, line, err = run_cell(tiny_root, workload, seconds=1.0)
    assert rc == 0
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert len(line["metrics"]) >= 2
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")


def test_counters_are_window_deltas(tiny_root):
    """The hot mix is planned in set-up, so the window itself plans
    nothing: a counter read from zero would count those plans."""
    from harness import cell

    c = cell.set_up("o1280-hot-open", 5, tiny_root)
    try:
        assert c.served.counters()["cache.misses"] >= 28
        w = cell.measure(c, 5, 1.0)
    finally:
        c.close()
    assert w.counters["cache.misses"] == 0
    assert w.counters["cache.hits"] > 0
    # read as the window closes: every answer by then, none from set-up
    end = min(r.due for r in w.records) + w.seconds
    by_close = sum(1 for r in w.records if r.answered and r.done <= end)
    assert by_close <= w.counters["admission.served"] <= len(w.records)


def test_traced_run_reports_the_layers(tiny_root, monkeypatch):
    """The CPU has no device plane, so the reduction reads the recorded
    TPU trace; everything else is the traced run's own."""
    from harness import trace as tr

    monkeypatch.setattr(tr, "find_xplane", lambda d: str(FIXTURE))
    rc, line, _ = run_cell(tiny_root, "o1280-hot-open", seconds=0.2,
                           trace=True)
    names = set(line["metrics"])
    assert {"admission.reqs_per_window", "plan_cache.hit_pct",
            "gather.ms_per_window", "jit.compiles_in_window",
            "device.idle_pct", "gather.hbm_roofline_pct"} <= names
    assert "latency_p50_ms" not in names
    assert line["device"]["busy_s"] > 0
    assert line["device"]["window_s"] == pytest.approx(0.2, rel=0.2)
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10
