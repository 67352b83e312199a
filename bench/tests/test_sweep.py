"""The knee rule of ``sweep.py``, and a sweep end to end at a tiny size."""

import importlib.util
import json

from harness import spec

_SPEC = importlib.util.spec_from_file_location("bench_sweep",
                                               spec.BENCH / "sweep.py")
sweep = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(sweep)


def row(rate, keep_up=1.0, second=100.0, last=105.0, late=0.5):
    return {"rate_per_s": rate, "keep_up": keep_up,
            "second_fifth_p50_ms": second, "last_fifth_p50_ms": last,
            "lateness_p50_ms": late}


def test_a_growing_backlog_or_a_late_scheduler_is_not_sustained():
    assert sweep.sustained(row(10))
    assert not sweep.sustained(row(10, keep_up=0.9))
    assert not sweep.sustained(row(10, last=130.0))
    assert not sweep.sustained(row(10, late=5.0))


def test_the_knee_is_the_highest_rate_with_every_lower_one_sustained():
    rows = [dict(r, sustained=sweep.sustained(r)) for r in
            (row(40, keep_up=0.5), row(10), row(20), row(30, last=300.0))]
    assert sweep.knee(rows) == 20
    assert sweep.knee(rows[:1]) is None


def test_a_sweep_runs_and_names_the_cell_rate(tiny_root, capsys):
    assert sweep.main(["--workload", "o1280-hot-open", "--rates", "20,40",
                       "--seconds", "0.5", "--seed", "3"]) == 0
    lines = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    assert [r["rate_per_s"] for r in lines[:2]] == [20.0, 40.0]
    assert all("sustained" in r and "keep_up" in r for r in lines[:2])
    last = lines[-1]
    assert set(last) == {"knee_per_s", "cell_rate_per_s"}
    if last["knee_per_s"] is not None:
        assert last["cell_rate_per_s"] == 0.8 * last["knee_per_s"]
