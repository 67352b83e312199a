"""The comparison that decides ``correct`` fails when it must: the
control (the reference with bfloat16 values) and the faults a serving
cell can have, planted underneath a run whose look for a chip is
skipped."""

import numpy as np
import pytest

from conftest import run_cell
from harness import cell, check, spec
from harness.reference import Reference

BM = spec.load_benchmark()
CELLS = [w["name"] for w in BM["workloads"]]


def repeats(workload: str) -> bool:
    """Whether the cell's mix sends one request more than once (a static
    stream), so that a later answer can come from another plan."""
    _, _, mix = spec.resolve(BM, workload)
    return any("drift" not in s and "random" not in s
               for s in mix["streams"])


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(tiny_root, workload):
    c = cell.set_up(workload, 21, tiny_root)
    try:
        w = cell.measure(c, 21, 1.0)
    finally:
        c.close()
    ref = Reference(c.config)
    program = check.compare(w.records, ref, 21)
    control = check.control(w.records, ref, 21)
    assert check.verdict(program)
    assert not check.verdict(control)
    assert control["value_mismatch"] > 0.99 * sum(
        len(r.values) for r in w.records)


def altered_answer(monkeypatch):
    import repro.serve.extraction as ex

    real = ex.gather

    def gather(*args, **kw):
        out = np.array(real(*args, **kw))
        out[len(out) // 2] += 1.0
        return out

    monkeypatch.setattr(ex, "gather", gather)


def half_the_window_left_out(monkeypatch):
    """Every second request of the measured window is never answered
    (set-up's warm requests are served, or set-up would wait on them)."""
    from repro.serve.sharded import AdmissionQueue

    real = AdmissionQueue._serve_window
    seen = {"n": 0, "armed": False}

    def serve(self, win):
        if not seen["armed"]:
            return real(self, win)
        kept = [w for i, w in enumerate(win, seen["n"]) if i % 2 == 0]
        seen["n"] += len(win)
        if kept:
            real(self, kept)

    monkeypatch.setattr(AdmissionQueue, "_serve_window", serve)
    arm_in_window(monkeypatch, seen)


def arm_in_window(monkeypatch, seen: dict) -> None:
    """``seen["armed"]`` turns true as the measured window starts."""
    from harness import window

    def armed(run):
        def go(*args, **kw):
            seen["armed"] = True
            return run(*args, **kw)
        return go

    monkeypatch.setattr(window, "run_open", armed(window.run_open))
    monkeypatch.setattr(window, "run_closed", armed(window.run_closed))


def later_plan_altered(monkeypatch):
    """Inside the window, a request's first answer comes from its right
    plan and every later answer from another plan with one offset
    dropped; the values still match the payload at that plan's offsets,
    so only comparing every plan that answered catches it."""
    from dataclasses import replace

    from repro.serve.sharded import ShardedExtractionService

    real = ShardedExtractionService._plan_one
    seen = {"armed": False, "keys": set()}

    def plan_one(self, request, key=None):
        plan, cached, key, stats = real(self, request, key)
        if seen["armed"] and plan.n_points > 1:
            if key in seen["keys"]:
                plan = replace(plan, offsets=plan.offsets[1:])
            seen["keys"].add(key)
        return plan, cached, key, stats

    monkeypatch.setattr(ShardedExtractionService, "_plan_one", plan_one)
    arm_in_window(monkeypatch, seen)


def altered_plan(monkeypatch):
    from dataclasses import replace

    from repro.serve.sharded import ShardedExtractionService

    real = ShardedExtractionService._plan_one

    def plan_one(self, request, key=None):
        plan, cached, key, stats = real(self, request, key)
        if plan.n_points > 1:
            plan = replace(plan, offsets=plan.offsets[1:])
        return plan, cached, key, stats

    monkeypatch.setattr(ShardedExtractionService, "_plan_one", plan_one)


FAULTS = [(altered_answer, "value_mismatch"),
          (half_the_window_left_out, "unanswered"),
          (altered_plan, "plan_mismatch")]


@pytest.mark.parametrize("workload,fault,number", [
    (w, f, n) for w in CELLS for f, n in FAULTS
    + [(later_plan_altered, "plan_mismatch")] * repeats(w)])
def test_faults_make_the_run_not_correct(tiny_root, monkeypatch, workload,
                                         fault, number):
    from harness import window

    monkeypatch.setattr(window, "ANSWER_WAIT_S", 2.0)
    fault(monkeypatch)
    rc, line, err = run_cell(tiny_root, workload, seconds=1.0)
    assert rc == 0
    assert line["correct"] is False
    assert line["checks"][number]["value"] > 0
    assert f"check {number}:" in err
