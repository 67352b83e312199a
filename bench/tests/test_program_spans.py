"""The program's own spans and the counters behind the per-layer metrics
that read them.

Spans are ``Stage("<name>", ...)`` in ``src/repro/serve``; none may fall
under a ``trace.HOST_ACTIVITY`` pattern, or a traced run's idle gaps
would move between the breakdown's labels.  The gather's four stages
share their boundaries, so their metrics sum to ``gather.ms_per_window``.
"""

import re
from pathlib import Path

import pytest

from conftest import run_cell
from harness import trace as tr

SERVE = Path(__file__).resolve().parents[2] / "src" / "repro" / "serve"
FIXTURE = Path(__file__).parent / "data" / "take.xplane.pb"
STAGES = ("gather.union_ms_per_window", "gather.launch_ms_per_window",
          "gather.copy_ms_per_window", "gather.slice_ms_per_window")
NEW = STAGES + ("admission.wait_ms_per_req",
                "plan_cache.lookup_ms_per_window")
OLD = ("admission.reqs_per_window", "plan_cache.hit_pct",
       "gather.ms_per_window", "jit.compiles_in_window",
       "device.idle_pct", "gather.hbm_roofline_pct")


def program_spans() -> set[str]:
    rx = re.compile(r'Stage\(\s*"([^"]+)"')
    return {m for f in SERVE.glob("*.py") for m in rx.findall(f.read_text())}


def test_program_spans_are_the_served_path_stages():
    assert program_spans() == {
        "polytope.admission.collect", "polytope.window",
        "polytope.plan_cache.lookup", "polytope.planner.cold",
        "polytope.planner.delta", "polytope.gather.union",
        "polytope.gather.launch", "polytope.gather.copy",
        "polytope.gather.slice"}


@pytest.mark.parametrize("label,rx", tr.HOST_ACTIVITY,
                         ids=[label for label, _ in tr.HOST_ACTIVITY])
def test_no_program_span_matches_a_host_activity(label, rx):
    assert not [s for s in program_spans() if rx.search(s)], label


@pytest.fixture
def traced_line(tiny_root, monkeypatch):
    """A traced tiny run of the hot cell; the CPU has no device plane,
    so the reduction reads the recorded TPU trace."""
    monkeypatch.setattr(tr, "find_xplane", lambda d: str(FIXTURE))
    rc, line, _ = run_cell(tiny_root, "o1280-hot-open", seconds=0.5,
                           trace=True)
    assert rc == 0 and line["correct"] is True, line["checks"]
    return line


def test_traced_run_reports_the_program_stages(traced_line):
    metrics = traced_line["metrics"]
    assert set(NEW) | set(OLD) <= set(metrics)
    assert all(metrics[m]["unit"] == "ms" for m in NEW)
    assert all(metrics[m]["value"] > 0 for m in NEW)


def test_gather_stages_sum_to_the_gather(traced_line):
    metrics = traced_line["metrics"]
    total = sum(metrics[m]["value"] for m in STAGES)
    assert total == pytest.approx(metrics["gather.ms_per_window"]["value"],
                                  rel=1e-3)


@pytest.mark.parametrize("metric", NEW)
def test_readers_find_nothing_in_a_program_without_the_counters(metric):
    """A program that keeps none of the stage counters (the parent of
    the change that added them) leaves the metric out of the line."""
    from types import SimpleNamespace

    from harness import spec

    older = {"cache.gather_time_s": 0.3, "admission.windows": 2,
             "admission.submitted": 9, "admission.served": 9}
    read = spec.reader(metric)
    assert read(SimpleNamespace(counters=older, trace=None)) is None
