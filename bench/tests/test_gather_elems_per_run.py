"""``gather.elems_per_run``: the union elements read
(``cache.bytes_read`` / 4) over the runs of the windows' unions
(``CacheStats.union_runs``)."""

from types import SimpleNamespace

import pytest

from conftest import run_cell
from harness import spec
from harness import trace as tr
from test_program_spans import FIXTURE


def read(counters):
    return spec.reader("gather.elems_per_run")(
        SimpleNamespace(counters=counters, trace=None))


def test_reader_finds_nothing_in_a_program_without_the_counter():
    assert read({"cache.bytes_read": 4096, "admission.windows": 2}) is None


@pytest.mark.parametrize("elems, runs", [(0, 0), (0, 3), (3000, 0)])
def test_reader_finds_nothing_when_no_union_was_read(elems, runs):
    assert read({"cache.bytes_read": 4 * elems,
                 "cache.union_runs": runs}) is None


def test_reader_is_union_elements_over_union_runs():
    counters = {"cache.bytes_read": 4 * 92_016, "cache.union_runs": 429}
    assert read(counters) == pytest.approx(92_016 / 429)


def test_traced_run_reports_elements_per_run(tiny_root, monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda d: str(FIXTURE))
    rc, line, _ = run_cell(tiny_root, "o1280-hot-open", seconds=0.3,
                           trace=True)
    assert rc == 0 and line["correct"] is True, line["checks"]
    metric = line["metrics"]["gather.elems_per_run"]
    assert metric["unit"] == "elem"
    assert metric["value"] >= 1
