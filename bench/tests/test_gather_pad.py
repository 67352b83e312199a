"""``gather.pad_pct``: the padding the served union read's launch adds
(``CacheStats.gather_padded_elems``) over the union elements read."""

from types import SimpleNamespace

import pytest

from conftest import run_cell
from harness import spec
from harness import trace as tr
from test_program_spans import FIXTURE


def read(counters):
    return spec.reader("gather.pad_pct")(
        SimpleNamespace(counters=counters, trace=None))


def test_reader_finds_nothing_in_a_program_without_the_counter():
    assert read({"cache.bytes_read": 4096, "admission.windows": 2}) is None


def test_reader_is_padding_over_union_elements():
    counters = {"cache.bytes_read": 4 * 3000,
                "cache.gather_padded_elems": 1096}
    assert read(counters) == pytest.approx(100.0 * 1096 / 3000)


def test_reader_finds_nothing_when_no_union_was_read():
    assert read({"cache.bytes_read": 0,
                 "cache.gather_padded_elems": 0}) is None


def test_traced_run_reports_the_padding(tiny_root, monkeypatch):
    """The tiny cell's payload is a device array, so every window's read
    is padded to its bucket and the metric is in the traced line."""
    monkeypatch.setattr(tr, "find_xplane", lambda d: str(FIXTURE))
    rc, line, _ = run_cell(tiny_root, "o1280-hot-open", seconds=0.3,
                           trace=True)
    assert rc == 0 and line["correct"] is True, line["checks"]
    metric = line["metrics"]["gather.pad_pct"]
    assert metric["unit"] == "%"
    assert metric["value"] > 0
