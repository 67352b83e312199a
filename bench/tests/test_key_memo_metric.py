"""``plan_cache.key_memo_hit_pct``: the share of requests whose plan-cache
key came from the key memo (``CacheStats.key_memo_hits`` over
``key_memo_hits + key_memo_misses``)."""

from types import SimpleNamespace

import pytest

from conftest import DATA, run_cell
from harness import spec
from harness import trace as tr

NAME = "plan_cache.key_memo_hit_pct"


def read(counters):
    return spec.reader(NAME)(SimpleNamespace(counters=counters, trace=None))


def test_reader_finds_nothing_in_a_program_without_the_memo():
    assert read({"cache.hits": 3, "cache.misses": 1}) is None


def test_reader_finds_nothing_with_no_lookups():
    assert read({"cache.key_memo_hits": 0,
                 "cache.key_memo_misses": 0}) is None


@pytest.mark.parametrize("hits, misses", [(0, 5), (772, 1148), (6400, 0)])
def test_reader_is_hits_over_lookups(hits, misses):
    assert read({"cache.key_memo_hits": hits,
                 "cache.key_memo_misses": misses}) == pytest.approx(
                     100.0 * hits / (hits + misses))


@pytest.mark.parametrize("cell", ["o1280-hot-open", "n320-drift-open"])
def test_traced_run_reports_the_memo(tiny_root, monkeypatch, cell):
    """The hot mix is planned, and so memoized, in set-up: every key in
    the window comes from the memo.  The drift mix repeats some raw
    requests and not the rest."""
    monkeypatch.setattr(tr, "find_xplane",
                        lambda d: str(DATA / "take.xplane.pb"))
    rc, line, _ = run_cell(tiny_root, cell, seed=2 ** 31 + 29,
                           seconds=0.5, trace=True)
    assert rc == 0 and line["correct"] is True, line["checks"]
    metric = line["metrics"][NAME]
    assert metric["unit"] == "%"
    if cell == "o1280-hot-open":
        assert metric["value"] == 100.0
    else:
        assert 0.0 < metric["value"] < 100.0
