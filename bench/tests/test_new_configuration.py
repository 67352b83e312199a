"""A configuration joins the benchmark as new files plus additions to
``BENCHMARK.json``: its tiny stand-in (found by ``"stands_for"``), its
traffic mix, a cell, and the cell appended to the ``workloads`` lists of
the metrics it reports.  No file the benchmark already has is edited."""

import json
import shutil

import pytest

from conftest import BENCH, DATA, run_cell, write_checkout
from harness import spec

# Names no real configuration or cell takes, so that the check still
# holds once the N320 archive and its drift cell are in BENCHMARK.json.
CONFIG = "room-check-era5"
CELL = "room-check-drift"
HOT = "o1280-hot-open"


def stand_in(path):
    """An N12 stand-in for an ERA5 archive, with hourly steps so that
    the drift mix's 1-hour steps move its windows by whole steps."""
    cfg = json.loads((DATA / "n12-tiny.json").read_text())
    cfg["name"] = "n12-hourly-tiny"
    cfg["stands_for"] = CONFIG
    cfg["lead_axes"][0]["merge"][1] = {"start": 0.0, "step": 3600.0,
                                       "count": 24}
    cfg["system"]["kwargs"]["times_per_day"] = 24
    cfg["elements"] = 2 * 24 * 3 * 24 * 48
    path.mkdir(parents=True, exist_ok=True)
    (path / "n12-hourly-tiny.json").write_text(json.dumps(cfg))
    return path


def with_new_cell(bm: dict) -> dict:
    """``bm`` with an ERA5 configuration and its drift cell added."""
    bm = json.loads(json.dumps(bm))
    bm["configs"].append({
        "name": CONFIG,
        "source": "ERA5 reanalysis, Hersbach et al. 2020 "
                  "(doi 10.1002/qj.3803)",
        "file": f"bench/configs/{CONFIG}.json", "reduced": ["n_dates"],
        "why": "hourly fields on 37 levels on the N320 Gaussian grid"})
    bm["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": CELL, "chips": 1,
        "why": "storms and boxes drifting east, rolling 6-hour windows: "
               "every request a new geometry, so the delta planner splices"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in ("latency_p50_ms", "plan_cache.hit_pct"):
            m["workloads"].append(CELL)
    return bm


def reported(bm: dict, workload: str) -> dict:
    return {trace: [m["name"] for m in spec.metrics_for(bm, workload, trace)]
            for trace in (False, True)}


def test_a_configuration_joins_as_new_files(tiny_root, tmp_path):
    real = spec.load_benchmark(BENCH.parent)
    bm = with_new_cell(real)
    write_checkout(tiny_root, bm, dirs=(DATA, stand_in(tmp_path / "new")))
    shutil.copy(DATA / "mixes" / "drift.json",
                tiny_root / "bench" / "traffic" / f"{CELL}.json")

    rc, line, err = run_cell(tiny_root, CELL, seconds=1.0)
    assert rc == 0
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"latency_p50_ms", "setup_s"} <= set(line["metrics"])
    assert reported(bm, CELL)[True] == ["plan_cache.hit_pct"]
    assert reported(spec.load_benchmark(tiny_root), HOT) == \
        reported(real, HOT)


def test_a_configuration_without_a_stand_in_is_named(tmp_path):
    bm = with_new_cell(spec.load_benchmark(BENCH.parent))
    with pytest.raises(LookupError, match=f"'{CONFIG}'.*found \\[\\]"):
        write_checkout(tmp_path, bm)


def test_a_configuration_with_two_stand_ins_is_named(tmp_path):
    bm = with_new_cell(spec.load_benchmark(BENCH.parent))
    new = stand_in(tmp_path / "new")
    shutil.copy(new / "n12-hourly-tiny.json", new / "n12-hourly-copy.json")
    with pytest.raises(LookupError, match=f"'{CONFIG}'.*n12-hourly-copy"):
        write_checkout(tmp_path, bm, dirs=(DATA, new))
