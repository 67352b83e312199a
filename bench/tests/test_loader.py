"""The harness finds every piece of a cell by name, and nothing else."""

import json

import pytest

from harness import spec
from harness.traffic import Traffic

BM = spec.load_benchmark()
CELLS = [w["name"] for w in BM["workloads"]]
METRICS = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_by_name(workload):
    cell, config, mix = spec.resolve(BM, workload)
    assert config["name"] == cell["config"]
    traffic = Traffic.load(mix, config)
    assert traffic.streams
    assert traffic.loop["kind"] in ("open", "closed")


@pytest.mark.parametrize("metric", METRICS)
def test_every_metric_has_a_reader(metric):
    read = spec.reader(metric)
    assert callable(read)


@pytest.mark.parametrize("workload", CELLS)
def test_each_run_reports_setup_another_e2e_and_a_layer(workload):
    e2e = {m["name"] for m in spec.metrics_for(BM, workload, trace=False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = spec.metrics_for(BM, workload, trace=True)
    assert layers
    assert all(m["moves"] in e2e for m in layers)


def test_unknown_names_fail():
    with pytest.raises(KeyError):
        spec.resolve(BM, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.reader("no.such.metric")


def test_peaks_are_keyed_by_device_kind():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("cpu")


def test_configuration_files_match_their_entries():
    for entry in BM["configs"]:
        config = json.loads((spec.ROOT / entry["file"]).read_text())
        assert config["name"] == entry["name"]
        assert sorted(config["reduced"]) == sorted(entry["reduced"])
