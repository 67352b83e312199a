"""Finding a cell's pieces by name: ``BENCHMARK.json`` at the root of
the checkout names each configuration's file, each cell's traffic mix
(``bench/traffic/<traffic>.json``) and each metric, whose reader is
``bench/metrics/<metric name>.py``.  The chip peaks are
``bench/peaks.json``, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def resolve(bm: dict, workload: str, root: Path = ROOT):
    """``(cell, configuration, traffic mix)`` of a workload name."""
    cell = _named(bm["workloads"], workload, "workload")
    cfg_entry = _named(bm["configs"], cell["config"], "configuration")
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def metrics_for(bm: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: with ``trace`` the
    per-layer ones, else the end-to-end ones.  A metric with a
    ``workloads`` list is reported in those cells; a per-layer metric
    without one is reported wherever its ``moves`` metric is."""
    e2e = [m for m in bm["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(metric: str, bench: Path = BENCH):
    """The ``read(window)`` function of ``bench/metrics/<metric>.py``."""
    path = bench / "metrics" / f"{metric}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, bench: Path = BENCH) -> dict:
    table = json.loads((bench / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"bench/peaks.json has no peaks for device kind "
                       f"{device_kind!r}") from None
