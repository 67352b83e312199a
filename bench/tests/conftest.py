"""CPU rehearsal of the benchmark: the harness at tiny sizes, with the
look for a chip replaced, so that everything but the chip is exercised.

    python -m pytest bench/tests
"""

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

DATA = Path(__file__).resolve().parent / "data"
TINY = {"o1280-oper-archive": "o32-tiny"}


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A checkout whose BENCHMARK.json points every configuration at its
    tiny stand-in, with CPU 'peaks' and no look for a chip."""
    from harness import cell, spec

    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (tmp_path / "bench").mkdir()
    shutil.copytree(BENCH / "traffic", tmp_path / "bench" / "traffic")
    for c in bm["configs"]:
        c["file"] = str(DATA / f"{TINY[c['name']]}.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    monkeypatch.setattr(spec, "peaks", lambda kind: {
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9})
    monkeypatch.setattr(cell, "require_accelerator",
                        lambda chips: __import__("jax").devices()[:chips])
    return tmp_path


def run_cell(root, workload, seed=7, seconds=1.0, trace=False):
    """One run of ``workload``; returns (exit code, result line, stderr)."""
    import io

    from harness import cell

    out, err = io.StringIO(), io.StringIO()
    rc = cell.run(workload, seed, seconds, trace,
                  require=cell.require_accelerator, root=root,
                  out=out, err=err)
    return rc, json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()
