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


def stand_ins(*dirs: Path) -> dict[str, list[Path]]:
    """Configuration name → the tiny stand-ins for it: the JSON files of
    ``dirs`` whose key ``"stands_for"`` names it."""
    found: dict[str, list[Path]] = {}
    for d in dirs:
        for f in sorted(Path(d).glob("*.json")):
            name = json.loads(f.read_text()).get("stands_for")
            if name:
                found.setdefault(name, []).append(f)
    return found


def write_checkout(root: Path, bm: dict, dirs=(DATA,)) -> None:
    """Write at ``root`` the traffic mixes and ``bm`` with every
    configuration pointed at its one stand-in in ``dirs``; a
    configuration with none, or with two, is named in the error."""
    found = stand_ins(*dirs)
    bm = json.loads(json.dumps(bm))
    for c in bm["configs"]:
        files = found.get(c["name"], [])
        if len(files) != 1:
            raise LookupError(
                f"configuration {c['name']!r} needs one tiny stand-in, a "
                f"JSON file in {', '.join(map(str, dirs))} with "
                f"\"stands_for\": \"{c['name']}\"; found "
                f"{[f.name for f in files]}")
        c["file"] = str(files[0])
    shutil.copytree(BENCH / "traffic", root / "bench" / "traffic",
                    dirs_exist_ok=True)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A checkout whose BENCHMARK.json points every configuration at its
    tiny stand-in, with CPU 'peaks' and no look for a chip."""
    from harness import cell, spec

    write_checkout(tmp_path, spec.load_benchmark(BENCH.parent))
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
