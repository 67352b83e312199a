"""The command exits non-zero, with no result line, without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "o1280-hot-open",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in obj
    return True


def test_no_tpu_exits_non_zero_and_reports_nothing():
    proc = run(ROOT)
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert no_result(proc)


def test_benchmark_files_alone_exit_non_zero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(tmp_path)
    assert proc.returncode != 0
    assert no_result(proc)


@pytest.mark.parametrize("args", [["--seed", "-1"], ["--seconds", "0"]])
def test_bad_arguments_exit_non_zero(args):
    base = {"--workload": "o1280-hot-open", "--seed": "1",
            "--seconds": "1", "--trace": "0"}
    base.update(dict(zip(args[::2], args[1::2])))
    argv = [a for kv in base.items() for a in kv]
    proc = subprocess.run([sys.executable, "bench/run.py", *argv], cwd=ROOT,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and no_result(proc)
