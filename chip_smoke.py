"""Smoke run of the served extraction path on one TPU chip.

    python chip_smoke.py [--seed N]

Run it from the root of the repository, on a machine with a TPU.  It is
one process and starts no other.  The phases run in order, and any
failure exits non-zero before the result line is printed:

  a. require a TPU as JAX's first device;
  b. build the O1280 archive of the paper's Table 1 (8 steps × 20
     levels × 6,599,680 points, float32, 4.22 GB) from the seed and
     place it on the chip once;
  c. drive ``repro.launch.serve``'s extract path (the function the CLI
     runs) with 64 Zipfian requests from 4 client threads through the
     admission queue and 4 plan-cache shards; every request must be
     answered, bit-equal to the host payload at the plan's offsets;
  d. run the compiled Pallas gathers ``gather_runs`` and ``gather_rows``
     on the union of (c)'s plans and compare them with ``jnp.take``;
  e. plan five countries and a seam-crossing box on a 640 × 1280
     irregular cube with the device planner, byte-identical to the host
     ``Slicer``.

Wall times printed on the way come from this one smoke run, compile
included; they are not benchmark figures.  The last line of standard
output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import DevicePlanner, ExtractionPlan, Slicer  # noqa: E402
from repro.core.index_tree import coalesce_runs  # noqa: E402
from repro.dataplane.weather import (COUNTRIES,  # noqa: E402
                                     IrregularWeatherCube, WeatherCube)
from repro.kernels.gather import kernel as gk, ops as gops  # noqa: E402
from repro.launch import use_compile_cache  # noqa: E402
from repro.launch.serve import (build_parser, load_payload,  # noqa: E402
                                run_extract)

# WeatherCube(n=1280, n_times=8, n_levels=20): paper Table 1's archive.
GRID_N, N_TIMES, N_LEVELS = 1280, 8, 20
REQUESTS, THREADS, SHARDS = 64, 4, 4


def note(msg: str) -> None:
    print(f"[chip smoke run, not a benchmark] {msg}", flush=True)


def bit_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(a.view(np.uint32), b.view(np.uint32)))


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found; JAX's first device is "
                 f"{dev.platform!r}")
    note(f"a: device {dev.device_kind!r}, {len(jax.devices())} device(s)")
    return dev


def place_payload(wc: WeatherCube, seed: int):
    t0 = time.perf_counter()
    host, payload = load_payload(wc, seed)
    payload.block_until_ready()
    dev = payload.devices().pop()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    note(f"b: {payload.size:,} float32 elements, {payload.nbytes:,} bytes "
         f"on {dev.device_kind}, peak_bytes_in_use {peak}, "
         f"{time.perf_counter() - t0:.2f} s to build and place")
    if payload.dtype != jnp.float32 or payload.size != wc.cube.n_elements:
        raise AssertionError(f"payload {payload.dtype}{payload.shape} does "
                             f"not match the cube")
    return host, payload


def serve_requests(seed: int, host: np.ndarray, payload) -> list:
    args = build_parser().parse_args([
        "--mode", "extract", "--grid-n", str(GRID_N),
        "--n-times", str(N_TIMES), "--n-levels", str(N_LEVELS),
        "--requests", str(REQUESTS), "--threads", str(THREADS),
        "--shards", str(SHARDS), "--seed", str(seed)])
    t0 = time.perf_counter()
    _, answers = run_extract(args, payload)
    if len(answers) != REQUESTS:
        raise AssertionError(f"{len(answers)} of {REQUESTS} answered")
    for res in answers:
        if not bit_equal(res.values, host[res.plan.offsets]):
            raise AssertionError(f"answer to {res.key} differs from the "
                                 f"payload at its plan offsets")
    note(f"c: {len(answers)} of {REQUESTS} requests answered, all "
         f"bit-equal, {time.perf_counter() - t0:.2f} s")
    return answers


def timed(fn):
    """(result, first-call seconds incl. compile, second-call seconds)."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t1 = time.perf_counter()
    jax.block_until_ready(fn())
    return out, t1 - t0, time.perf_counter() - t1


def compiled_gathers(answers: list, payload) -> None:
    union = np.unique(np.concatenate([r.plan.offsets for r in answers]))
    starts, lengths = coalesce_runs(union)
    plan = ExtractionPlan(offsets=union, run_starts=starts,
                          run_lengths=lengths, coords={}, itemsize=4)
    want = jnp.take(payload, jnp.asarray(union.astype(np.int32)))

    runs, first, again = timed(lambda: gops.gather_plan_runs(
        payload, plan.run_starts, plan.run_lengths, use_pallas=True,
        interpret=False))
    if not bit_equal(runs, want):
        raise AssertionError("compiled gather_runs differs from jnp.take")
    note(f"d: gather_runs over {len(union):,} points in {len(starts):,} "
         f"runs equals jnp.take; first call {first:.3f} s incl. compile, "
         f"second {again:.4f} s")

    block = gops.BURST_BLOCK
    table = payload.reshape(-1, block)
    rows = np.unique(union // block)
    got, first, again = timed(lambda: gk.gather_rows(table, rows,
                                                     interpret=False))
    if not bit_equal(got, jnp.take(table, jnp.asarray(rows.astype(np.int32)),
                                   axis=0)):
        raise AssertionError("compiled gather_rows differs from jnp.take")
    note(f"d: gather_rows of {len(rows):,} rows of {block} equals "
         f"jnp.take; first call {first:.3f} s incl. compile, second "
         f"{again:.4f} s")


def device_plans() -> None:
    iwc = IrregularWeatherCube(n_lat=640, n_lon=1280)
    requests = {c: iwc.country_request(c) for c in COUNTRIES}
    requests["seam_box"] = iwc.seam_box_request(35.0, 62.0, -25.0, 25.0)
    planner = DevicePlanner(iwc.cube)
    host = Slicer(iwc.cube, fast_paths=False)
    for name, request in requests.items():
        t0 = time.perf_counter()
        out = planner.plan(request)
        dt = time.perf_counter() - t0
        if out is None:
            raise AssertionError(f"{name} fell back to the host planner")
        dplan, _ = out
        hplan, _ = host.extract_plan(request)
        for field in ("offsets", "run_starts", "run_lengths"):
            if not np.array_equal(getattr(dplan, field),
                                  getattr(hplan, field)):
                raise AssertionError(f"{name}: device {field} differ from "
                                     f"the host Slicer's")
        note(f"e: {name} device plan byte-identical to the host Slicer "
             f"({dplan.n_points} points), {dt:.3f} s")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    seed = ap.parse_args().seed

    dev = require_tpu()
    use_compile_cache()
    phases = {}
    t0 = time.perf_counter()
    wc = WeatherCube(n=GRID_N, n_times=N_TIMES, n_levels=N_LEVELS,
                     dtype=np.dtype(np.float32))
    host, payload = place_payload(wc, seed)
    phases["b"] = time.perf_counter() - t0
    answers = serve_requests(seed, host, payload)
    phases["c"] = time.perf_counter() - t0 - sum(phases.values())
    compiled_gathers(answers, payload)
    phases["d"] = time.perf_counter() - t0 - sum(phases.values())
    device_plans()
    phases["e"] = time.perf_counter() - t0 - sum(phases.values())
    note("phase wall times: " + ", ".join(
        f"{k} {v:.2f} s" for k, v in phases.items()))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
