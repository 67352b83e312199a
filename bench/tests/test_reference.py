"""The reference: its payload equals the device's, and its offsets are
the program's planner's on many requests at small sizes.  (The
program's planner is used here only as a second witness; the
benchmark's check never calls it.)"""

import json
from pathlib import Path

import numpy as np
import pytest

from harness import payload
from harness.reference import Reference
from harness.system import build_cube, to_request
from harness.traffic import Traffic

DATA = Path(__file__).parent / "data"


@pytest.mark.parametrize("seed", [0, 12345, 2 ** 31 + 7, 2 ** 40 + 3])
def test_device_payload_equals_reference_hash(seed):
    n = 100_003
    dev = np.asarray(payload.make_device_payload(seed, n))
    want = payload.reference_values(seed, np.arange(n))
    assert dev.dtype == np.float32
    np.testing.assert_array_equal(dev.view(np.uint32), want.view(np.uint32))
    assert np.all((want >= 1.0) & (want < 2.0))


def test_seeds_change_every_value():
    a = payload.reference_bits(1, np.arange(10_000))
    b = payload.reference_bits(2, np.arange(10_000))
    assert np.mean(a == b) < 1e-3


def test_bfloat16_rounding_changes_values():
    from harness.check import to_bfloat16

    v = payload.reference_values(4, np.arange(10_000))
    bf = to_bfloat16(v)
    assert np.mean(bf != v) > 0.99
    assert np.max(np.abs(bf - v)) <= 2.0 ** -8


ROOT = Path(__file__).resolve().parents[2]
MIXES = {"o32-tiny": [ROOT / "bench" / "traffic" / "o1280-hot-open.json",
                      DATA / "mixes" / "random.json"],
         "n12-tiny": [DATA / "mixes" / "drift.json",
                      DATA / "mixes" / "closed.json"]}


def mixes(config_name):
    for path in MIXES[config_name]:
        yield json.loads(path.read_text())


@pytest.mark.parametrize("config_name", ["o32-tiny", "n12-tiny"])
def test_reference_offsets_equal_the_planner_on_every_mix(config_name):
    from repro.core import Slicer

    config = json.loads((DATA / f"{config_name}.json").read_text())
    ref = Reference(config)
    slicer = Slicer(build_cube(config), fast_paths=False)
    checked = 0
    for mix in mixes(config_name):
        t = Traffic.load(mix, config)
        if t.loop["kind"] == "open":
            _, descs = t.open_loop(seed=11, seconds=60 / t.loop["rate_per_s"])
        else:
            descs = [s.desc for s in t.streams]
        for d in descs:
            plan, _ = slicer.extract_plan(to_request(d))
            np.testing.assert_array_equal(plan.offsets, ref.offsets(d))
            checked += 1
    assert checked >= 60
