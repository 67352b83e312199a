"""The trace reduction, on a small trace recorded on a TPU v5e: five
``jnp.take`` calls of 100 to 2,000 points from a 1 Mi-element array,
with their copies back to the host."""

from pathlib import Path

import numpy as np
import pytest

from harness import trace as tr

FIXTURE = Path(__file__).parent / "data" / "take.xplane.pb"


@pytest.fixture(scope="module")
def profile():
    return tr.load(FIXTURE)


def test_union_intervals_merges_overlaps():
    iv = tr.union_intervals(np.array([0.0, 1.0, 5.0, 6.0]),
                            np.array([2.0, 3.0, 6.0, 8.0]))
    np.testing.assert_array_equal(iv, [[0, 3], [5, 8]])


def test_device_busy_is_the_union_of_op_intervals(profile):
    lo, hi = tr.window_ns(profile, 0.2)
    r = tr.reduce_profile(profile, (lo, hi))
    ops = []
    for plane in profile.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
    iv = tr.union_intervals(np.array([o[0] for o in ops]),
                            np.array([o[1] for o in ops]))
    assert r.busy_s == pytest.approx((iv[:, 1] - iv[:, 0]).sum() / 1e9)
    assert 0 < r.busy_s < r.window_s == pytest.approx(0.2)


def test_gather_time_is_the_take_modules(profile):
    r = tr.reduce_profile(profile, tr.window_ns(profile, 0.2))
    take = r.module_seconds(r"^jit__take\(")
    assert take > 0
    assert take == pytest.approx(sum(r.module_s.values()))
    assert len([m for m in r.module_s if m.startswith("jit__take(")]) == 3


def test_idle_gaps_are_attributed_to_host_activity(profile):
    r = tr.reduce_profile(profile, tr.window_ns(profile, 0.2))
    labels = dict(r.idle_gaps)
    # every idle second is attributed once
    assert sum(labels.values()) == pytest.approx(r.window_s - r.busy_s,
                                                 rel=1e-6)
    # the first call of each size compiled inside the trace
    assert labels["compile"] > 0.05
    assert {"transfer", "dispatch", tr.UNTRACED} <= set(labels)
    assert len(r.device_ops) <= tr.TOP
    assert all(s > 0 for _, s in r.device_ops)


def test_a_trace_without_a_device_fails(tmp_path):
    class Empty:
        planes = []

    with pytest.raises(ValueError, match="no TPU device plane"):
        tr.reduce_profile(Empty(), (0.0, 1.0))


def test_device_ops_are_grouped_by_module_and_op(profile):
    assert tr.op_key("jit__take(6409336343963712020)",
                     "%fusion.3 = f32[4061]{0} fusion(...)") == \
        "jit__take:%fusion"
    r = tr.reduce_profile(profile, tr.window_ns(profile, 0.2))
    names = [k for k, _ in r.device_ops]
    assert names[0] == "jit__take:%fusion"      # the gather itself
    assert len(names) == len(set(names))
