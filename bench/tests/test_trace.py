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


def synthetic(host_ms, ops_ms=((0, 10), (90, 100))):
    """A trace of one TPU whose ops run over ``ops_ms`` and whose host
    thread holds the events ``(name, start, end)``, in milliseconds."""
    from types import SimpleNamespace as NS

    def line(name, events):
        return NS(name=name, events=[NS(name=n, start_ns=a * 1e6,
                                        duration_ns=(b - a) * 1e6)
                                     for n, a, b in events])

    return NS(planes=[
        NS(name="/device:TPU:0",
           lines=[line(tr.OPS_LINE, [("%fusion", a, b) for a, b in ops_ms])]),
        NS(name=tr.HOST_PLANE, lines=[line("worker", host_ms)])])


def test_idle_gaps_name_the_innermost_program_stage():
    r = tr.reduce_profile(synthetic([
        ("polytope.window", 10, 80),
        ("polytope.plan_cache.lookup", 10, 40),
        ("polytope.planner.delta", 15, 35),
        ("backend_compile", 20, 25),
        ("polytope.admission.collect", 40, 60)]), (0.0, 100e6))
    labels = dict(r.idle_gaps)
    assert labels == pytest.approx({
        "compile": 0.005, "polytope.planner.delta": 0.015,
        "polytope.plan_cache.lookup": 0.010,
        "polytope.admission.collect": 0.020, tr.UNTRACED: 0.030})
    assert sum(labels.values()) == pytest.approx(r.window_s - r.busy_s)
    assert "polytope.window" not in labels


def test_idle_gaps_keep_ten_labels_and_every_second():
    host = [("backend_compile", 10, 12), ("TransferToDevice", 12, 14),
            ("PjitFunction(_take)", 14, 16)]
    host += [(span, 16 + 4 * i, 20 + 4 * i)
             for i, span in enumerate(tr.PROGRAM_SPANS)]
    r = tr.reduce_profile(synthetic(host), (0.0, 100e6))
    assert len(r.idle_gaps) == tr.TOP
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(0.080)
    joined = r.idle_gaps[-1][0].split(" + ")
    assert len(joined) == 3 and set(joined) <= {"compile", "transfer",
                                                "dispatch"}
