"""Delta planner (DESIGN.md §6): differential suite against cold planning.

The contract under test: for every eligible drift, ``DeltaPlanner.splice``
must emit a plan *byte-identical* to running Algorithm 1 cold on the
drifted request — same offsets, same coalesced runs, same coords, same
§5.2 slice statistics — and every ineligible drift must fall back
(``None``) transparently, never emit an approximate plan.

Drift deltas in these tests are exact float64 multiples of the axis
steps (lon step 10 deg on the 36-column test cube, datetime step 28800 s
with 3 times/day, integer levels), so cold and spliced cell selection
cannot diverge through rounding.
"""

import numpy as np
import pytest

from repro.analysis.plan_check import verify_plan
from repro.core import (Box, DeltaPlanner, Polygon, PolytopeExtractor,
                        Request, Select, Span)
from repro.dataplane.weather import COUNTRIES, IrregularWeatherCube
from repro.serve.extraction import NeighborhoodIndex
from repro.serve.sharded import ShardedExtractionService

LON_STEP = 10.0          # 360 / 36
DT_STEP = 28800.0        # 86400 / 3 times per day


@pytest.fixture(scope="module")
def wcube():
    return IrregularWeatherCube(n_dates=2, times_per_day=3, n_levels=4,
                                n_lat=24, n_lon=36)


@pytest.fixture(scope="module")
def extractor(wcube):
    return PolytopeExtractor(wcube.cube)


@pytest.fixture(scope="module")
def planner(wcube, extractor):
    return DeltaPlanner(wcube.cube, slicer=extractor.slicer)


def lon_box(lon_lo, lon_hi, lat_lo=20.0, lat_hi=70.0, datetime=0.0,
            level=1.0):
    """A box at one datetime; ``level`` a value, or ``(lo, hi)`` a span."""
    lev = (Span("level", *level) if isinstance(level, tuple)
           else Select("level", [level]))
    return Request([Select("datetime", [datetime]), lev,
                    Box(("lat", "lon"), [lat_lo, lon_lo],
                        [lat_hi, lon_hi])])


def window_req(t0, n_steps=3, level=1.0):
    return Request([Span("datetime", t0, t0 + (n_steps - 1) * DT_STEP),
                    Select("level", [level]),
                    Box(("lat", "lon"), [10.0, 40.0], [60.0, 120.0])])


def assert_identical(plan, stats, cold_plan, cold_stats):
    np.testing.assert_array_equal(plan.offsets, cold_plan.offsets)
    np.testing.assert_array_equal(plan.run_starts, cold_plan.run_starts)
    np.testing.assert_array_equal(plan.run_lengths, cold_plan.run_lengths)
    assert set(plan.coords) == set(cold_plan.coords)
    for k in plan.coords:
        np.testing.assert_array_equal(plan.coords[k], cold_plan.coords[k])
    assert plan.itemsize == cold_plan.itemsize
    assert stats.n_points == cold_stats.n_points
    assert stats.n_slices == cold_stats.n_slices
    assert stats.n_slices_by_dim == cold_stats.n_slices_by_dim


def splice_or_fail(planner, extractor, r_old, r_new, dc):
    """Plan r_old cold, splice to r_new, and differentially check the
    result against planning r_new cold.  Fails the test on fallback."""
    sig_old, a_old = r_old.shape_signature()
    sig_new, a_new = r_new.shape_signature()
    assert sig_old == sig_new, "drift must preserve the shape signature"
    shifts = planner.axis_shifts(a_old, a_new)
    assert shifts is not None
    p_old, s_old = extractor.plan(r_old)
    out = planner.splice(r_new, r_old, p_old, s_old, shifts)
    assert out is not None, f"unexpected fallback for shifts={shifts}"
    plan, stats = out
    verify_plan(plan, datacube=dc, stats=stats)
    cold_plan, cold_stats = extractor.plan(r_new)
    assert_identical(plan, stats, cold_plan, cold_stats)
    return shifts


class TestEligibility:
    def test_probed_axes(self, planner):
        assert set(planner._info) == {"datetime", "level", "lon"}
        assert planner._info["lon"].cyclic
        assert not planner._info["datetime"].cyclic
        assert planner._info["level"].step == 1.0

    def test_gaussian_lat_is_ineligible(self, planner):
        # non-uniform mapped axis: any lat drift must force a cold plan
        assert planner.axis_shifts({"lat": 20.0}, {"lat": 21.0}) is None

    def test_zero_delta_axes_are_dropped(self, planner):
        shifts = planner.axis_shifts({"lon": 40.0, "level": 1.0},
                                     {"lon": 50.0, "level": 1.0})
        assert shifts == {"lon": (10.0, 1)}

    def test_fractional_step_is_rejected(self, planner):
        assert planner.axis_shifts({"lon": 40.0}, {"lon": 44.0}) is None

    def test_drift_radius_bound(self, wcube, extractor):
        dp = DeltaPlanner(wcube.cube, slicer=extractor.slicer, max_steps=2)
        assert dp.axis_shifts({"lon": 0.0}, {"lon": 20.0}) is not None
        assert dp.axis_shifts({"lon": 0.0}, {"lon": 30.0}) is None

    def test_anchor_key_mismatch(self, planner):
        assert planner.axis_shifts({"lon": 0.0},
                                   {"lon": 0.0, "level": 1.0}) is None


class TestSpliceByteIdentity:
    def test_lon_box_single_step(self, planner, extractor, wcube):
        shifts = splice_or_fail(planner, extractor,
                                lon_box(34.0, 76.0),
                                lon_box(44.0, 86.0), wcube.cube)
        assert shifts == {"lon": (LON_STEP, 1)}

    def test_lon_box_multi_step_and_negative(self, planner, extractor,
                                             wcube):
        for k in (3, -2, 7):
            splice_or_fail(planner, extractor, lon_box(34.0, 76.0),
                           lon_box(34.0 + k * LON_STEP,
                                   76.0 + k * LON_STEP), wcube.cube)

    def test_lon_box_crosses_seam(self, planner, extractor, wcube):
        # box drifts over the 360/0 wrap; offsets wrap within the digit
        splice_or_fail(planner, extractor, lon_box(311.0, 353.0),
                       lon_box(331.0, 373.0), wcube.cube)

    def test_wrapping_drift_reduces_mod_circle(self, planner, extractor,
                                               wcube):
        # +33 columns on a 36-column circle is really −3: the reduced
        # shift stays inside the drift radius and splices exactly
        shifts = splice_or_fail(planner, extractor, lon_box(34.0, 76.0),
                                lon_box(34.0 + 33 * LON_STEP,
                                        76.0 + 33 * LON_STEP), wcube.cube)
        assert shifts["lon"][1] == -3

    def test_level_interior_drift(self, planner, extractor, wcube):
        shifts = splice_or_fail(planner, extractor,
                                lon_box(34.0, 76.0, level=1.0),
                                lon_box(34.0, 76.0, level=2.0), wcube.cube)
        assert shifts == {"level": (1.0, 1)}

    def test_combined_lon_and_level_drift(self, planner, extractor, wcube):
        splice_or_fail(planner, extractor,
                       lon_box(34.0, 76.0, level=1.0),
                       lon_box(54.0, 96.0, level=2.0), wcube.cube)

    def test_rolling_window_forward(self, planner, extractor, wcube):
        # lead-axis Span drift: 2 slabs kept, 1 fresh, 1 dropped
        splice_or_fail(planner, extractor, window_req(0.0),
                       window_req(DT_STEP), wcube.cube)

    def test_rolling_window_backward(self, planner, extractor, wcube):
        splice_or_fail(planner, extractor, window_req(2 * DT_STEP),
                       window_req(DT_STEP), wcube.cube)

    def test_rolling_window_two_steps(self, planner, extractor, wcube):
        # only 1 of 3 slabs overlaps the parent window
        splice_or_fail(planner, extractor, window_req(0.0),
                       window_req(2 * DT_STEP), wcube.cube)

    def test_disjoint_windows_still_splice(self, planner, extractor, wcube):
        # zero window overlap is still a pure translation on a uniform
        # lead axis: every slab's sub-tree is identical, so the whole
        # plan shifts arithmetically without re-slicing anything
        splice_or_fail(planner, extractor, window_req(0.0),
                       window_req(3 * DT_STEP), wcube.cube)

    @pytest.mark.parametrize("old, new", [
        (1.0, 0.0), (2.0, 3.0),
        ((1.0, 2.0), (0.0, 1.0)), ((1.0, 2.0), (2.0, 3.0)),
        ((0.0, 1.0), (2.0, 3.0))],
        ids=["select-onto-first", "select-onto-last", "span-onto-first",
             "span-onto-last", "span-first-to-last"])
    def test_level_drift_onto_the_edge(self, planner, extractor, wcube,
                                       old, new):
        # a window that reaches the first or last level clips nothing,
        # so the drift splices to the cold plan's bytes
        shifts = splice_or_fail(planner, extractor,
                                lon_box(34.0, 76.0, level=old),
                                lon_box(34.0, 76.0, level=new), wcube.cube)
        assert set(shifts) == {"level"}

    def test_lead_select_drift(self, planner, extractor, wcube):
        splice_or_fail(planner, extractor,
                       lon_box(34.0, 76.0, datetime=0.0),
                       lon_box(34.0, 76.0, datetime=2 * DT_STEP),
                       wcube.cube)

    def test_storm_track_polygon(self, planner, extractor, wcube):
        def storm(d):
            verts = COUNTRIES["france"].copy()
            verts[:, 1] += d
            return Request([Select("datetime", [0.0]),
                            Select("level", [1.0]),
                            Polygon(("lat", "lon"), verts)])
        splice_or_fail(planner, extractor, storm(0.0), storm(2 * LON_STEP),
                       wcube.cube)

    def test_seeded_drift_sweep(self, planner, extractor, wcube):
        rng = np.random.default_rng(7)
        prev = lon_box(34.0, 76.0)
        lon = 34.0
        for _ in range(12):
            k = int(rng.integers(-4, 5))
            if k == 0:
                continue
            lon += k * LON_STEP
            cur = lon_box(lon, lon + 42.0)
            splice_or_fail(planner, extractor, prev, cur, wcube.cube)
            prev = cur

    def test_zero_shift_passthrough_reuses_parent(self, planner, extractor):
        r = lon_box(34.0, 76.0)
        p, s = extractor.plan(r)
        out = planner.splice(r, r, p, s, {})
        assert out is not None
        plan, stats = out
        assert plan is p            # parent object reused, not copied
        assert stats.n_points == s.n_points
        assert stats.n_slices_by_dim == s.n_slices_by_dim


class TestFallbackTransparency:
    @staticmethod
    def assert_falls_back(planner, extractor, r_old, r_new):
        shifts = planner.axis_shifts(r_old.shape_signature()[1],
                                     r_new.shape_signature()[1])
        assert shifts is not None and set(shifts) == {"level"}
        p, s = extractor.plan(r_old)
        assert planner.splice(r_new, r_old, p, s, shifts) is None

    def test_boundary_level_select_falls_back(self, planner, extractor):
        # levels are 0..3: a select drifted past the last level snaps
        # back to it when planned cold, so the shifted parent is wrong
        self.assert_falls_back(planner, extractor,
                               lon_box(34.0, 76.0, level=3.0),
                               lon_box(34.0, 76.0, level=4.0))

    @pytest.mark.parametrize("old, new", [
        ((2.0, 3.0), (3.0, 4.0)), ((0.0, 1.0), (-1.0, 0.0))],
        ids=["past-last", "past-first"])
    def test_level_span_past_the_edge_falls_back(self, planner, extractor,
                                                 old, new):
        # cold planning clips the window at the edge and loses a level
        # that the shifted parent would keep
        self.assert_falls_back(planner, extractor,
                               lon_box(34.0, 76.0, level=old),
                               lon_box(34.0, 76.0, level=new))

    def test_near_full_circle_cyclic_falls_back(self, planner, extractor):
        # a lon window wider than period − step can alias across the
        # seam under shifting — the splicer refuses it
        r_old = lon_box(1.0, 352.0)
        r_new = lon_box(11.0, 362.0)
        shifts = planner.axis_shifts(r_old.shape_signature()[1],
                                     r_new.shape_signature()[1])
        assert shifts is not None
        p, s = extractor.plan(r_old)
        assert planner.splice(r_new, r_old, p, s, shifts) is None

    def test_service_falls_back_cold_on_lat_drift(self, wcube):
        svc = ShardedExtractionService(wcube.cube, shards=1,
                                       verify=True)
        cold = PolytopeExtractor(wcube.cube)
        r0 = lon_box(34.0, 76.0, lat_lo=20.0, lat_hi=60.0)
        r1 = lon_box(34.0, 76.0, lat_lo=25.0, lat_hi=65.0)
        svc.plan(r0)
        plan, cached, _ = svc.plan(r1)
        assert not cached
        assert svc.stats.delta_hits == 0
        np.testing.assert_array_equal(plan.offsets, cold.plan(r1)[0].offsets)


class TestServiceDelta:
    def test_drift_stream_counters_and_values(self, wcube):
        svc = ShardedExtractionService(wcube.cube, shards=1,
                                       verify=True)
        data = wcube.field_data(seed=3)
        results = []
        for k in range(6):
            r = lon_box(34.0 + k * LON_STEP, 76.0 + k * LON_STEP)
            results.append(svc.extract(r, data))
        st = svc.stats
        assert st.delta_hits == 5
        assert st.misses == 6 and st.hits == 0
        assert st.lookups == st.hits + st.misses
        for res in results:
            np.testing.assert_array_equal(res.values,
                                          data[res.plan.offsets])
        # the exact key was installed: replay is a plain cache hit
        res = svc.extract(lon_box(34.0 + 5 * LON_STEP,
                                  76.0 + 5 * LON_STEP), data)
        assert res.cached

    def test_spliced_equals_cold_service(self, wcube):
        warm = ShardedExtractionService(wcube.cube, shards=1, verify=True,
                                        delta=True)
        cold = ShardedExtractionService(wcube.cube, shards=1, verify=True,
                                        delta=False)
        for k in range(4):
            r = lon_box(34.0 + k * LON_STEP, 76.0 + k * LON_STEP)
            pw, _, _ = warm.plan(r)
            pc, _, _ = cold.plan(r)
            np.testing.assert_array_equal(pw.offsets, pc.offsets)
            np.testing.assert_array_equal(pw.run_starts, pc.run_starts)
        assert warm.stats.delta_hits == 3
        assert cold.stats.delta_hits == 0

    def test_evicted_parent_plans_cold(self, wcube):
        svc = ShardedExtractionService(wcube.cube, shards=1,
                                       capacity_per_shard=1, verify=True)
        r0, r1 = lon_box(34.0, 76.0), lon_box(44.0, 86.0)
        svc.plan(r0)
        # parent evicted by an unrelated plan: neighborhood entry is
        # stale, peek misses, and the drifted request must plan cold
        svc.plan(window_req(0.0))
        plan, cached, _ = svc.plan(r1)
        assert not cached and plan.n_points > 0

    def test_delta_disabled_has_no_neighborhood(self, wcube):
        svc = ShardedExtractionService(wcube.cube, shards=1, delta=False)
        svc.plan(lon_box(34.0, 76.0))
        svc.plan(lon_box(44.0, 86.0))
        assert svc.stats.delta_hits == 0 and svc.stats.delta_misses == 0


class TestNeighborhoodIndex:
    def test_per_signature_bound_and_mru_order(self):
        idx = NeighborhoodIndex(capacity=16, per_signature=2)
        for i in range(3):
            idx.add("sig", f"k{i}", {"lon": float(i)}, None, None)
        cands = idx.candidates("sig")
        assert [c.key for c in cands] == ["k2", "k1"]   # MRU first, k0 out

    def test_capacity_evicts_lru_signature(self):
        idx = NeighborhoodIndex(capacity=2, per_signature=4)
        idx.add("s1", "a", {}, None, None)
        idx.add("s2", "b", {}, None, None)
        idx.add("s3", "c", {}, None, None)
        assert idx.candidates("s1") == []
        assert len(idx.candidates("s3")) == 1

    def test_pop_and_install_roundtrip(self):
        idx = NeighborhoodIndex(capacity=8)
        idx.add("s1", "a", {"lon": 1.0}, None, None)
        moved = idx.pop_signature("s1")
        assert idx.candidates("s1") == []
        idx2 = NeighborhoodIndex(capacity=8)
        idx2.install("s1", moved)
        assert [c.key for c in idx2.candidates("s1")] == ["a"]


class TestShardedDelta:
    def test_drift_stream_parity_and_counters(self, wcube):
        svc = ShardedExtractionService(wcube.cube, shards=3,
                                       capacity_per_shard=64, verify=True)
        cold = PolytopeExtractor(wcube.cube)
        data = wcube.field_data(seed=5)
        for k in range(5):
            r = lon_box(34.0 + k * LON_STEP, 76.0 + k * LON_STEP)
            res = svc.extract(r, data)
            np.testing.assert_array_equal(res.plan.offsets,
                                          cold.plan(r)[0].offsets)
            np.testing.assert_array_equal(res.values,
                                          data[res.plan.offsets])
        assert svc.shards.stats.delta_hits == 4

    def test_signature_routing_is_consistent(self, wcube):
        # every member of a drift chain shares one signature, so the
        # chain lands in exactly one shard's neighborhood index
        svc = ShardedExtractionService(wcube.cube, shards=4,
                                       capacity_per_shard=64)
        for k in range(4):
            svc.plan(lon_box(34.0 + k * LON_STEP, 76.0 + k * LON_STEP))
        populated = [n for n, h in svc.shards._hoods.items() if len(h)]
        assert len(populated) == 1

    def test_rebalance_migrates_neighborhoods(self, wcube):
        svc = ShardedExtractionService(wcube.cube, shards=2,
                                       capacity_per_shard=64, verify=True)
        for k in range(3):
            svc.plan(lon_box(34.0 + k * LON_STEP, 76.0 + k * LON_STEP))
        before = svc.shards.stats.delta_hits
        assert before == 2
        svc.shards.add_shard("shard-new")
        # chain must keep splicing after the hood reroutes
        svc.plan(lon_box(64.0, 106.0))
        assert svc.shards.stats.delta_hits == before + 1


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    _props = settings(max_examples=25, deadline=None)

    class TestDriftSweepHypothesis:
        """Hypothesis-deepened drift sweep: any integral (lon, level,
        datetime) drift vector inside the eligibility envelope must
        splice byte-identically to cold planning."""

        @_props
        @given(lon_k=st.integers(-6, 6), lev_k=st.integers(-1, 1),
               dt_k=st.integers(-2, 2))
        def test_splice_matches_cold(self, lon_k, lev_k, dt_k):
            if lon_k == 0 and lev_k == 0 and dt_k == 0:
                return
            wc = IrregularWeatherCube(n_dates=2, times_per_day=3,
                                      n_levels=4, n_lat=24, n_lon=36)
            ex = PolytopeExtractor(wc.cube)
            dp = DeltaPlanner(wc.cube, slicer=ex.slicer)
            r_old = lon_box(34.0, 76.0, level=1.0, datetime=2 * DT_STEP)
            r_new = lon_box(34.0 + lon_k * LON_STEP,
                            76.0 + lon_k * LON_STEP,
                            level=1.0 + lev_k,
                            datetime=(2 + dt_k) * DT_STEP)
            splice_or_fail(dp, ex, r_old, r_new, wc.cube)
