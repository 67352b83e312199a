"""Axis-transform layer (DESIGN.md §2.5): unit semantics, the
differential harness (transformed extraction ≡ materialized-cube
extraction, byte for byte), and seam canonicalization (period-shifted
cyclic requests share one plan-cache key).

The differential oracle: ``IrregularWeatherCube.materialized()`` builds
the explicitly merged/remapped cube over plain axes with the *same*
flat storage layout, so a request answered through the transform layer
must produce exactly the same offsets — and therefore the same bytes —
as the request answered against the materialized cube (cross-seam
cyclic requests are split into in-period pieces by hand on the
materialized side).
"""

import numpy as np
import pytest

from repro.core import (Box, CyclicAxis, CyclicTransform, MappedTransform,
                        MergedTransform, OrderedAxis, Polygon,
                        PolytopeExtractor, Request, Select, Slicer, Span,
                        TensorDatacube, TransformedDatacube, Union)
from repro.dataplane.weather import (COUNTRIES, IrregularWeatherCube,
                                     gaussian_latitudes)
from repro.serve.sharded import ShardedExtractionService

PERIOD = 360.0


def small_irregular(**kw):
    kw.setdefault("n_dates", 2)
    kw.setdefault("times_per_day", 3)
    kw.setdefault("n_levels", 2)
    kw.setdefault("n_lat", 24)
    kw.setdefault("n_lon", 36)
    return IrregularWeatherCube(**kw)


def split_lon_span(lo: float, hi: float, period: float = PERIOD):
    """Canonical in-period pieces of an unwrapped [lo, hi] lon interval
    (the manual seam split the transform layer performs internally)."""
    if hi - lo >= period:
        return [(0.0, period)]
    k = np.floor(lo / period)
    lo, hi = lo - k * period, hi - k * period
    if hi < period:
        return [(lo, hi)]
    # hi lands on/over the seam: the wrapped tail [0, hi-period] is part
    # of the interval (hi == period includes stored value 0 exactly)
    return [(lo, period), (0.0, hi - period)]


def assert_same_bytes(plan_t, plan_m, data):
    """Byte-identity: same storage offsets ⇒ same bytes."""
    np.testing.assert_array_equal(np.sort(plan_t.offsets),
                                  np.sort(plan_m.offsets))
    np.testing.assert_array_equal(data[np.sort(plan_t.offsets)],
                                  data[np.sort(plan_m.offsets)])


# ---------------------------------------------------------------------------
class TestTransformUnits:
    def test_logical_axis_names_and_periods(self):
        iwc = small_irregular()
        assert iwc.cube.axis_names == ("datetime", "level", "lat", "lon")
        assert iwc.cube.axis_periods() == {"lon": 360.0}

    def test_merged_positions_roundtrip(self):
        t = MergedTransform("dt", ("date", "time"))
        t.logical_axis([OrderedAxis("date", [0.0, 86400.0]),
                        OrderedAxis("time", [0.0, 21600.0, 43200.0])])
        maj, mnr = t.storage_positions(np.arange(6))
        np.testing.assert_array_equal(maj, [0, 0, 0, 1, 1, 1])
        np.testing.assert_array_equal(mnr, [0, 1, 2, 0, 1, 2])

    def test_merged_requires_monotone_combination(self):
        t = MergedTransform("dt", ("date", "time"))
        with pytest.raises(ValueError, match="strictly increasing"):
            # major step (10) smaller than minor span (0..15)
            t.logical_axis([OrderedAxis("date", [0.0, 10.0]),
                            OrderedAxis("time", [0.0, 15.0])])

    def test_mapped_requires_matching_length_and_monotone(self):
        ax = OrderedAxis("row", np.arange(4.0))
        with pytest.raises(ValueError, match="values for"):
            MappedTransform("lat", "row", values=[1.0, 2.0]).logical_axis([ax])
        with pytest.raises(ValueError, match="monotone"):
            MappedTransform("lat", "row",
                            values=[0.0, 2.0, 1.0, 3.0]).logical_axis([ax])

    def test_mapped_func_form(self):
        ax = OrderedAxis("row", np.arange(5.0))
        t = MappedTransform("lat", "row", func=lambda i: 90.0 - 2.0 * i ** 2)
        logical = t.logical_axis([ax])
        assert len(logical) == 5

    def test_storage_axes_must_be_consecutive(self):
        base = TensorDatacube([OrderedAxis(n, np.arange(3.0))
                               for n in ("a", "b", "c")])
        with pytest.raises(ValueError, match="consecutive"):
            TransformedDatacube(base, [MergedTransform("ac", ("a", "c"))])

    def test_offsets_resolve_to_storage(self):
        iwc = small_irregular()
        tdc, base = iwc.cube, iwc.cube.base
        ntime = iwc.times_per_day
        # logical datetime position p ↔ storage (date p//ntime, time p%ntime)
        for p in (0, ntime - 1, ntime, 2 * ntime - 1):
            lo = tdc.base_offset({"datetime": p, "level": 1, "lat": 5,
                                  "lon": 7})
            so = base.base_offset({"date": p // ntime, "time": p % ntime,
                                   "level": 1, "lat_row": 5, "lon": 7})
            assert lo == so

    def test_leaf_offsets_contiguous_for_trailing_axis(self):
        iwc = small_irregular()
        pos = np.arange(10, dtype=np.int64)
        offs = iwc.cube.leaf_offsets(
            {"datetime": 1, "level": 0, "lat": 3}, pos)
        assert np.all(np.diff(offs) == 1)

    def test_merged_leaf_offsets_contiguous_across_minor_boundary(self):
        # merged pair as the deepest axes: logical positions stay
        # byte-contiguous across the date/time storage split
        base = TensorDatacube([OrderedAxis("x", np.arange(2.0)),
                               OrderedAxis("date", [0.0, 86400.0]),
                               OrderedAxis("time", [0.0, 21600.0])])
        tdc = TransformedDatacube(base, [MergedTransform("dt",
                                                         ("date", "time"))])
        offs = tdc.leaf_offsets({"x": 1}, np.arange(4, dtype=np.int64))
        np.testing.assert_array_equal(offs, [4, 5, 6, 7])

    def test_cyclic_nearest_wraps_across_seam(self):
        ax = CyclicAxis("lon", 360.0 * np.arange(16) / 16, period=360.0)
        pos, val = ax.nearest(359.9)          # 0.1° across the seam
        assert (pos, val) == (0, 0.0)
        pos, val = ax.nearest(340.0)          # 2.5° to 337.5, 20° to 360
        assert (pos, val) == (15, 337.5)
        pos, val = ax.nearest(-8.0)           # wraps to 352 → nearest 360≡0
        assert (pos, val) == (0, 0.0)


# ---------------------------------------------------------------------------
class TestDifferentialMaterialized:
    """For any request, extraction through transformed axes is
    byte-identical to extraction against the explicitly materialized
    (unrolled/merged/remapped) datacube."""

    def test_merged_and_mapped_randomized_boxes(self):
        iwc = small_irregular()
        tdc, mat = iwc.cube, iwc.materialized()
        data = iwc.field_data(seed=11)
        dtv = iwc.datetime_values
        rng = np.random.default_rng(42)
        for _ in range(25):
            t0, t1 = np.sort(rng.uniform(dtv[0] - 1e4, dtv[-1] + 1e4, 2))
            la0, la1 = np.sort(rng.uniform(-90, 90, 2))
            lo0 = rng.uniform(0, 300.0)
            lo1 = lo0 + rng.uniform(0, 359.0 - lo0)  # in-period lon
            req = Request([Span("datetime", t0, t1),
                           Box(("lat", "lon"), [la0, lo0], [la1, lo1])])
            plan_t, _ = Slicer(tdc).extract_plan(req)
            plan_m, _ = Slicer(mat).extract_plan(req)
            assert_same_bytes(plan_t, plan_m, data)

    def test_cyclic_randomized_cross_seam_spans(self):
        iwc = small_irregular()
        tdc, mat = iwc.cube, iwc.materialized()
        data = iwc.field_data(seed=12)
        rng = np.random.default_rng(7)
        n_straddle = 0
        for _ in range(30):
            lo = rng.uniform(-720.0, 720.0)
            width = rng.uniform(1.0, 500.0)
            hi = lo + width
            segs = split_lon_span(lo, hi)
            n_straddle += len(segs) > 1
            shapes = [Select("datetime", [0.0]), Select("level", [0.0]),
                      Span("lat", -60.0, 60.0)]
            req_t = Request(shapes + [Span("lon", lo, hi)])
            req_m = Request(shapes + [Union([Span("lon", a, b)
                                             for a, b in segs])])
            plan_t, _ = Slicer(tdc).extract_plan(req_t)
            plan_m, _ = Slicer(mat).extract_plan(req_m)
            assert_same_bytes(plan_t, plan_m, data)
        assert n_straddle > 5          # the sample genuinely hit the seam

    def test_whole_circle_request_reads_every_lon(self):
        iwc = small_irregular()
        plan, _ = Slicer(iwc.cube).extract_plan(Request([
            Select("datetime", [0.0]), Select("level", [0.0]),
            Select("lat", [float(iwc.latitudes[3])]),
            Span("lon", -123.0, -123.0 + 360.0)]))
        assert plan.n_points == iwc.n_lon
        assert plan.n_runs == 1       # one contiguous storage row

    def test_cross_seam_country_polygon(self):
        iwc = small_irregular(n_lat=96, n_lon=192)
        data = iwc.field_data(seed=13)
        pe = PolytopeExtractor(iwc.cube)
        res = pe.extract(iwc.country_request("uk"), data)
        # materialized oracle: the polygon plus its +period copy
        pts = COUNTRIES["uk"]
        req_m = Request([Select("datetime", [0.0]), Select("level", [0.0]),
                         Union([Polygon(("lat", "lon"), pts),
                                Polygon(("lat", "lon"),
                                        pts + [0.0, 360.0])])])
        plan_m, _ = Slicer(iwc.materialized()).extract_plan(req_m)
        assert res.plan.n_points > 0
        assert_same_bytes(res.plan, plan_m, data)
        # the crop genuinely straddles the seam: unwrapped lon values on
        # both sides
        lons = res.plan.coords["lon"]
        assert lons.min() < 0.0 <= lons.max()

    def test_timeseries_across_date_boundary(self):
        iwc = small_irregular()
        data = iwc.field_data(seed=14)
        t0 = float(iwc.time_values[-1]) - 1.0          # last slot of day 0
        t1 = 86400.0 + float(iwc.time_values[0]) + 1.0  # first of day 1
        req = iwc.timeseries_request(float(iwc.latitudes[5]),
                                     float(iwc.lon_values[4]), t0, t1)
        plan_t, _ = Slicer(iwc.cube).extract_plan(req)
        plan_m, _ = Slicer(iwc.materialized()).extract_plan(req)
        assert plan_t.n_points == 2                    # one each side
        assert_same_bytes(plan_t, plan_m, data)

    def test_slice_stats_consistent_on_transformed_cube(self):
        iwc = small_irregular()
        _, stats = Slicer(iwc.cube).extract_plan(
            iwc.seam_box_request(-30.0, 30.0, -40.0, 40.0))
        assert stats.n_slices > 0
        assert sum(stats.n_slices_by_dim.values()) == stats.n_slices


# ---------------------------------------------------------------------------
class TestSeamCanonicalization:
    """Seam-straddling cyclic requests shifted by whole periods share one
    canonical hash — the plan cache hits across the seam."""

    def periods(self):
        return {"lon": PERIOD}

    def test_period_shifted_spans_share_hash(self):
        p = self.periods()
        reqs = [Request([Span("lon", -20.0 + k * PERIOD,
                              20.0 + k * PERIOD)]) for k in (-2, -1, 0, 1, 3)]
        hashes = {r.canonical_hash(periods=p) for r in reqs}
        assert len(hashes) == 1
        # without periods they are distinct spellings
        assert len({r.canonical_hash() for r in reqs}) == len(reqs)

    def test_period_shifted_polygons_share_hash(self):
        p = self.periods()
        pts = COUNTRIES["uk"]
        r0 = Request([Polygon(("lat", "lon"), pts)])
        r1 = Request([Polygon(("lat", "lon"), pts + [0.0, 360.0])])
        r2 = Request([Polygon(("lat", "lon"), pts - [0.0, 720.0])])
        assert (r0.canonical_hash(periods=p) == r1.canonical_hash(periods=p)
                == r2.canonical_hash(periods=p))

    def test_select_values_fold_modulo_period(self):
        p = self.periods()
        assert (Request([Select("lon", [350.0])]).canonical_hash(periods=p)
                == Request([Select("lon", [-10.0])]).canonical_hash(periods=p))
        # non-cyclic axes unaffected
        assert (Request([Select("lat", [350.0])]).canonical_hash(periods=p)
                != Request([Select("lat", [-10.0])]).canonical_hash(periods=p))

    def test_distinct_geometry_still_distinct(self):
        p = self.periods()
        assert (Request([Span("lon", -20.0, 20.0)]).canonical_hash(periods=p)
                != Request([Span("lon", -20.0, 25.0)]).canonical_hash(periods=p))

    def test_plan_cache_hits_across_the_seam(self):
        iwc = small_irregular()
        svc = ShardedExtractionService(iwc.cube, shards=1)
        base = iwc.seam_box_request(30.0, 60.0, -15.0, 15.0)
        shifted = Request([Select("datetime", [0.0]), Select("level", [0.0]),
                           Box(("lat", "lon"), [30.0, 345.0],
                               [60.0, 375.0])])
        cold = svc.extract(base)
        warm = svc.extract(shifted)
        assert not cold.cached and warm.cached
        assert warm.plan is cold.plan
        assert svc.stats.hits == 1 and svc.stats.misses == 1

    def test_service_plans_match_plain_slicer_on_transformed_cube(self):
        iwc = small_irregular()
        svc = ShardedExtractionService(iwc.cube, shards=1)
        req = iwc.country_request("uk")
        res = svc.extract(req)
        ref, _ = Slicer(iwc.cube).extract_plan(iwc.country_request("uk"))
        np.testing.assert_array_equal(res.plan.offsets, ref.offsets)


# ---------------------------------------------------------------------------
class TestStandaloneTransformCubes:
    """Transforms compose with arbitrary regular bases, not just the
    weather scenario."""

    def test_mapped_only_cube_matches_plain_irregular_axis(self):
        vals = np.cumsum(np.random.default_rng(3).uniform(0.5, 2.0, 20))
        base = TensorDatacube([OrderedAxis("row", np.arange(20.0)),
                               OrderedAxis("y", np.arange(8.0))])
        tdc = TransformedDatacube(base, [MappedTransform("x", "row",
                                                         values=vals)])
        mat = TensorDatacube([OrderedAxis("x", vals),
                              OrderedAxis("y", np.arange(8.0))])
        req = Request([Box(("x", "y"), [vals[3], 2.0], [vals[11], 6.0])])
        plan_t, _ = Slicer(tdc).extract_plan(req)
        plan_m, _ = Slicer(mat).extract_plan(req)
        np.testing.assert_array_equal(plan_t.offsets, plan_m.offsets)

    def test_descending_mapped_values_keep_storage_order(self):
        # north→south latitudes: logical values descending in storage
        lats = gaussian_latitudes(12)
        assert lats[0] > lats[-1]
        base = TensorDatacube([OrderedAxis("row", np.arange(12.0)),
                               OrderedAxis("y", np.arange(4.0))])
        tdc = TransformedDatacube(base, [MappedTransform("lat", "row",
                                                         values=lats)])
        plan, _ = Slicer(tdc).extract_plan(
            Request([Select("lat", [float(lats[2])]), Span("y", 0.0, 3.0)]))
        # storage row 2 (third from north), full y row
        np.testing.assert_array_equal(plan.offsets, np.arange(8, 12))

    def test_cyclic_transform_equals_cyclic_axis_cube(self):
        vals = 360.0 * np.arange(24) / 24
        base = TensorDatacube([OrderedAxis("t", np.arange(3.0)),
                               OrderedAxis("lon", vals)])
        tdc = TransformedDatacube(base, [CyclicTransform("lon",
                                                         period=360.0)])
        direct = TensorDatacube([OrderedAxis("t", np.arange(3.0)),
                                 CyclicAxis("lon", vals, period=360.0)])
        req = Request([Select("t", [1.0]), Span("lon", -50.0, 20.0)])
        plan_t, _ = Slicer(tdc).extract_plan(req)
        plan_d, _ = Slicer(direct).extract_plan(req)
        np.testing.assert_array_equal(np.sort(plan_t.offsets),
                                      np.sort(plan_d.offsets))
        assert tdc.axis_periods() == direct.axis_periods() == {"lon": 360.0}
