"""Device-resident planner == host Algorithm-1 slicer, byte for byte.

The fused pipeline (repro.core.DevicePlanner → repro.kernels.plan)
promises *byte-identical* plans: same offsets, same coalesced runs,
same §5.2 slice statistics as the host planner — both the per-index
reference (``Slicer(fast_paths=False)``) and the production fast-path
planner.  The fast-lane classes exercise the jnp reference pipeline on
the irregular weather cube (merged datetime, mapped Gaussian latitudes,
cyclic longitude with seam-straddling requests); the slow classes add
the Pallas kernel (interpret mode, so the suite passes on CPU CI) and
hypothesis-generated geometry.
"""

import numpy as np
import pytest

from repro.analysis.plan_check import verify_plan
from repro.core import (Box, ConvexPolytope, DevicePlanner, ExtractionPlan,
                        OrderedAxis, PolytopeExtractor, Request, Select,
                        Slicer, TensorDatacube, batched_plan_2d,
                        batched_plan_runs_2d, compress_plan,
                        decompress_plan, gather)
from repro.dataplane.weather import IrregularWeatherCube, WeatherCube

COUNTRY_NAMES = ("france", "germany", "italy", "norway", "uk")


@pytest.fixture(scope="module")
def iwc():
    return IrregularWeatherCube()      # 96 × 192, cyclic lon


def _iwc_requests(iwc):
    reqs = {c: iwc.country_request(c) for c in COUNTRY_NAMES}
    reqs["seam_box"] = iwc.seam_box_request(35.0, 62.0, -25.0, 25.0)
    return reqs


def _assert_plans_equal(dev, host, label=""):
    dplan, dstats = dev
    hplan, hstats = host
    np.testing.assert_array_equal(dplan.offsets, hplan.offsets, label)
    np.testing.assert_array_equal(dplan.run_starts, hplan.run_starts)
    np.testing.assert_array_equal(dplan.run_lengths, hplan.run_lengths)
    assert dstats.n_slices == hstats.n_slices, label
    assert dstats.n_slices_by_dim == hstats.n_slices_by_dim, label
    assert dstats.n_points == hstats.n_points, label


class TestIrregularWeatherParity:
    """Device plans vs both host planners on the transformed cube."""

    @pytest.mark.parametrize("name", COUNTRY_NAMES + ("seam_box",))
    def test_byte_identical_and_verified(self, iwc, name):
        request = _iwc_requests(iwc)[name]
        dev = DevicePlanner(iwc.cube).plan(request)
        assert dev is not None, f"{name} must be device-plannable"
        _assert_plans_equal(dev, Slicer(iwc.cube,
                                        fast_paths=False).extract_plan(
                                            request), f"{name} vs slow host")
        _assert_plans_equal(dev, Slicer(iwc.cube).extract_plan(request),
                            f"{name} vs fast host")
        plan, stats = dev
        assert plan.coords == {}
        verify_plan(plan, datacube=iwc.cube, stats=stats)

    def test_slicer_entry_point_routes_to_device(self, iwc):
        request = iwc.country_request("france")
        via_slicer = Slicer(iwc.cube,
                            device_planner=True).extract_plan(request)
        direct = DevicePlanner(iwc.cube).plan(request)
        _assert_plans_equal(via_slicer, direct)
        # device plans carry no coords — the entry point preserved that
        assert via_slicer[0].coords == {}
        # verify=True runs the plan checker over the device plan
        Slicer(iwc.cube, device_planner=True,
               verify=True).extract_plan(request)


class TestRegularGridParity:
    def _cube(self, n=32):
        return TensorDatacube([
            OrderedAxis("t", np.arange(3.0)),
            OrderedAxis("x", np.arange(float(n))),
            OrderedAxis("y", np.arange(float(n))),
        ])

    def test_triangle(self):
        cube = self._cube()
        tri = np.array([[4.0, 2.0], [28.0, 9.0], [15.0, 30.0]])
        req = Request([Select("t", [1.0]),
                       ConvexPolytope(("x", "y"), tri)])
        dev = DevicePlanner(cube).plan(req)
        assert dev is not None
        _assert_plans_equal(dev, Slicer(cube,
                                        fast_paths=False).extract_plan(req))
        verify_plan(dev[0], datacube=cube, stats=dev[1])

    def test_empty_intersection(self):
        cube = self._cube()
        req = Request([Box(("x", "y"), [100.0, 100.0], [120.0, 130.0])])
        dev = DevicePlanner(cube).plan(req)
        assert dev is not None
        plan, stats = dev
        hplan, hstats = Slicer(cube).extract_plan(req)
        assert plan.n_points == hplan.n_points == 0
        assert stats.n_points == hstats.n_points == 0

    def test_implicit_all_on_lead_axis(self):
        cube = self._cube()
        req = Request([Box(("x", "y"), [3.0, 4.0], [10.0, 21.0])])
        dev = DevicePlanner(cube).plan(req)
        assert dev is not None
        _assert_plans_equal(dev, Slicer(cube,
                                        fast_paths=False).extract_plan(req))


class TestTransparentFallback:
    def test_octahedral_cube_falls_back(self):
        wc = WeatherCube(n=64, n_times=1, n_levels=1)
        req = wc.country_request("france")
        assert DevicePlanner(wc.cube).plan(req) is None
        fell_back = Slicer(wc.cube, device_planner=True).extract_plan(req)
        host = Slicer(wc.cube).extract_plan(req)
        np.testing.assert_array_equal(fell_back[0].offsets,
                                      host[0].offsets)

    def test_ineligible_request_falls_back(self, iwc):
        # selects on the trailing (lat, lon) axes are outside the
        # pipeline's job shape
        req = iwc.timeseries_request(51.5, 0.0, 0.0, 43200.0)
        assert DevicePlanner(iwc.cube).plan(req) is None
        fell_back = Slicer(iwc.cube, device_planner=True).extract_plan(req)
        host = Slicer(iwc.cube).extract_plan(req)
        np.testing.assert_array_equal(fell_back[0].offsets,
                                      host[0].offsets)


class TestCompressedPlan:
    def test_round_trip_is_exact(self, iwc):
        plan, _ = Slicer(iwc.cube).extract_plan(
            iwc.country_request("france"))
        cp = compress_plan(plan)
        back = decompress_plan(cp)
        np.testing.assert_array_equal(back.offsets, plan.offsets)
        np.testing.assert_array_equal(back.run_starts, plan.run_starts)
        np.testing.assert_array_equal(back.run_lengths, plan.run_lengths)
        assert cp.n_points == plan.n_points
        assert cp.nbytes_encoded < plan.offsets.nbytes

    def test_overlapping_runs_rejected(self):
        plan = ExtractionPlan(offsets=np.arange(10, dtype=np.int64),
                              run_starts=np.array([0, 4], np.int64),
                              run_lengths=np.array([6, 6], np.int64),
                              coords={})
        with pytest.raises(ValueError):
            compress_plan(plan)

    def test_i32_gap_overflow_rejected(self):
        big = 2 ** 31 + 10
        plan = ExtractionPlan(offsets=np.array([0, big], np.int64),
                              run_starts=np.array([0, big], np.int64),
                              run_lengths=np.array([1, 1], np.int64),
                              coords={})
        with pytest.raises(OverflowError):
            compress_plan(plan)


class TestBurstGather:
    def test_matches_per_element_gather(self, iwc):
        import jax.numpy as jnp

        from repro.kernels.gather import ops as gops

        plan, _ = Slicer(iwc.cube).extract_plan(
            iwc.country_request("uk"))
        flat = jnp.asarray(np.arange(iwc.cube.n_elements, dtype=np.float32))
        exp = np.asarray(flat)[plan.offsets]
        for block in (4, 128):
            got = gops.gather_plan_runs(flat, plan.run_starts,
                                        plan.run_lengths, block=block)
            np.testing.assert_array_equal(np.asarray(got), exp)

    def test_extractor_end_to_end(self, iwc):
        import jax.numpy as jnp

        data = iwc.field_data().astype(np.float32)   # device-native dtype
        req = iwc.seam_box_request(35.0, 62.0, -25.0, 25.0)
        plan, _ = PolytopeExtractor(iwc.cube, device_planner=True).plan(req)
        values = gather(jnp.asarray(data), plan, burst=True)
        host = PolytopeExtractor(iwc.cube).extract(req, data)
        np.testing.assert_array_equal(np.asarray(values), host.values)

    def test_extractor_reads_a_device_payload(self, iwc):
        """With no path flags the extractor reads a device payload with
        ``jnp.take``: the values a host payload gives, as a device
        array."""
        import jax
        import jax.numpy as jnp

        data = iwc.field_data().astype(np.float32)
        req = iwc.country_request("uk")
        pe = PolytopeExtractor(iwc.cube)
        dev = pe.extract(req, jnp.asarray(data))
        assert isinstance(dev.values, jax.Array)
        np.testing.assert_array_equal(np.asarray(dev.values),
                                      pe.extract(req, data).values)


# ---------------------------------------------------------------------------
# slow lane: Pallas kernels (interpret mode) + hypothesis geometry
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestPallasKernelParity:
    """The persistent Pallas pipeline emits the same bytes as its jnp
    reference — full stack, through DevicePlanner, including the cyclic
    seam (uk / seam_box)."""

    @pytest.mark.parametrize("name", ("germany", "uk", "seam_box"))
    def test_pallas_equals_ref(self, iwc, name):
        request = _iwc_requests(iwc)[name]
        ref = DevicePlanner(iwc.cube).plan(request)
        dev = DevicePlanner(iwc.cube, use_pallas=True,
                            interpret=True).plan(request)
        assert ref is not None and dev is not None
        _assert_plans_equal(dev, ref, name)

    def test_pallas_burst_gather(self, iwc):
        import jax.numpy as jnp

        from repro.kernels.gather import ops as gops

        plan, _ = Slicer(iwc.cube).extract_plan(
            iwc.country_request("italy"))
        flat = jnp.asarray(np.arange(iwc.cube.n_elements, dtype=np.float32))
        got = gops.gather_plan_runs(flat, plan.run_starts,
                                    plan.run_lengths, use_pallas=True,
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(flat)[plan.offsets])


def _forall_seeds(fn, max_examples: int = 25) -> None:
    """Run a seed-indexed property under hypothesis when available
    (shrinking, example database), else over a deterministic seed
    sweep — the property executes either way."""
    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        for seed in range(max_examples):
            fn(seed)
        return
    settings(deadline=None, max_examples=max_examples)(
        given(seed=st.integers(0, 2000))(fn))()


@pytest.mark.slow
class TestHypothesisParity:
    """Property tests: random geometry, device ≡ host, bytes and stats."""

    def _check(self, cube, request):
        dev = DevicePlanner(cube).plan(request)
        assert dev is not None
        _assert_plans_equal(dev, Slicer(cube,
                                        fast_paths=False).extract_plan(
                                            request))
        verify_plan(dev[0], datacube=cube, stats=dev[1])

    def test_random_polygons(self):
        cube = TensorDatacube([OrderedAxis("a", np.arange(24.0)),
                               OrderedAxis("b", np.arange(24.0))])

        def run(seed):
            rng = np.random.default_rng(seed)
            pts = rng.uniform(0, 23, (int(rng.integers(3, 8)), 2))
            self._check(cube, Request([ConvexPolytope(("a", "b"), pts)]))

        _forall_seeds(run)

    def test_random_seam_boxes(self, iwc):
        def run(seed):
            rng = np.random.default_rng(seed)
            lat = np.sort(rng.uniform(-85, 85, 2))
            lon_lo = rng.uniform(-180, 180)
            width = rng.uniform(1.0, 400.0)    # > 360 ⇒ whole circle
            self._check(iwc.cube,
                        iwc.seam_box_request(lat[0], lat[1],
                                             lon_lo, lon_lo + width))

        _forall_seeds(run)


class TestBatchedRunsAdapter:
    def test_runs_equal_offset_lattice(self):
        import jax.numpy as jnp

        from repro.kernels.slice.ops import pack_polytopes
        from repro.core.geometry import Polytope

        tri = np.array([[0.0, 0.0], [6.0, 0.0], [0.0, 6.0]])
        sq = np.array([[2.0, 2.0], [8.5, 2.0], [8.5, 7.5], [2.0, 7.5]])
        verts, valid = pack_polytopes(
            [Polytope(("a", "b"), p) for p in (tri, sq)], v_max=4)
        ax = jnp.arange(10.0)
        offsets, n_points = batched_plan_2d(verts, valid, ax, ax, 10, 10,
                                            max_rows=10, max_cols=10)
        starts, lens, meta = batched_plan_runs_2d(verts, valid, ax, ax,
                                                  max_rows=10)
        n_runs = int(meta[0])
        starts = np.asarray(starts[:n_runs], np.int64)
        lens = np.asarray(lens[:n_runs], np.int64)
        ends = np.cumsum(lens)
        got = (np.repeat(starts, lens)
               + np.arange(int(ends[-1]) if n_runs else 0)
               - np.repeat(ends - lens, lens))
        exp = np.asarray(offsets).ravel()
        np.testing.assert_array_equal(np.sort(got),
                                      np.sort(exp[exp >= 0]))
        assert int(meta[2]) == int(np.asarray(n_points).sum())
