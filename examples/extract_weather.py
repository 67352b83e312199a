"""Domain-interface tour (paper §4): every Table-1 request type against
a synthetic weather cube, printing the index-tree → plan → gather flow —
plus the irregular-datacube scenario (DESIGN.md §2.5): merged date/time,
mapped Gaussian latitudes, and a cross-seam UK crop on a cyclic
longitude, served through the plan cache with a seam-shifted cache hit.

  PYTHONPATH=src python examples/extract_weather.py
"""

import numpy as np

from repro.core import (BoundingBoxExtractor, Box, PolytopeExtractor,
                        Request, Select, Slicer, TraditionalExtractor)
from repro.dataplane.weather import (COUNTRIES, IrregularWeatherCube,
                                     WeatherCube, paris_newyork_path)
from repro.serve.sharded import ShardedExtractionService


def irregular_scenarios(iwc: IrregularWeatherCube) -> dict:
    return {
        "uk_cross_seam_crop": iwc.country_request("uk"),
        "seam_box_-20_20": iwc.seam_box_request(40.0, 60.0, -20.0, 20.0),
        "timeseries_across_midnight": iwc.timeseries_request(
            51.5, 0.0, 43200.0, 86400.0 + 43200.0),
    }


def run_irregular() -> None:
    print("— irregular datacube (merged datetime · mapped Gaussian lat · "
          "cyclic lon) —")
    iwc = IrregularWeatherCube(n_lat=160, n_lon=320)
    data = iwc.field_data(seed=3)
    svc = ShardedExtractionService(iwc.cube, shards=1)
    bb = BoundingBoxExtractor(iwc.cube)
    tr = TraditionalExtractor(iwc.cube, field_axes=("lat", "lon"))
    print(f"cube: {iwc.cube.n_elements:,} elements, logical axes "
          f"{iwc.cube.axis_names}, periods {iwc.cube.axis_periods()}\n")

    for name, req in irregular_scenarios(iwc).items():
        res = svc.extract(req, data)
        plan, stats = res.plan, res.stats
        nbytes = max(plan.nbytes, 1)
        plan_ms = stats.total_time_s * 1e3 if stats else 0.0
        print(f"{name}: {plan.n_points} points, {plan.nbytes:,} B in "
              f"{plan.n_runs} runs, reduction "
              f"{tr.nbytes(req) / nbytes:,.0f}× vs whole-field and "
              f"{bb.plan(req).nbytes / nbytes:,.1f}× "
              f"vs bounding box, planned in {plan_ms:.1f} ms, "
              f"values mean {float(np.mean(res.values)):.2f}")

    # Seam-shifted re-request: same geometry expressed +360° away must
    # hit the plan cache (canonicalization modulo the period).
    shifted = Request([Select("datetime", [0.0]), Select("level", [0.0]),
                       Box(("lat", "lon"), [40.0, 340.0], [60.0, 380.0])])
    base = iwc.seam_box_request(40.0, 60.0, -20.0, 20.0)
    svc.extract(base)
    hit = svc.extract(shifted)
    print(f"seam-shifted box (+360°) served from cache: {hit.cached}")


def main() -> None:
    wc = WeatherCube(n=96, n_times=8, n_levels=10)
    data = wc.field_data(seed=7)
    pe = PolytopeExtractor(wc.cube)
    print(f"cube: {wc.cube.n_elements:,} elements "
          f"({wc.cube.nbytes / 2**20:.0f} MiB), octahedral O{wc.n}, "
          f"{wc.n_times} times × {wc.n_levels} levels\n")

    demos = {
        "Italy, t=2, level=0": wc.country_request("italy",
                                                  time=2 * 3600.0),
        "London time-series (all 8 steps)": wc.timeseries_request(
            51.5, 0.0, 0.0, 7 * 3600.0),
        "Rome vertical profile (10 levels)": wc.profile_request(
            41.9, 12.5),
        "Paris→NY flight tube": wc.flight_path_request(
            paris_newyork_path(wc), width=2.0),
    }

    for name, req in demos.items():
        root, stats = Slicer(wc.cube).build_index_tree(req)
        res = pe.extract(req, data)
        plan = res.plan
        print(f"{name}")
        print(f"  index tree: depth {root.depth()}, "
              f"{plan.n_points} leaf points, "
              f"{stats.n_slices} slices "
              f"{dict(sorted(stats.n_slices_by_dim.items()))}")
        print(f"  plan: {plan.nbytes:,} B in {plan.n_runs} contiguous "
              f"runs (largest {int(plan.run_lengths.max()) if plan.n_runs else 0} elems)")
        print(f"  values: mean {float(np.mean(res.values)):.2f}, "
              f"extracted in {stats.total_time_s * 1e3:.1f} ms\n")

    run_irregular()


if __name__ == "__main__":
    main()
