"""Extraction executors + the baselines the paper compares against.

* :class:`PolytopeExtractor` — the paper's technique: plan with the
  slicer, then read only the planned bytes.  On device the read is a
  ``jnp.take``; :func:`gather` also reads coalesced runs as bursts, or
  through the Pallas scalar-prefetch DMA kernel (``repro.kernels.gather``).
* :class:`BoundingBoxExtractor` — the "state of practice" baseline: the
  tensor-product box of the per-axis extents.
* :class:`TraditionalExtractor` — whole-field reads (paper Table 1
  column 1): everything under the selected leading-axis indices.

All three report bytes-read, so Table 1's reduction factors are computed
like-for-like.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import numpy as np

from .datacube import Datacube, OctahedralGridDatacube, TensorDatacube
from .index_tree import ExtractionPlan, coalesce_runs
from .shapes import Request
from .slicer import Slicer, SliceStats


@dataclass
class ExtractResult:
    values: np.ndarray | None
    plan: ExtractionPlan
    stats: SliceStats | None = None

    @property
    def nbytes(self) -> int:
        return self.plan.nbytes


class PolytopeExtractor:
    """Plan on host (float64 geometry) or on device (the fused
    ``device_planner`` pipeline), gather on host or device."""

    def __init__(self, datacube: Datacube, verify: bool = False,
                 device_planner: bool = False):
        self.datacube = datacube
        self.slicer = Slicer(datacube, verify=verify,
                             device_planner=device_planner)

    def plan(self, request: Request) -> tuple[ExtractionPlan, SliceStats]:
        return self.slicer.extract_plan(request)

    def extract(self, request: Request,
                flat_data: Any | None = None) -> ExtractResult:
        plan, stats = self.plan(request)
        values = None
        if flat_data is not None:
            values = gather(flat_data, plan)
        return ExtractResult(values=values, plan=plan, stats=stats)


def gather(flat_data: Any, plan: ExtractionPlan,
           use_kernel: bool = False, burst: bool = False) -> Any:
    """Read exactly the planned elements.

    A numpy payload is indexed on the host; a device payload is read on
    its device.  ``burst=True`` reads the aligned windows that hold the
    plan's coalesced runs (run-length-aware DMA) instead of one load per
    element, and ``use_kernel`` runs that read as the Pallas kernel;
    results are identical — runs tile the offsets exactly.
    """
    if isinstance(flat_data, np.ndarray):
        return flat_data[plan.offsets]
    if burst or use_kernel:
        from repro.kernels.gather import ops as gops

        return gops.gather_plan_runs(flat_data, plan.run_starts,
                                     plan.run_lengths,
                                     use_pallas=use_kernel)
    import jax.numpy as jnp

    from repro.kernels import checked_cast_i32

    offs = checked_cast_i32(plan.offsets, what="plan offsets",
                            n_elements=flat_data.shape[0])
    return jnp.take(flat_data, offs, axis=0)


class BoundingBoxExtractor:
    """Tensor-product box of the request's per-axis extents."""

    def __init__(self, datacube: Datacube):
        self.datacube = datacube

    def plan(self, request: Request) -> ExtractionPlan:
        polys = request.polytopes()
        sels = request.selects()
        # per-axis extents across all polytopes (the box around the union)
        ext: dict[str, list[float]] = {}
        for p in polys:
            for ax in p.axes:
                lo, hi = p.extents(ax)
                cur = ext.setdefault(ax, [lo, hi])
                cur[0] = min(cur[0], lo)
                cur[1] = max(cur[1], hi)

        # Walk the cube like the slicer would, but with box shapes only.
        from .shapes import Box, Select, Span

        shapes: list = [Span(ax, lo, hi) for ax, (lo, hi) in ext.items()]
        shapes += [Select(s.axis, s.values) for s in sels]
        box_request = Request(shapes)
        plan, _ = Slicer(self.datacube).extract_plan(box_request)
        return plan

    def extract(self, request: Request,
                flat_data: Any | None = None) -> ExtractResult:
        plan = self.plan(request)
        values = None
        if flat_data is not None:
            values = gather(flat_data, plan)
        return ExtractResult(values=values, plan=plan)


class TraditionalExtractor:
    """Whole-field baseline: read the complete subcube under the selected
    leading axes (what ECMWF MARS / DICOM effectively do today)."""

    def __init__(self, datacube: Datacube,
                 field_axes: tuple[str, ...] = ("lat", "lon")):
        self.datacube = datacube
        self.field_axes = field_axes

    def nbytes(self, request: Request) -> int:
        """Bytes = (#selected leading-index combinations) × field size."""
        dc = self.datacube
        polys = request.polytopes()
        sels = {s.axis: s for s in request.selects()}
        n_lead = 1
        if isinstance(dc, OctahedralGridDatacube):
            lead_names = dc._lead_names
            field_elems = dc.points_per_field
        elif hasattr(dc, "axis_names"):
            # regular or transformed cube: fields are the trailing
            # (logical) field axes, everything else is a lead axis
            lead_names = tuple(n for n in dc.axis_names
                               if n not in self.field_axes)
            field_elems = int(np.prod([len(dc.axis(n, {})) for n in
                                       self.field_axes]))
        else:
            return dc.nbytes
        for name in lead_names:
            ax = dc.axis(name, {})
            if name in sels:
                n_lead *= len(sels[name].values)
                continue
            on_axis = [p for p in polys if name in p.axes]
            if not on_axis:
                n_lead *= len(ax)
                continue
            lo = min(p.extents(name)[0] for p in on_axis)
            hi = max(p.extents(name)[1] for p in on_axis)
            pos, _ = ax.indices_in_range(lo, hi)
            n_lead *= max(1, len(pos))
        return n_lead * field_elems * dc.dtype.itemsize
