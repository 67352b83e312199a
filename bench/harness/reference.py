"""The plain reference: which flat offsets a request covers, and the
payload value at any offset.

It imports nothing of the program.  The grid and the axis values come
from the configuration file alone, and the request semantics are the
service's stated ones, written out directly:

* a ``select`` on an ordered axis snaps to the nearest index (a tie goes
  to the lower value); on longitude the distance is cyclic;
* a ``span`` covers every index inside ``[lo, hi]``, widened by 1e-9 of
  the axis' largest magnitude so that bounds lying on an index value are
  kept;
* a ``box`` or ``polygon`` covers, on each latitude row inside its
  latitude extent, the longitudes inside its cross-section at that row
  (a scanline fill), with the same widening, modulo 360 degrees.

Offsets index the flat storage of the configuration: leading axes in
order, then the horizontal field as concatenated latitude rows.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

REL_TOL = 1e-9


def axis_values(spec: dict) -> np.ndarray:
    """Float64 values of a leading axis: ``start + step * i`` for
    ``count`` indices, or the row-major outer sum of merged parts."""
    if "merge" in spec:
        major, minor = (axis_values(p) for p in spec["merge"])
        return (major[:, None] + minor[None, :]).ravel()
    return spec["start"] + spec["step"] * np.arange(spec["count"],
                                                    dtype=np.float64)


def gaussian_latitudes(n_lat: int) -> np.ndarray:
    """Gauss-Legendre latitudes, north to south, in degrees."""
    nodes, _ = np.polynomial.legendre.leggauss(n_lat)
    return np.degrees(np.arcsin(nodes))[::-1].copy()


@dataclass
class Grid:
    """A horizontal field of latitude rows, each a full circle of
    equally spaced longitudes starting at 0, stored row after row."""

    lats: np.ndarray         # (rows,) storage order
    counts: np.ndarray       # (rows,) points per row
    row_offsets: np.ndarray  # (rows + 1,)

    @classmethod
    def from_config(cls, grid: dict) -> "Grid":
        if grid["kind"] == "octahedral":
            n = int(grid["n"])
            north = 20 + 4 * np.arange(n)
            counts = np.concatenate([north, north[::-1]])
            j = np.arange(2 * n)
            lats = 90.0 - np.degrees(np.pi * (j + 0.5) / (2 * n))
        elif grid["kind"] == "gaussian_regular":
            lats = gaussian_latitudes(int(grid["n_lat"]))
            counts = np.full(len(lats), int(grid["n_lon"]))
        else:
            raise ValueError(f"unknown grid kind {grid['kind']!r}")
        offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        return cls(lats=lats, counts=counts.astype(np.int64),
                   row_offsets=offsets)

    @property
    def points(self) -> int:
        return int(self.row_offsets[-1])

    def lons(self, row: int) -> np.ndarray:
        cnt = int(self.counts[row])
        return 360.0 * np.arange(cnt) / cnt


def _nearest(sorted_vals: np.ndarray, v: float) -> int:
    """Index into ascending ``sorted_vals`` of the value nearest ``v``;
    a tie goes to the lower value."""
    i = int(np.clip(np.searchsorted(sorted_vals, v), 1,
                    len(sorted_vals) - 1))
    return i if abs(sorted_vals[i] - v) < abs(sorted_vals[i - 1] - v) \
        else i - 1


def _in_range(vals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    eps = REL_TOL * max(abs(vals.min()), abs(vals.max()), 1.0)
    return (vals >= lo - eps) & (vals <= hi + eps)


def _cyclic_in_range(lons: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Mask of the row's longitudes inside the unwrapped ``[lo, hi]``,
    taken modulo 360."""
    if hi - lo >= 360.0:
        return np.ones(len(lons), bool)
    eps = REL_TOL * max(abs(lons[0]), abs(lons[-1]), 1.0)
    mask = np.zeros(len(lons), bool)
    for k in range(math.floor((lo - lons[-1]) / 360.0),
                   math.ceil((hi - lons[0]) / 360.0) + 1):
        shift = k * 360.0
        mask |= (lons >= (lo - shift) - eps) & (lons <= (hi - shift) + eps)
    return mask


def _cyclic_nearest(lons: np.ndarray, lon: float) -> int:
    v = lon % 360.0
    j = _nearest(lons, v)
    if abs(360.0 - v) < abs(lons[j] - v):
        return 0
    return j


def _scanline(ring: np.ndarray, lat: float) -> list[tuple[float, float]]:
    """Longitude intervals of a simple polygon's cross-section at
    ``lat``: crossings of the ring's edges, paired left to right."""
    y0, x0 = ring[:, 0], ring[:, 1]
    y1, x1 = np.roll(y0, -1), np.roll(x0, -1)
    hit = ((y0 <= lat) & (lat < y1)) | ((y1 <= lat) & (lat < y0))
    xs = np.sort(x0[hit] + (lat - y0[hit]) * (x1[hit] - x0[hit])
                 / (y1[hit] - y0[hit]))
    return [(float(xs[i]), float(xs[i + 1]))
            for i in range(0, len(xs) - 1, 2)]


class Reference:
    """Offsets and values of a configuration, from its file alone."""

    def __init__(self, config: dict):
        self.grid = Grid.from_config(config["grid"])
        self.lead_names = [a["name"] for a in config["lead_axes"]]
        self.lead_values = [axis_values(a) for a in config["lead_axes"]]
        sizes = [len(v) for v in self.lead_values]
        strides = []
        acc = self.grid.points
        for size in reversed(sizes):
            strides.append(acc)
            acc *= size
        self.lead_strides = strides[::-1]
        self.n_elements = acc
        self._lat_order = np.argsort(self.grid.lats, kind="stable")
        self._lat_sorted = self.grid.lats[self._lat_order]

    # -- leading axes ------------------------------------------------------
    def _lead_positions(self, name: str, sel) -> np.ndarray:
        vals = self.lead_values[self.lead_names.index(name)]
        if sel is None:
            return np.arange(len(vals))
        if sel[0] == "select":
            return np.array([_nearest(vals, float(sel[1]))])
        if sel[0] == "span":
            return np.flatnonzero(_in_range(vals, float(sel[1]),
                                            float(sel[2])))
        raise ValueError(f"unknown selection {sel!r}")

    def _lead_bases(self, lead: dict) -> np.ndarray:
        unknown = set(lead) - set(self.lead_names)
        if unknown:
            raise ValueError(f"no leading axis named {sorted(unknown)}")
        bases = np.zeros(1, np.int64)
        for name, stride in zip(self.lead_names, self.lead_strides):
            pos = self._lead_positions(name, lead.get(name))
            bases = (bases[:, None] + pos[None, :] * stride).ravel()
        return bases

    # -- horizontal field --------------------------------------------------
    def _field_offsets(self, horiz: list) -> np.ndarray:
        g = self.grid
        kind = horiz[0]
        if kind == "point":
            lat, lon = float(horiz[1]), float(horiz[2])
            row = int(self._lat_order[_nearest(self._lat_sorted, lat)])
            col = _cyclic_nearest(g.lons(row), lon)
            return np.array([g.row_offsets[row] + col], np.int64)
        if kind == "box":
            (lat_lo, lon_lo), (lat_hi, lon_hi) = horiz[1], horiz[2]
            rows = np.flatnonzero(_in_range(g.lats, lat_lo, lat_hi))
            parts = [g.row_offsets[r] + np.flatnonzero(
                _cyclic_in_range(g.lons(r), lon_lo, lon_hi)) for r in rows]
        elif kind == "polygon":
            ring = np.asarray(horiz[1], np.float64)
            rows = np.flatnonzero(_in_range(g.lats, ring[:, 0].min(),
                                            ring[:, 0].max()))
            parts = []
            for r in rows:
                lons = g.lons(r)
                mask = np.zeros(len(lons), bool)
                for lo, hi in _scanline(ring, float(g.lats[r])):
                    mask |= _cyclic_in_range(lons, lo, hi)
                parts.append(g.row_offsets[r] + np.flatnonzero(mask))
        else:
            raise ValueError(f"unknown horizontal shape {kind!r}")
        if not parts:
            return np.empty(0, np.int64)
        return np.concatenate(parts).astype(np.int64)

    def offsets(self, desc: dict) -> np.ndarray:
        """Sorted flat offsets the request ``desc`` covers."""
        bases = self._lead_bases(desc.get("lead", {}))
        field = self._field_offsets(desc["horiz"])
        return np.unique((bases[:, None] + field[None, :]).ravel())
