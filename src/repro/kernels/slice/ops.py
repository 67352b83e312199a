"""Jit'd wrapper + host adapters for the batched slice kernel."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import kernel, ref


def slice_batch(verts, valid, planes, k: int, use_pallas: bool = False,
                interpret: bool | None = None):
    if use_pallas:
        return kernel.slice_batch(verts, valid, planes, k,
                                  interpret=interpret)
    return ref.slice_batch(verts, valid, planes, k)


def pack_polytopes(polys, v_max: int | None = None):
    """Pack a BFS layer of host Polytopes into padded device arrays."""
    if not polys:
        raise ValueError("empty layer")
    d = polys[0].points.shape[1]
    v_max = v_max or max(p.n_vertices for p in polys)
    p = len(polys)
    verts = np.zeros((p, v_max, d), np.float32)
    valid = np.zeros((p, v_max), bool)
    for i, poly in enumerate(polys):
        n = min(poly.n_vertices, v_max)
        verts[i, :n] = poly.points[:n]
        valid[i, :n] = True
    return jnp.asarray(verts), jnp.asarray(valid)


def unpack_sliced(out, mask, axes, k: int):
    """Rebuild host Polytopes from kernel output (drops sliced axis k)."""
    from repro.core.geometry import Polytope, _dedupe
    from repro.core.hull import convex_hull_prune

    out = np.asarray(out, np.float64)
    mask = np.asarray(mask)
    rest = tuple(a for j, a in enumerate(axes) if j != k)
    keep_cols = [j for j in range(out.shape[2]) if j != k]
    polys = []
    for i in range(out.shape[0]):
        pts = out[i][mask[i]][:, keep_cols]
        if len(pts) == 0:
            polys.append(None)
            continue
        pts = convex_hull_prune(_dedupe(pts))
        polys.append(Polytope(rest, pts))
    return polys
