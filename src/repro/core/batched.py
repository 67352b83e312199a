"""On-device batched extraction for 2-D polytopes on regular grids.

The host slicer (Algorithm 1) plans one request at a time in float64.
Training pipelines want the opposite trade: *many congruent small
requests per step* (batched country crops, per-sample regions of
interest) with static shapes, planned on the accelerator itself.

This module runs one BFS layer of Algorithm 1 as a batched device
computation: for a batch of convex 2-D polytopes over regular ordered
axes,

  1. per-polytope extents on axis 0 → index ranges (``searchsorted``),
  2. slice every (polytope × row) pair at once — the
     ``repro.kernels.slice`` Pallas kernel (or its jnp oracle),
  3. per-row 1-D extents on axis 1 → index ranges,
  4. emit a padded (P, R, C) offset lattice + validity mask — the
     batched extraction plan consumed by ``gather_rows``.

Shapes are static: R = max rows, C = max columns per row; masked slots
are -1 (exactly the padding convention of the gather/bag kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels._casting import checked_cast_i32, ensure_i32_addressable
from repro.kernels.slice import ref as slice_ref


@functools.partial(jax.jit, static_argnames=("max_rows", "max_cols",
                                             "n0", "n1"))
def batched_plan_2d(verts: jax.Array, valid: jax.Array,
                    axis0: jax.Array, axis1: jax.Array,
                    n0: int, n1: int,
                    max_rows: int, max_cols: int
                    ) -> tuple[jax.Array, jax.Array]:
    """Plan a batch of convex 2-D polytopes on a regular (n0 × n1) grid.

    verts  — (P, V, 2) float32 polytope vertices (axis0, axis1 coords)
    valid  — (P, V) bool vertex mask
    axis0  — (n0,) sorted axis-0 index values
    axis1  — (n1,) sorted axis-1 index values

    Returns (offsets (P, max_rows, max_cols) int32 flat offsets with -1
    padding, n_points (P,)).
    """
    p, v, _ = verts.shape
    big = jnp.asarray(jnp.inf, verts.dtype)

    c0 = jnp.where(valid, verts[:, :, 0], big)
    lo0 = jnp.min(c0, axis=1)
    hi0 = jnp.max(jnp.where(valid, verts[:, :, 0], -big), axis=1)

    # rows intersecting each polytope
    start = jnp.searchsorted(axis0, lo0 - 1e-6, side="left")  # (P,)
    row_ids = start[:, None] + jnp.arange(max_rows)[None, :]  # (P, R)
    row_ok = (row_ids < n0) & \
        (axis0[jnp.clip(row_ids, 0, n0 - 1)] <= hi0[:, None] + 1e-6)
    row_vals = axis0[jnp.clip(row_ids, 0, n0 - 1)]

    # slice every (polytope, row) pair via the shared slicing core —
    # extents of the remaining coordinate only, so the (V × V) candidate
    # lattice never materializes (same math as the old slice_batch +
    # masked min/max, fused).
    scale = jnp.maximum(1.0, jnp.max(jnp.abs(verts[:, :, 0]), axis=1))
    lo1, hi1, hit2 = slice_ref.slice_minor_extents(
        verts[:, None, :, 0], verts[:, None, :, 1], valid[:, None, :],
        row_vals, (slice_ref.PLANE_TOL * scale)[:, None])
    lo1 = lo1.reshape(p * max_rows)
    hi1 = hi1.reshape(p * max_rows)
    hit = hit2.reshape(p * max_rows) & row_ok.reshape(-1)

    c_start = jnp.searchsorted(axis1, lo1 - 1e-6, side="left")
    col_ids = c_start[:, None] + jnp.arange(max_cols)[None, :]
    col_ok = (col_ids < n1) & \
        (axis1[jnp.clip(col_ids, 0, n1 - 1)] <= hi1[:, None] + 1e-6) & \
        hit[:, None]

    # n0/n1 are static, so this guard runs at trace time: a grid whose
    # flat offsets overflow int32 fails loudly instead of truncating.
    ensure_i32_addressable(n0 * n1, what="batched_plan_2d grid")
    offsets = checked_cast_i32(jnp.where(
        col_ok,
        row_ids.reshape(-1)[:, None] * n1 + jnp.clip(col_ids, 0, n1 - 1),
        -1), what="batched_plan_2d offsets", allow_negative_one=True)
    offsets = offsets.reshape(p, max_rows, max_cols)
    n_points = jnp.sum(offsets >= 0, axis=(1, 2))
    return offsets, n_points


def batched_plan_runs_2d(verts: jax.Array, valid: jax.Array,
                         axis0: jax.Array, axis1: jax.Array,
                         max_rows: int, use_pallas: bool = False,
                         interpret: bool | None = None):
    """Run-pair form of :func:`batched_plan_2d`: the compressed plan
    representation, straight from the fused pipeline.

    Same geometry/tolerance conventions as the offset-lattice path (the
    f32 ``1e-6`` regime), but emits compacted ``(run_start, run_length)``
    pairs instead of the padded (P, R, C) lattice — rows become single
    entries regardless of width, and the output feeds
    ``kernels.gather.gather_plan_runs`` burst DMA directly.  Returns
    (run_starts (M,) i32, run_lengths (M,) i32, meta (3,) i32 =
    [n_runs, n_rows, n_points]) flat across the batch in
    (polytope, row) order.
    """
    from repro.kernels.plan import ops as plan_ops

    p = verts.shape[0]
    n0, n1 = int(axis0.shape[0]), int(axis1.shape[0])
    ensure_i32_addressable(n0 * n1, what="batched_plan_runs_2d grid")
    # scalars layout: [eps0, eps1, plane_tol_rel, period]
    scalars = jnp.asarray([1e-6, 1e-6, slice_ref.PLANE_TOL, 0.0],
                          verts.dtype)
    rowoff = jnp.arange(0, n0 * n1, n1, dtype=jnp.int32)
    return plan_ops.plan_runs_2d(
        verts, valid, jnp.zeros(p, jnp.int32), axis0, rowoff, axis1,
        scalars, n0=n0, n1=n1, max_rows=max_rows, cyclic=False,
        use_pallas=use_pallas, interpret=interpret)


def batched_extract_2d(flat_data: jax.Array, verts, valid, axis0, axis1,
                       max_rows: int, max_cols: int):
    """Plan + gather in one jit: (P, max_rows·max_cols) values with 0 at
    padded slots, plus the offset lattice."""
    n0, n1 = int(axis0.shape[0]), int(axis1.shape[0])
    offsets, n_points = batched_plan_2d(verts, valid, axis0, axis1,
                                        n0, n1, max_rows, max_cols)
    flat_off = offsets.reshape(offsets.shape[0], -1)
    vals = jnp.where(flat_off >= 0,
                     jnp.take(flat_data, jnp.maximum(flat_off, 0)),
                     0)
    return vals, offsets, n_points
