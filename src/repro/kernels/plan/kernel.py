"""Pallas TPU kernel: persistent device-resident BFS planning pipeline.

One ``pallas_call`` executes the whole Algorithm-1 trailing stage: the
grid walks the jobs (leading-path × polytope pairs) and every step runs
slice → column ranges → run emission for its job, appending compacted
``(run_start, run_length)`` pairs directly into the plan buffer that
``kernels/gather`` scalar-prefetches.  Nothing returns to the host
between layers — the BFS frontier (candidate rows and their column
ranges) lives in registers/VMEM for exactly one grid step.

Persistence idiom (same as ``gather_rows_bag``): TPU grids execute
sequentially, so the outputs are *revisited* blocks — the run buffers
and a 3-word ``meta`` carry (``[cursor, n_rows, n_points]``) persist
across steps.  Each step compacts its local slots with an exclusive
prefix sum over the valid-run mask and scatters them at the carried
cursor; invalid slots scatter out of bounds and drop.  Because the
cursor advances in job order and the local scan preserves
(row, segment) order, the emitted buffer is byte-identical to the jnp
oracle's global compaction (``ref.plan_runs_2d``).

The per-job math is literally ``ref.row_slots_2d`` called on the
(1, V, 2) job block — the oracle and the kernel cannot drift.  CPU CI
runs interpret mode; the gathers (``sv0[rows]``) and the (M,)-buffer
read-modify-write are VMEM-resident on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

from .ref import row_slots_2d


def _plan_kernel(verts_ref, valid_ref, base_ref, sv0_ref, rowoff0_ref,
                 sv1_ref, scalars_ref, starts_ref, lens_ref, meta_ref, *,
                 n0: int, n1: int, max_rows: int, cyclic: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        starts_ref[...] = jnp.zeros_like(starts_ref)
        lens_ref[...] = jnp.zeros_like(lens_ref)
        meta_ref[...] = jnp.zeros_like(meta_ref)

    starts, lengths, ok, n_rows, n_points = row_slots_2d(
        verts_ref[...], valid_ref[...], base_ref[...], sv0_ref[...],
        rowoff0_ref[...], sv1_ref[...], scalars_ref[...],
        n0=n0, n1=n1, max_rows=max_rows, cyclic=cyclic)

    # In-kernel compaction: exclusive prefix sum over the valid mask
    # gives each live slot its position after the carried cursor.
    s = 2 * max_rows
    ok_f = ok.reshape(s)
    tgt = jnp.cumsum(ok_f, dtype=jnp.int32) - ok_f
    meta = meta_ref[...]
    cursor = meta[0]
    m = starts_ref.shape[0]
    # dead slots scatter to index m — out of bounds, dropped
    pos = jnp.where(ok_f, cursor + tgt, m)
    starts_ref[...] = starts_ref[...].at[pos].set(
        jnp.where(ok_f, starts.reshape(s), 0))
    lens_ref[...] = lens_ref[...].at[pos].set(
        jnp.where(ok_f, lengths.reshape(s), 0))
    n_runs = jnp.sum(ok_f, dtype=jnp.int32)
    meta_ref[...] = meta + jnp.stack([n_runs, n_rows, n_points])


def plan_runs_2d(verts, valid, base, sv0, rowoff0, sv1, scalars, *,
                 n0: int, n1: int, max_rows: int, cyclic: bool,
                 interpret: bool | None = None):
    """Device pipeline with the ``ref.plan_runs_2d`` contract:
    returns (run_starts (M,) i32, run_lengths (M,) i32, meta (3,) i32)
    with M = J · max_rows · 2, byte-identical to the oracle."""
    return _plan_runs_2d(verts, valid, base, sv0, rowoff0, sv1, scalars,
                         n0=n0, n1=n1, max_rows=max_rows, cyclic=cyclic,
                         interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "n0", "n1", "max_rows", "cyclic", "interpret"))
def _plan_runs_2d(verts, valid, base, sv0, rowoff0, sv1, scalars, *,
                  n0: int, n1: int, max_rows: int, cyclic: bool,
                  interpret: bool):
    j, v, _ = verts.shape
    m = j * max_rows * 2
    if j == 0:
        zero = jnp.zeros((0,), jnp.int32)
        return zero, zero, jnp.zeros((3,), jnp.int32)

    return pl.pallas_call(
        functools.partial(_plan_kernel, n0=n0, n1=n1, max_rows=max_rows,
                          cyclic=cyclic),
        grid=(j,),
        in_specs=[
            pl.BlockSpec((1, v, 2), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, v), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (i,)),
            pl.BlockSpec((n0,), lambda i: (0,)),
            pl.BlockSpec((n0,), lambda i: (0,)),
            pl.BlockSpec((n1,), lambda i: (0,)),
            pl.BlockSpec((4,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((m,), lambda i: (0,)),
            pl.BlockSpec((m,), lambda i: (0,)),
            pl.BlockSpec((3,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m,), jnp.int32),
            jax.ShapeDtypeStruct((m,), jnp.int32),
            jax.ShapeDtypeStruct((3,), jnp.int32),
        ],
        interpret=interpret,
        name="polytope_plan_runs",
    )(verts, valid, base, sv0, rowoff0, sv1, scalars)
