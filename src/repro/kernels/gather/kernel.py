"""Pallas TPU kernels for exact-byte extraction gathers.

This is the paper's contribution mapped onto the TPU memory hierarchy
(DESIGN.md §3): the Polytope planner has already computed *which* rows
are needed; these kernels DMA exactly those rows from HBM using
scalar-prefetched indices (`PrefetchScalarGridSpec`), never touching the
rest of the datacube — the bounding-box baseline would stream the whole
enclosing block.

Three entry points:

* ``gather_rows``     — (N, D) table × (M,) indices → (M, D).  The table
  stays in HBM; each grid step starts ``ROWS_PER_STEP`` row DMAs
  straight into its (8, D) output tile, all in flight before the first
  wait.  Mosaic needs the output block's last two dimensions to be
  (8k, 128k) or whole, so a step writes a whole tile, not one row.
* ``gather_runs``     — the burst gather: ``block``-element windows of a
  flat payload at aligned window indices, i.e. ``gather_rows`` over the
  payload viewed as (N / block, block).  The view is taken inside the
  same jit as the kernel, where it is a bitcast of the resident payload
  and not a copy.  A 1-D HBM slice at an arbitrary element offset is
  refused by Mosaic (slices must align to the 1-D tiling of 1024), which
  is why windows are aligned rows.
* ``gather_rows_bag`` — fused EmbeddingBag: (B, L) padded index bags →
  (B, D) segment-sum, accumulating over the L grid axis in the revisited
  output block (TPU grids execute sequentially, so output revisiting is
  the idiomatic reduction).

D is the datacube's minor storage axis, so each row DMA is one
contiguous burst — the HBM analogue of the paper's coalesced byte-run
reads (``ExtractionPlan.run_starts``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels._casting import checked_cast_i32

# Rows per grid step: one (8, 128) f32 output tile.
ROWS_PER_STEP = 8
# Scalar-prefetched indices live in SMEM, 1 MiB on a v5e chip (2**18
# int32 indices do not fit): longer index vectors go in several calls.
MAX_ROWS_PER_CALL = 1 << 17


def _rows_kernel(idx_ref, table_ref, out_ref, sems):
    i = pl.program_id(0)
    copies = [
        pltpu.make_async_copy(
            table_ref.at[pl.ds(idx_ref[i * ROWS_PER_STEP + k], 1)],
            out_ref.at[pl.ds(k, 1)], sems.at[k])
        for k in range(ROWS_PER_STEP)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def _rows_call(table: jax.Array, indices: jax.Array,
               interpret: bool) -> jax.Array:
    d = table.shape[1]
    m = indices.shape[0]          # a multiple of ROWS_PER_STEP
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // ROWS_PER_STEP,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((ROWS_PER_STEP, d), lambda i, idx: (i, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((ROWS_PER_STEP,))],
    )
    return pl.pallas_call(
        _rows_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), table.dtype),
        interpret=interpret,
        name="polytope_gather_rows",
    )(indices, table)


def _in_calls(call, indices, n_rows: int, what: str, width: int,
              dtype) -> jax.Array:
    """Validate and cast row indices, pad them to whole grid steps
    (padding reads row 0 and is sliced off), and gather them in calls of
    at most ``MAX_ROWS_PER_CALL`` rows; returns (M, width)."""
    idx = checked_cast_i32(np.asarray(indices), what=what,
                           n_elements=n_rows)
    m = idx.shape[0]
    if m == 0:
        return jnp.zeros((0, width), dtype)
    idx = np.pad(idx, (0, -m % ROWS_PER_STEP))
    outs = [call(jnp.asarray(idx[s:s + MAX_ROWS_PER_CALL]))
            for s in range(0, idx.shape[0], MAX_ROWS_PER_CALL)]
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    return out[:m]


def gather_rows(table: jax.Array, indices: jax.Array,
                interpret: bool | None = None) -> jax.Array:
    """Gather ``table[indices]`` reading only the planned rows.

    table   — (N, D)
    indices — (M,) integer, each in [0, N); validated host-side and cast
    to the int32 the scalar-prefetch index map requires (offsets past
    2³¹ raise instead of truncating).
    """
    interpret = resolve_interpret(interpret)
    return _in_calls(
        lambda idx: _gather_rows(table, idx, interpret=interpret),
        indices, table.shape[0], "gather_rows indices", table.shape[1],
        table.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_rows(table: jax.Array, indices: jax.Array,
                 interpret: bool) -> jax.Array:
    return _rows_call(table, indices, interpret)


def gather_runs(flat: jax.Array, chunk_rows: jax.Array, block: int,
                interpret: bool | None = None) -> jax.Array:
    """Burst-gather the ``block``-element windows
    ``flat[r·block : (r+1)·block]`` for each ``r`` in ``chunk_rows``.

    flat       — (n,) payload with n a multiple of ``block``: a payload
                 that is not is padded once where it is loaded, never
                 per call
    chunk_rows — (C,) window indices in [0, n / block)
    Returns (C, block); callers compact the planned elements out of it
    (``ops.gather_plan_runs``).
    """
    n = flat.shape[0]
    if n % block:
        raise ValueError(
            f"burst gather needs a payload length divisible by {block}, "
            f"got {n}; pad the payload once when it is placed")
    interpret = resolve_interpret(interpret)
    return _in_calls(
        lambda idx: _gather_runs(flat, idx, block=block,
                                 interpret=interpret),
        chunk_rows, n // block, "gather_runs rows", block, flat.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _gather_runs(flat: jax.Array, chunk_rows: jax.Array, block: int,
                 interpret: bool) -> jax.Array:
    return _rows_call(flat.reshape(-1, block), chunk_rows, interpret)


def _bag_kernel(idx_ref, table_ref, out_ref):
    b = pl.program_id(0)
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Padded slots carry index -1 → contribute zero.
    valid = idx_ref[b, l] >= 0
    row = table_ref[...]
    out_ref[...] += jnp.where(valid, row, jnp.zeros_like(row))


def gather_rows_bag(table: jax.Array, bags: jax.Array,
                    interpret: bool | None = None) -> jax.Array:
    """Fused EmbeddingBag(sum): out[b] = Σ_l table[bags[b, l]].

    table — (N, D);  bags — (B, L) integer, padded with -1 (the only
    negative value allowed; validated host-side before the int32 cast).
    """
    bags32 = checked_cast_i32(bags, what="gather_rows_bag bags",
                              n_elements=table.shape[0],
                              allow_negative_one=True)
    return _gather_rows_bag(table, bags32,
                            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_rows_bag(table: jax.Array, bags32: jax.Array,
                     interpret: bool) -> jax.Array:
    n, d = table.shape
    b, l = bags32.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, l),
        in_specs=[
            # clamp -1 padding to row 0; the kernel masks it out.
            pl.BlockSpec((1, d),
                         lambda i, j, idx: (jnp.maximum(idx[i, j], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i, j, idx: (i, 0)),
    )
    return pl.pallas_call(
        _bag_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        interpret=interpret,
        name="polytope_gather_bag",
    )(bags32, table)
