"""Public jit'd entry points for extraction gathers.

``use_pallas`` selects the Pallas kernel, which runs in interpret mode
on the CPU backend and compiles on a TPU (``repro.kernels
.resolve_interpret``).  The default dispatch keeps the pure-jnp path so
the whole framework works identically with or without the kernels —
kernels are an optimisation layer, not a dependency.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels._casting import checked_cast_i32, ensure_i32_addressable

from . import kernel, ref

# Burst window width in elements: the payload is read as aligned
# windows of this many elements, one DMA per distinct window a plan
# touches; the planned elements are compacted out afterwards.
BURST_BLOCK = 128


def gather_rows(table: jax.Array, indices: jax.Array,
                use_pallas: bool = False,
                interpret: bool | None = None) -> jax.Array:
    if use_pallas:
        return kernel.gather_rows(table, indices, interpret=interpret)
    return ref.gather_rows(table, indices)


def gather_rows_bag(table: jax.Array, bags: jax.Array,
                    use_pallas: bool = False,
                    interpret: bool | None = None) -> jax.Array:
    if use_pallas:
        return kernel.gather_rows_bag(table, bags, interpret=interpret)
    return ref.gather_rows_bag(table, bags)


def chunk_runs(run_starts: np.ndarray, run_lengths: np.ndarray,
               block: int = BURST_BLOCK
               ) -> tuple[np.ndarray, np.ndarray]:
    """Map coalesced plan runs onto the aligned ``block``-element windows
    of the payload that hold them.

    Pure numpy (host side — plan post-processing, not kernel work).
    Returns (rows (C,) int64, gather_idx (N,) int64): ``rows`` are the
    distinct windows the runs touch, ascending, and ``gather_idx``
    compacts the (C·block,) window lattice back to the plan's N points
    in run order.
    """
    starts = np.asarray(run_starts, np.int64)
    lens = np.asarray(run_lengths, np.int64)
    if starts.size == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    ends = np.cumsum(lens)
    offsets = (np.repeat(starts - (ends - lens), lens)
               + np.arange(int(ends[-1]), dtype=np.int64))
    rows, slot = np.unique(offsets // block, return_inverse=True)
    return rows, slot.reshape(-1) * block + offsets % block


def gather_plan_runs(flat: jax.Array, run_starts: np.ndarray,
                     run_lengths: np.ndarray, block: int = BURST_BLOCK,
                     use_pallas: bool = False,
                     interpret: bool | None = None) -> jax.Array:
    """Run-length-aware burst gather of an extraction plan.

    Reads every planned element of the flat (n,) payload as wide
    contiguous copies — one DMA per aligned ``block``-element window the
    plan's runs touch — then compacts the window lattice back to the
    plan's point order.  Byte-equal to ``flat[plan.offsets]``.  The
    payload is read in place: no padded copy is made per call.
    """
    rows, gather_idx = chunk_runs(run_starts, run_lengths, block)
    if rows.size == 0:
        return jnp.zeros((0,), flat.dtype)
    n_rows = -(-flat.shape[0] // block)
    ensure_i32_addressable(n_rows * block, what="burst gather windows")
    rows32 = checked_cast_i32(rows, what="burst gather rows",
                              n_elements=n_rows)
    if use_pallas:
        out = kernel.gather_runs(flat, rows32, block, interpret=interpret)
    else:
        out = ref.gather_runs(flat, rows32, block)
    idx = checked_cast_i32(gather_idx,
                           what="burst gather compaction indices",
                           n_elements=out.size)
    return jnp.take(out.reshape(-1), idx)
