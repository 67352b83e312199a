"""Pallas TPU kernel: segment-sum as a one-hot MXU matmul.

TPU has no scatter unit; the idiomatic TPU scatter-add is
``onehot(seg_ids) @ messages`` — a (S × E_blk) × (E_blk × D) matmul per
edge block, accumulated into the revisited (S, D) output block.  The
MXU turns the GNN aggregation (and EmbeddingBag epilogues) into dense
systolic work (DESIGN.md §3 hardware adaptation: scatter → matmul).

Constraint: the full (num_segments, D) accumulator lives in VMEM, so
this kernel serves minibatch/molecule regimes (S·D ≲ 512k floats).
Full-graph regimes keep `jax.ops.segment_sum` (XLA handles HBM-resident
scatter); the dispatch in ops.py chooses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels._casting import checked_cast_i32

BLOCK_E = 256


def _segment_sum_kernel(seg_ref, msg_ref, out_ref, *, num_segments: int,
                        n_blocks: int):
    e = pl.program_id(0)

    @pl.when(e == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seg = seg_ref[...]                               # (BLOCK_E,)
    msg = msg_ref[...].astype(jnp.float32)           # (BLOCK_E, D)
    valid = seg >= 0
    seg_ids = jnp.where(valid, seg, 0)
    onehot = (seg_ids[None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (num_segments, seg.shape[0]), 0))
    onehot = jnp.where(valid[None, :], onehot, False).astype(jnp.float32)
    out_ref[...] += (onehot @ msg).astype(out_ref.dtype)


def segment_sum(messages: jax.Array, segment_ids: jax.Array,
                num_segments: int,
                interpret: bool | None = None) -> jax.Array:
    """Validate segment ids host-side (each in [0, num_segments), ``-1``
    padding allowed), cast through the bounds-checked helper, then run
    the jitted one-hot MXU kernel; tracers pass through."""
    seg32 = checked_cast_i32(segment_ids, what="segment_sum segment_ids",
                             n_elements=num_segments,
                             allow_negative_one=True)
    return _segment_sum(messages, seg32, num_segments,
                        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def _segment_sum(messages: jax.Array, segment_ids: jax.Array,
                 num_segments: int, interpret: bool) -> jax.Array:
    e, d = messages.shape
    pad = (-e) % BLOCK_E
    if pad:
        messages = jnp.pad(messages, ((0, pad), (0, 0)))
        # -1 padding stays int32 — masked out inside the kernel
        segment_ids = jnp.pad(segment_ids, (0, pad), constant_values=-1)
    ee = messages.shape[0]
    n_blocks = ee // BLOCK_E

    return pl.pallas_call(
        functools.partial(_segment_sum_kernel, num_segments=num_segments,
                          n_blocks=n_blocks),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((BLOCK_E,), lambda i: (i,)),
            pl.BlockSpec((BLOCK_E, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_segments, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_segments, d), messages.dtype),
        interpret=interpret,
        name="segment_sum_onehot_mxu",
    )(segment_ids, messages)
