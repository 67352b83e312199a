"""Dispatching segment reduction: Pallas for VMEM-resident accumulators,
XLA segment_sum otherwise."""

from __future__ import annotations

import jax

from . import kernel, ref

VMEM_SEGMENT_LIMIT = 512 * 1024  # floats of (S, D) accumulator


def segment_sum(messages: jax.Array, segment_ids: jax.Array,
                num_segments: int, use_pallas: bool = False,
                interpret: bool | None = None) -> jax.Array:
    d = messages.shape[-1]
    if use_pallas and num_segments * d <= VMEM_SEGMENT_LIMIT:
        return kernel.segment_sum(messages, segment_ids, num_segments,
                                  interpret=interpret)
    return ref.segment_sum(messages, segment_ids, num_segments)


def segment_max(messages: jax.Array, segment_ids: jax.Array,
                num_segments: int, **_) -> jax.Array:
    return ref.segment_max(messages, segment_ids, num_segments)
