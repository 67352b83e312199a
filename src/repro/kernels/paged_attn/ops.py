"""Jit'd dispatch for paged decode attention."""

from __future__ import annotations

from . import kernel, ref


def paged_decode_attention(q, k_pages, v_pages, block_table, seq_lens,
                           use_pallas: bool = False,
                           interpret: bool | None = None):
    if use_pallas:
        return kernel.paged_decode_attention(q, k_pages, v_pages,
                                             block_table, seq_lens,
                                             interpret=interpret)
    return ref.paged_decode_attention(q, k_pages, v_pages, block_table,
                                      seq_lens)
