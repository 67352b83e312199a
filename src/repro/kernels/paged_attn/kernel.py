"""Pallas TPU kernel: paged decode attention with online softmax.

The serving engine's Polytope planner (``repro.serve.kv_cache``) emits a
block table — the extraction plan over the KV-cache datacube
(layer, page, slot).  This kernel consumes that plan with scalar
prefetch: grid step (b, kvh, p) DMAs exactly page ``block_table[b, p]``
for kv head ``kvh`` HBM→VMEM and folds it into a running
flash-attention accumulator (m, l, acc held in VMEM scratch).  Pages not
in the plan are never read — the paper's exact-byte I/O on the KV cache.

Decode attention is memory-bound (one q token vs S cached tokens), so
roofline here is HBM bytes = exactly the live pages; a bounding-box
reader would stream the whole padded (B, PMAX·PS) rectangle including
dead pages.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels._casting import checked_cast_i32

NEG_INF = -1e30


def _paged_attn_kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, out_ref,
                       m_ref, l_ref, acc_ref, *, ps: int, pmax: int):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (G, Dh)
    k = k_ref[0, 0].astype(jnp.float32)              # (PS, Dh)
    v = v_ref[0, 0].astype(jnp.float32)              # (PS, Dh)
    dh = q.shape[-1]

    seq_len = lens_ref[b]
    base = p * ps
    offs = base + jax.lax.broadcasted_iota(jnp.int32, (ps,), 0)
    slot_live = offs < seq_len                        # (PS,)

    s = (q @ k.T) / jnp.sqrt(jnp.float32(dh))         # (G, PS)
    s = jnp.where(slot_live[None, :], s, NEG_INF)

    m_prev = m_ref[...]                               # (G, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    pexp = jnp.exp(s - m_cur)                         # (G, PS)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + pexp @ v
    m_ref[...] = m_cur

    @pl.when(p == pmax - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        out_ref[0, 0] = (acc_ref[...] / denom).astype(out_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_table, seq_lens,
                           interpret: bool | None = None):
    """Validate the plan indices host-side, then run the jitted kernel.

    ``block_table`` entries are page ids in [0, n_pages) with ``-1``
    marking unused slots; ``seq_lens`` live KV lengths in
    [0, PMAX·PS].  Both are scalar-prefetch inputs the kernel consumes
    as int32, so the cast goes through the bounds-checked helper
    (offsets past 2³¹ raise instead of truncating); tracers pass
    through.
    """
    n_pages, _, ps, _ = k_pages.shape
    pmax = block_table.shape[1]
    table32 = checked_cast_i32(block_table,
                               what="paged_decode_attention block_table",
                               n_elements=n_pages,
                               allow_negative_one=True)
    lens32 = checked_cast_i32(seq_lens,
                              what="paged_decode_attention seq_lens",
                              n_elements=pmax * ps + 1)
    return _paged_decode_attention(q, k_pages, v_pages, table32, lens32,
                                   interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode_attention(q, k_pages, v_pages, block_table, seq_lens,
                            interpret: bool):
    b, h, dh = q.shape
    n_pages, kvh, ps, _ = k_pages.shape
    pmax = block_table.shape[1]
    g = h // kvh
    q4 = q.reshape(b, kvh, g, dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, pmax),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda b_, k_, p_, tbl, ln: (b_, k_, 0, 0)),
            pl.BlockSpec((1, 1, ps, dh),
                         lambda b_, k_, p_, tbl, ln:
                         (jnp.maximum(tbl[b_, p_], 0), k_, 0, 0)),
            pl.BlockSpec((1, 1, ps, dh),
                         lambda b_, k_, p_, tbl, ln:
                         (jnp.maximum(tbl[b_, p_], 0), k_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda b_, k_, p_, tbl, ln: (b_, k_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, ps=ps, pmax=pmax),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dh), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_table, seq_lens, q4, k_pages, v_pages)
    return out.reshape(b, h, dh)
