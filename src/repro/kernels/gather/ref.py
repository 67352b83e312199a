"""Pure-jnp oracles for the gather kernels."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels._casting import checked_cast_i32


def gather_rows(table: jax.Array, indices: jax.Array) -> jax.Array:
    idx = checked_cast_i32(indices, what="gather_rows indices",
                           n_elements=table.shape[0])
    return jnp.take(table, idx, axis=0)


def gather_rows_bag(table: jax.Array, bags: jax.Array) -> jax.Array:
    """EmbeddingBag(sum) with -1 padding."""
    bags = checked_cast_i32(bags, what="gather_rows_bag bags",
                            n_elements=table.shape[0],
                            allow_negative_one=True)
    valid = (bags >= 0)[..., None]
    rows = jnp.take(table, jnp.maximum(bags, 0), axis=0)
    return jnp.sum(jnp.where(valid, rows, 0), axis=1).astype(table.dtype)


def gather_runs(flat: jax.Array, chunk_rows: jax.Array,
                block: int) -> jax.Array:
    """Oracle for the burst kernel: aligned window loads, (C, block).
    A payload whose length is not a multiple of ``block`` reads zeros
    past its end, which no plan element maps to."""
    rows = checked_cast_i32(chunk_rows, what="gather_runs rows",
                            n_elements=-(-flat.shape[0] // block))
    window = (rows[:, None] * block
              + jnp.arange(block, dtype=jnp.int32)[None, :])
    return jnp.take(flat, window, axis=0, mode="fill", fill_value=0)
