"""Pallas TPU kernel: batched polytope-hyperplane slicing.

One grid step slices BLOCK_P polytopes against their planes — a BFS
layer of Algorithm 1 becomes a single kernel launch (DESIGN.md §3).
The math is pure VPU work (sign split, all-pairs lerp) on small tiles
that live entirely in VMEM: verts (BLOCK_P, V, D) plus the (V × V) pair
lattice.  V and D are tiny (≤ 32, ≤ 8), so the working set is a few KB
per step; the batch dimension P provides the parallelism.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

from .ref import PLANE_TOL

BLOCK_P = 8


def _slice_kernel(verts_ref, valid_ref, planes_ref, out_ref, mask_ref, *,
                  k: int):
    verts = verts_ref[...]                         # (BP, V, D)
    valid = valid_ref[...]                         # (BP, V)
    planes = planes_ref[...]                       # (BP,)
    bp, v, d = verts.shape

    c = planes[:, None]
    coord = verts[:, :, k]
    scale = jnp.maximum(1.0, jnp.max(jnp.abs(coord), axis=1, keepdims=True))
    big = jnp.asarray(1e30, verts.dtype)
    dist = jnp.where(valid, coord - c, big)

    on = (jnp.abs(dist) <= PLANE_TOL * scale) & valid
    below = (dist < -PLANE_TOL * scale) & valid
    above = (dist > PLANE_TOL * scale) & (dist < big) & valid

    on_pts = verts.at[:, :, k].set(jnp.broadcast_to(c, (bp, v)))

    di = jnp.where(below, dist, 0.0)[:, :, None]
    dj = jnp.where(above, dist, 0.0)[:, None, :]
    denom = di - dj
    t = jnp.where(jnp.abs(denom) > 0,
                  di / jnp.where(denom == 0, 1.0, denom), 0.0)
    vi = verts[:, :, None, :]
    vj = verts[:, None, :, :]
    interp = vi + t[..., None] * (vj - vi)
    interp = interp.at[:, :, :, k].set(
        jnp.broadcast_to(c[:, :, None], (bp, v, v)))
    pair_valid = below[:, :, None] & above[:, None, :]

    out = jnp.concatenate([on_pts, interp.reshape(bp, v * v, d)], axis=1)
    out_valid = jnp.concatenate([on, pair_valid.reshape(bp, v * v)], axis=1)
    out_ref[...] = jnp.where(out_valid[..., None], out, 0.0)
    mask_ref[...] = out_valid


def slice_batch(verts: jax.Array, valid: jax.Array, planes: jax.Array,
                k: int, interpret: bool | None = None
                ) -> tuple[jax.Array, jax.Array]:
    return _slice_batch(verts, valid, planes, k=k,
                        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _slice_batch(verts: jax.Array, valid: jax.Array, planes: jax.Array,
                 k: int, interpret: bool) -> tuple[jax.Array, jax.Array]:
    p, v, d = verts.shape
    pad = (-p) % BLOCK_P
    if pad:
        verts = jnp.pad(verts, ((0, pad), (0, 0), (0, 0)))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
        planes = jnp.pad(planes, (0, pad))
    pp = verts.shape[0]
    n_slots = v + v * v

    out, mask = pl.pallas_call(
        functools.partial(_slice_kernel, k=k),
        grid=(pp // BLOCK_P,),
        in_specs=[
            pl.BlockSpec((BLOCK_P, v, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((BLOCK_P, v), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_P,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_P, n_slots, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((BLOCK_P, n_slots), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pp, n_slots, d), verts.dtype),
            jax.ShapeDtypeStruct((pp, n_slots), jnp.bool_),
        ],
        interpret=interpret,
        name="polytope_slice_batch",
    )(verts, valid, planes)
    return out[:p], mask[:p]
