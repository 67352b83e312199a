"""Public entry point for the fused planning pipeline.

``use_pallas`` selects the persistent Pallas pipeline (interpreted on
the CPU backend; it does not compile for a TPU — Mosaic refuses its
block shapes, and no float64 operand can reach a TPU kernel); the
default is the pure-jnp oracle, which is the same fused
computation without the explicit grid — either way planning is ONE
device invocation instead of a host round-trip per BFS layer.
"""

from __future__ import annotations

from . import kernel, ref

EPS0 = ref.EPS0
EPS1 = ref.EPS1
PLANE_TOL_REL = ref.PLANE_TOL_REL
PERIOD = ref.PERIOD


def plan_runs_2d(verts, valid, base, sv0, rowoff0, sv1, scalars, *,
                 n0: int, n1: int, max_rows: int, cyclic: bool,
                 use_pallas: bool = False,
                 interpret: bool | None = None):
    if use_pallas:
        return kernel.plan_runs_2d(
            verts, valid, base, sv0, rowoff0, sv1, scalars,
            n0=n0, n1=n1, max_rows=max_rows, cyclic=cyclic,
            interpret=interpret)
    return ref.plan_runs_2d(
        verts, valid, base, sv0, rowoff0, sv1, scalars,
        n0=n0, n1=n1, max_rows=max_rows, cyclic=cyclic)
