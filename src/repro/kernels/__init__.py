# Pallas TPU kernels for the framework's compute hot spots.  Each
# subpackage is <name>/{kernel.py, ops.py, ref.py}: pl.pallas_call with
# explicit BlockSpec VMEM tiling, a jit'd dispatching wrapper, and the
# pure-jnp oracle the tests assert against.
#
# gather     — exact-byte extraction gather + fused EmbeddingBag (the
#              paper's I/O path on TPU: scalar-prefetch DMA of planned rows)
#              + run-length burst gather over coalesced plan runs
# slice      — batched polytope-hyperplane slicing (one BFS layer of
#              Algorithm 1 per launch)
# plan       — persistent device-resident BFS planning: the full
#              Algorithm-1 trailing stage (slice → compact → run
#              emission) in one pipeline invocation
# paged_attn — decode attention reading only planner-named KV pages
# segment    — segment-sum as one-hot MXU matmul (GNN / bag aggregation)
#
# _casting.checked_cast_i32 is the ONLY place an offset-carrying array
# may be cast to the kernels' int32 index dtype (enforced by the
# unchecked-i32-cast lint rule in repro.analysis).


def resolve_interpret(interpret: bool | None) -> bool:
    """Pallas interpret mode: ``interpret`` if given, else true exactly
    when JAX's default backend is the CPU.  Every kernel wrapper defaults
    to ``None`` and resolves it here, so a chip run compiles the kernel
    and a CPU run interprets it without either caller saying so."""
    if interpret is not None:
        return interpret
    import jax

    return jax.default_backend() == "cpu"


# The subpackages import resolve_interpret from here, so it is defined
# before they load.
from . import gather, paged_attn, plan, segment, slice  # noqa: E402,F401
from ._casting import checked_cast_i32, ensure_i32_addressable  # noqa: E402

__all__ = ["gather", "paged_attn", "plan", "segment", "slice",
           "checked_cast_i32", "ensure_i32_addressable",
           "resolve_interpret"]
