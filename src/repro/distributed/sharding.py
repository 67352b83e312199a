"""Sharding rules: param-path → PartitionSpec, per model family — plus
the consistent-hash ring that routes plan-cache keys to shards.

Rules are name-based (like MaxText's logical-axis rules): a single
function inspects the pytree path and leaf shape and returns the spec.
All rules speak axis *names* ("data", "model", and optionally "pod"),
so the same model code lowers on any mesh — single-pod (16, 16),
multi-pod (2, 16, 16), or the tiny CI meshes in tests.

Conventions:
 * TP: attention heads / FFN hidden / vocab / MoE experts → "model".
 * Batch-like inputs → ("pod", "data") for training (pod = outer DP).
 * Optimizer state (m/v): the param spec with "data" added on the first
   open dim — ZeRO-1 style state sharding.
 * Stacked-layer params (leading scan dim) get None prepended.
"""

from __future__ import annotations

import bisect
import hashlib
from typing import Any, Callable, Iterable

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# Consistent-hash routing (DESIGN.md §4)
#
# Plan-cache keys are stable sha256 content hashes
# (``Request.canonical_hash``), so the routing point is simply the key's
# leading 64-bit hex prefix — already uniform, never rehashed.  Shards
# get ``replicas`` virtual points on the ring, which keeps balance
# within a few percent and makes shard add/remove move only ~1/N of the
# key space (the classic consistent-hashing guarantee the rebalance
# tests pin down).
# ---------------------------------------------------------------------------

PREFIX_HEX = 16        # leading hex chars of a key → 64-bit ring point
RING_SPACE = 2 ** (4 * PREFIX_HEX)


def key_point(key: str) -> int:
    """Ring position of a canonical-hash key: its 64-bit hex prefix."""
    return int(key[:PREFIX_HEX], 16)


class HashRing:
    """Consistent-hash ring over named shards.

    Lock-free readers: the ring state is one tuple
    ``(nodes, points, owners)`` that mutators rebuild and swap with a
    single attribute store, so a concurrent ``route`` sees either the
    old or the new ring, never a half-built one.  Mutations themselves
    are admin-plane — callers (``ShardedPlanCache.add_shard``) serialize
    them externally.
    """

    def __init__(self, nodes: Iterable[str] = (), replicas: int = 64):
        if replicas < 1:
            raise ValueError("replicas must be >= 1")
        self.replicas = replicas
        self._state: tuple[tuple[str, ...], tuple[int, ...],
                           tuple[str, ...]] = ((), (), ())
        for node in nodes:
            self.add_node(node)

    @property
    def nodes(self) -> tuple[str, ...]:
        """Shard names in insertion order."""
        return self._state[0]

    def __len__(self) -> int:
        return len(self._state[0])

    def __contains__(self, node: str) -> bool:
        return node in self._state[0]

    @staticmethod
    def _virtual_points(node: str, replicas: int) -> list[int]:
        return [int(hashlib.sha256(f"{node}#{i}".encode()).hexdigest()
                    [:PREFIX_HEX], 16) for i in range(replicas)]

    def _rebuild(self, nodes: tuple[str, ...]) -> None:
        ring = sorted((p, n) for n in nodes
                      for p in self._virtual_points(n, self.replicas))
        self._state = (nodes, tuple(p for p, _ in ring),
                       tuple(n for _, n in ring))

    def add_node(self, node: str) -> None:
        nodes = self._state[0]
        if node in nodes:
            raise ValueError(f"shard {node!r} already on the ring")
        self._rebuild(nodes + (node,))

    def remove_node(self, node: str) -> None:
        nodes = self._state[0]
        if node not in nodes:
            raise KeyError(node)
        self._rebuild(tuple(n for n in nodes if n != node))

    def route(self, key: str) -> str:
        """Owning shard of a canonical-hash key (clockwise successor of
        the key's 64-bit prefix point on the ring)."""
        _, points, owners = self._state
        if not owners:
            raise RuntimeError("HashRing has no nodes")
        i = bisect.bisect_right(points, key_point(key))
        return owners[i % len(owners)]


def _path_names(path) -> list[str]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "idx"):
            names.append(str(p.idx))
        else:
            names.append(str(p))
    return names


def lm_rules(path, shape: tuple[int, ...]) -> P:
    """Transformer sharding (GQA / MLA / MoE / dense)."""
    names = _path_names(path)
    leaf = names[-1]
    stacked = "groups" in names         # scan-stacked → leading L dim
    inner = shape[1:] if stacked else shape

    def spec(*dims):
        full = (None,) + dims if stacked else dims
        return P(*full[: len(shape)])

    if leaf in ("scale", "bias", "b"):
        return spec(None)
    if "router" in names:
        return spec(None, None)
    if leaf in ("w_gate", "w_up") and len(inner) == 3:     # MoE (E, D, F)
        return spec("model", None, None)
    if leaf == "w_down" and len(inner) == 3:               # MoE (E, F, D)
        return spec("model", None, None)
    if "embed" in names or leaf == "table":                # (V, D)
        return spec("model", None)
    if leaf in ("wq", "wk", "wv", "wq_b", "wk_b", "wv_b"):
        return spec(None, "model")                         # (…, H·Dh)
    if leaf in ("wq_a", "wkv_a"):
        return spec(None, "model")                         # low-rank in
    if leaf == "wo":
        return spec("model", None)                         # (H·Dh, D)
    if leaf in ("w_gate", "w_up"):                         # dense (D, F)
        return spec(None, "model")
    if leaf == "w_down":                                   # dense (F, D)
        return spec("model", None)
    if leaf == "w":                                        # generic dense
        if len(inner) == 2:
            return spec(None, "model")
        return spec(*([None] * len(inner)))
    return P(*([None] * len(shape)))


def gnn_rules(path, shape: tuple[int, ...]) -> P:
    """NequIP params are tiny — replicate everything."""
    return P(*([None] * len(shape)))


def recsys_rules(path, shape: tuple[int, ...]) -> P:
    names = _path_names(path)
    leaf = names[-1]
    if leaf == "tables" and len(shape) == 3:     # (T, rows, D) row-shard
        return P(None, "model", None)
    if leaf == "table" and len(shape) == 2:      # (rows, D) row-shard
        return P("model", None)
    if ("tower" in " ".join(names) or "deep" in names or "top" in names
            or "bot" in names) and leaf == "w" and len(shape) == 2:
        return P(None, None)                     # small MLPs replicated
    # bert4rec reuses the transformer
    return lm_rules(path, shape)


RULES: dict[str, Callable] = {
    "lm": lm_rules,
    "gnn": gnn_rules,
    "recsys": recsys_rules,
}


def param_specs(params: Any, rules: Callable) -> Any:
    """PartitionSpec tree matching ``params``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: rules(path, np.shape(leaf)), params)


DATA_AXIS_SIZE = 16   # production data-axis extent (per pod)
POD_AXIS_SIZE = 2     # pods on the multi-pod mesh

# FSDP shards over data *and* pod: 671B-class models only fit when the
# cross-pod axis also carries parameter shards (sanitize_specs degrades
# this to data-only on single-pod meshes).
FSDP_AXES = ("data", "pod")


def add_data_axis(spec: P, shape: tuple[int, ...],
                  min_size: int = 2 ** 16,
                  data_size: int = DATA_AXIS_SIZE * POD_AXIS_SIZE,
                  axes: tuple = FSDP_AXES) -> P:
    """Add the FSDP axes on the first open, evenly-divisible dim of a
    ≥2-D tensor (ZeRO/FSDP).  jit input shardings require exact
    divisibility, so dims not divisible by the full extent are skipped."""
    if len(shape) < 2 or int(np.prod(shape)) < min_size:
        return spec
    flat = [a for d in spec if d is not None
            for a in (d if isinstance(d, tuple) else (d,))]
    if any(a in flat for a in axes):
        return spec
    dims = list(spec) + [None] * (len(shape) - len(spec))
    for i, d in enumerate(dims):
        if d is None and shape[i] > 1 and shape[i] % data_size == 0:
            dims[i] = axes
            break
    return P(*dims)


def sanitize_specs(spec_tree: Any, aval_tree: Any, mesh: Mesh) -> Any:
    """Make spec trees legal for this mesh: drop axis names the mesh
    does not have (rules may speak of "pod" on single-pod meshes), and
    drop axes whose product doesn't divide the dim size.

    jit ``in_shardings`` reject uneven partitions, and published configs
    have plenty of awkward extents (49155-token vocabs, 26 tables, 61
    layers) — any non-divisible dim falls back to replication on that
    dim, everything else keeps its sharding."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def fix(spec, aval):
        if not isinstance(spec, P):
            return spec
        shape = tuple(getattr(aval, "shape", ()))
        dims = list(spec)[: len(shape)]
        out = []
        for i, d in enumerate(dims):
            if d is None:
                out.append(None)
                continue
            axes = tuple(a for a in
                         (d if isinstance(d, tuple) else (d,))
                         if a in sizes)
            if not axes:
                out.append(None)
                continue
            total = int(np.prod([sizes[a] for a in axes]))
            if shape[i] % total:
                out.append(None)
            else:
                out.append(axes if len(axes) > 1 else axes[0])
        return P(*out)

    return jax.tree.map(fix, spec_tree, aval_tree,
                        is_leaf=lambda x: isinstance(x, P) or x is None)


def opt_state_specs(pspec_tree: Any, params: Any,
                    min_size: int = 2 ** 16) -> Any:
    """ZeRO-1: add "data" on the first open dim of each ≥2-D param."""
    return jax.tree.map(
        lambda spec, leaf: add_data_axis(spec, np.shape(leaf), min_size),
        pspec_tree, params)


def fsdp_rules(base_rules: Callable) -> Callable:
    """Wrap family rules with FSDP: params additionally shard on "data".

    Embedding tables are exempt: a token gather over a table sharded on
    *both* vocab and feature dims hits SPMD's involuntary-full-remat
    path (vocab-only sharding lowers to the standard masked-gather +
    all-reduce)."""
    def rules(path, shape):
        names = _path_names(path)
        if "embed" in names or names[-1] == "table":
            return base_rules(path, shape)
        return add_data_axis(base_rules(path, shape), shape)

    return rules


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree, is_leaf=lambda x: isinstance(x, P))


def batch_axes(mesh: Mesh) -> tuple:
    """The combined data-parallel axes present on this mesh."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes if axes else (mesh.axis_names[0],)
