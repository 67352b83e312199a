"""The building blocks of the extraction service (DESIGN.md §4): plan
caching, span-and-counter stages, and the shared union read.
:class:`repro.serve.sharded.ShardedExtractionService` assembles them.

Production request streams against a datacube are highly repetitive —
the same country crop every forecast cycle, the same recsys region every
step, the same flight corridor for every flight on a route.  Re-running
Algorithm 1 per request makes *planning*, not I/O, the bottleneck at
scale.  This module holds:

* :class:`PlanCache`, a bounded LRU of
  :class:`~repro.core.index_tree.ExtractionPlan` objects keyed by the
  request's canonical content hash (``Request.canonical_hash``), so
  permuted-but-equivalent requests collide, with hit/miss/eviction
  counters in :class:`CacheStats` exposed like ``SliceStats``;
* :class:`KeyMemo`, which finds a repeated request's key by its raw
  shapes, before the decomposition the canonical hash starts from;
* :class:`NeighborhoodIndex`, which finds a drifted request's parent
  plan for the delta planner;
* :class:`Stage`, one stage of the served path as a profiler span and a
  counter at once;
* :func:`shared_union_gather`, which executes a window's distinct plans
  through one coalesced-run union read, so overlapping requests read
  each byte once.

Plans are immutable once built, so cache hits return the *same* plan
object — byte-identical offsets to the cold plan by construction.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, fields, replace
from typing import Any, Iterable, Sequence

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import Request, gather
from repro.core.datacube import Datacube
from repro.core.index_tree import ExtractionPlan, expand_runs
from repro.core.slicer import SliceStats


@dataclass
class CacheStats:
    """Plan-cache instrumentation (the serving analogue of SliceStats)."""

    hits: int = 0                   # plan served from the LRU
    misses: int = 0                 # plan built by Algorithm 1
    evictions: int = 0              # plans dropped at capacity
    batch_dedup: int = 0            # duplicate requests inside one batch
    plan_time_s: float = 0.0        # cumulative cold-planning walltime
    gather_time_s: float = 0.0      # cumulative shared-gather walltime
    bytes_requested: int = 0        # sum over served requests
    bytes_read: int = 0             # union reads actually issued
    plans_shipped: int = 0          # cold plans shipped to peer replicas
    plans_received: int = 0         # peer plans installed locally
    migrations: int = 0             # entries popped for shard rebalance
    delta_hits: int = 0             # misses served by plan splicing
    delta_misses: int = 0           # misses with no splicable neighbor
    delta_time_s: float = 0.0       # cumulative splice walltime
    lookup_time_s: float = 0.0      # batch loop: key, route, get, plan
    # gather_time_s split into its four stages (they sum to it)
    union_time_s: float = 0.0       # host union build
    launch_time_s: float = 0.0      # gather call: trace, compile, dispatch
    copy_time_s: float = 0.0        # device wait + copy back to the host
    slice_time_s: float = 0.0       # per-request slices of the union
    gather_padded_elems: int = 0    # launch length less union length
    union_runs: int = 0             # runs of the windows' merged unions
    key_memo_hits: int = 0          # keys found by the raw request's digest
    key_memo_misses: int = 0        # keys computed by canonical_hash

    @property
    def lookups(self) -> int:
        return self.hits + self.misses

    @property
    def hit_rate(self) -> float:
        n = self.lookups
        return self.hits / n if n else 0.0

    @property
    def sharing_factor(self) -> float:
        """requested/read ≥ 1: how much the batch union read saved.

        Edge cases are explicit: nothing requested *and* nothing read
        (only empty plans in the batch) shares nothing and reports the
        neutral 1.0; bytes requested with **zero** bytes read is
        infinite sharing (``inf``), not 1.0 — returning 1.0 here would
        silently under/over-report savings on empty-gather batches
        (pinned by the regression test in tests/test_plan_cache.py).
        """
        if self.bytes_read:
            return self.bytes_requested / self.bytes_read
        return float("inf") if self.bytes_requested else 1.0


def merge_stats(parts: Iterable[CacheStats],
                into: CacheStats | None = None) -> CacheStats:
    """Field-wise sum of :class:`CacheStats` (derived rates recompute
    from the summed counters) — shard aggregation for the sharded cache.
    ``into`` accumulates in place (a service folding a batch's delta
    into its own stats under its own lock); a fresh object otherwise."""
    out = CacheStats() if into is None else into
    for s in parts:
        for f in fields(CacheStats):
            setattr(out, f.name, getattr(out, f.name) + getattr(s, f.name))
    return out


class Stage:
    """One stage of the served path, recorded twice at once: a
    ``jax.profiler.TraceAnnotation`` span named ``name`` (``meta`` rides
    along as its arguments; free while no profiler runs) and, when
    ``stats`` is given, the stage's ``perf_counter`` length added to
    ``stats.<counter>``.  ``start`` chains stages: given the previous
    stage's ``end``, adjacent stages share their boundary, so their
    lengths sum to the whole."""

    __slots__ = ("stats", "counter", "start", "end", "_span")

    def __init__(self, name: str, stats: Any = None, counter: str = "",
                 start: float | None = None, **meta: Any):
        self._span = TraceAnnotation(name, **meta)
        self.stats = stats
        self.counter = counter
        self.start = start
        self.end = 0.0

    def __enter__(self) -> "Stage":
        self._span.__enter__()
        if self.start is None:
            self.start = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.end = time.perf_counter()
        self._span.__exit__(*exc)
        if self.stats is not None:
            setattr(self.stats, self.counter,
                    getattr(self.stats, self.counter) + self.seconds)

    @property
    def seconds(self) -> float:
        return self.end - self.start


class PlanCache:
    """Bounded LRU of ``canonical_hash → ExtractionPlan``.

    Thread-safe: an internal lock serializes every OrderedDict access.
    ``keys()``/``__contains__`` racing a concurrent ``put`` eviction
    would otherwise iterate the dict mid-mutation — the unsynchronized
    read the lock-discipline fixture in ``tests/test_analysis.py`` pins
    as a regression.
    """

    def __init__(self, capacity: int = 1024):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._od: OrderedDict[str, ExtractionPlan] = OrderedDict()
        self.stats = CacheStats()
        self._lock = threading.Lock()

    def __len__(self) -> int:
        with self._lock:
            return len(self._od)

    def __contains__(self, key: str) -> bool:
        with self._lock:
            return key in self._od

    def get(self, key: str) -> ExtractionPlan | None:
        with self._lock:
            plan = self._od.get(key)
            if plan is None:
                self.stats.misses += 1
                return None
            self._od.move_to_end(key)
            self.stats.hits += 1
            return plan

    def put(self, key: str, plan: ExtractionPlan) -> None:
        with self._lock:
            if key in self._od:
                self._od.move_to_end(key)
            self._od[key] = plan
            while len(self._od) > self.capacity:
                self._od.popitem(last=False)
                self.stats.evictions += 1

    def peek(self, key: str) -> ExtractionPlan | None:
        """Uncounted, non-mutating lookup: the delta planner fetching a
        *parent* plan is not a request-path cache lookup, so it must not
        perturb the hit/miss counters (``lookups == hits + misses``
        stays tied to served requests) nor the LRU order (eviction
        reflects what users requested, not which parents were spliced
        from — the freshly spliced child is put at MRU anyway)."""
        with self._lock:
            return self._od.get(key)

    def pop(self, key: str) -> ExtractionPlan | None:
        """Remove and return ``key``'s plan (shard-rebalance migration).

        Counts ``stats.migrations`` when an entry was actually removed —
        without the counter, rebalance mutated the cache invisibly and
        the stats-conservation invariant in
        tests/test_serve_concurrent.py silently ignored migrated
        entries."""
        with self._lock:
            plan = self._od.pop(key, None)
            if plan is not None:
                self.stats.migrations += 1
            return plan

    def keys(self) -> list[str]:
        """LRU → MRU order (eviction order is the front)."""
        with self._lock:
            return list(self._od)

    def record(self, **deltas: float) -> None:
        """Atomically bump :class:`CacheStats` counters by name
        (``record(plan_time_s=dt, batch_dedup=1)``)."""
        with self._lock:
            for name, d in deltas.items():
                setattr(self.stats, name, getattr(self.stats, name) + d)

    def snapshot(self) -> CacheStats:
        """Consistent copy of the counters (safe to aggregate lock-free)."""
        with self._lock:
            return replace(self.stats)


class KeyMemo:
    """Bounded LRU of ``Request.spec_digest → canonical_hash``.

    A repeat of a request's raw shapes gets its plan-cache key without
    the decomposition (ear-clipping a country polygon) that
    ``canonical_hash`` starts from.  The key is a pure function of the
    raw request, so a memoized key is the one the hash would give.
    Thread-safe behind its own lock.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._od: OrderedDict[str, str] = OrderedDict()
        self._lock = threading.Lock()

    def __len__(self) -> int:
        with self._lock:
            return len(self._od)

    def get(self, digest: str) -> str | None:
        with self._lock:
            key = self._od.get(digest)
            if key is not None:
                self._od.move_to_end(digest)
            return key

    def put(self, digest: str, key: str) -> None:
        with self._lock:
            self._od[digest] = key
            self._od.move_to_end(digest)
            while len(self._od) > self.capacity:
                self._od.popitem(last=False)


@dataclass
class NeighborEntry:
    """One remembered request under a shape signature: where its plan
    lives (exact cache key), its per-axis anchor, and what it asked for
    (the delta planner re-slices changed leading slabs against it)."""

    key: str
    anchor: dict[str, float]
    request: Request
    stats: SliceStats


class NeighborhoodIndex:
    """Bounded two-level LRU: ``shape signature → recent requests``.

    The exact-match LRU misses every *drifted* repeat of a request; this
    index keys on the translation-invariant signature
    (``Request.shape_signature``) so a drifted request finds its parent
    plan, with the anchor delta left for the delta planner to apply.
    ``per_signature`` bounds the anchors remembered per shape; candidates
    come back MRU-first so the nearest parent is tried first.  The bound
    must absorb *interleaved* chains: congruent shapes at incompatible
    anchors (e.g. same-size boxes at different latitudes on the
    non-uniform Gaussian axis) share a signature, and a Zipf-skewed hot
    chain can flush a colder chain's parent out of too small a window.

    Thread-safe behind its own lock — entries are immutable once added.
    """

    def __init__(self, capacity: int = 1024, per_signature: int = 32):
        if capacity < 1 or per_signature < 1:
            raise ValueError("capacity and per_signature must be >= 1")
        self.capacity = capacity
        self.per_signature = per_signature
        self._od: OrderedDict[str, OrderedDict[str, NeighborEntry]] = \
            OrderedDict()
        self._lock = threading.Lock()

    def __len__(self) -> int:
        with self._lock:
            return sum(len(inner) for inner in self._od.values())

    def add(self, sig: str, key: str, anchor: dict[str, float],
            request: Request, stats: SliceStats) -> None:
        with self._lock:
            inner = self._od.get(sig)
            if inner is None:
                inner = OrderedDict()
                self._od[sig] = inner
            else:
                self._od.move_to_end(sig)
            if key in inner:
                inner.move_to_end(key)
            inner[key] = NeighborEntry(key=key, anchor=anchor,
                                       request=request, stats=stats)
            while len(inner) > self.per_signature:
                inner.popitem(last=False)
            while len(self._od) > self.capacity:
                self._od.popitem(last=False)

    def candidates(self, sig: str) -> list[NeighborEntry]:
        """Entries under ``sig``, most-recently-added first."""
        with self._lock:
            inner = self._od.get(sig)
            if inner is None:
                return []
            self._od.move_to_end(sig)
            return list(reversed(inner.values()))

    # -- sharded-migration surface (repro.serve.sharded) -------------------
    def signatures(self) -> list[str]:
        with self._lock:
            return list(self._od)

    def pop_signature(self, sig: str
                      ) -> "OrderedDict[str, NeighborEntry] | None":
        with self._lock:
            return self._od.pop(sig, None)

    def install(self, sig: str,
                entries: "OrderedDict[str, NeighborEntry]") -> None:
        with self._lock:
            inner = self._od.setdefault(sig, OrderedDict())
            inner.update(entries)
            while len(inner) > self.per_signature:
                inner.popitem(last=False)
            while len(self._od) > self.capacity:
                self._od.popitem(last=False)


@dataclass
class ServiceResult:
    """One served request: its plan, optional gathered values, and how
    the plan was obtained (``stats`` is None unless planned cold)."""

    request: Request
    key: str
    plan: ExtractionPlan
    cached: bool
    values: Any | None = None
    stats: SliceStats | None = None


# Launch lengths of a served union read: a power of two, at least this.
# The floor keeps the small point and profile unions on one program.
UNION_BUCKET_FLOOR = 1024


def bucket(n: int) -> int:
    """The smallest power of two ≥ ``max(n, UNION_BUCKET_FLOOR)``."""
    return 1 << (max(n, UNION_BUCKET_FLOOR) - 1).bit_length()


def padded_read_plan(union_plan: ExtractionPlan
                     ) -> tuple[ExtractionPlan, int]:
    """The union's plan at :func:`bucket` length, and where the union
    starts in it.

    ``jnp.take`` compiles once per index length, and nearly every
    coalesced window's union has a length of its own; read at a bucket's
    length, each bucket compiles once per process.  The padding repeats
    the union's first and last offsets, in-range indices (an
    out-of-range one would read as NaN), split so that the union sits in
    the middle: the read's middle element is one the window asked for,
    which a check that alters it (``bench/tests/test_correctness.py``)
    then finds in an answer.  The runs stay the union's, which the
    repeats add nothing to.
    """
    n = union_plan.n_points
    pad = bucket(n) - n
    lead = (pad + 1) // 2
    offsets = np.pad(union_plan.offsets, (lead, pad - lead), mode="edge")
    return replace(union_plan, offsets=offsets), lead


def union_of_runs(plans: Sequence[ExtractionPlan],
                  itemsize: int) -> ExtractionPlan:
    """The union of ``plans`` (non-empty, each tiled by its runs) as one
    plan, built from their runs and not their elements: the runs in
    order of start, those that overlap or touch merged into one, the
    offsets expanded from the merged runs.  Byte-identical to
    ``coalesce_runs(np.unique(<every plan's offsets>))``.  Runs that
    already come ascending and apart, as a window's only plan's do, are
    the union as they stand, and the plans' offsets end to end are its
    offsets."""
    starts = np.concatenate([p.run_starts for p in plans])
    lengths = np.concatenate([p.run_lengths for p in plans])
    ends = starts + lengths
    if np.all(starts[1:] > ends[:-1]):
        offsets = (plans[0].offsets if len(plans) == 1
                   else np.concatenate([p.offsets for p in plans]))
    else:
        order = np.argsort(starts, kind="stable")
        starts, ends = starts[order], ends[order]
        # a run opens a merged run where it starts past every earlier end
        reach = np.maximum.accumulate(ends)
        heads = np.flatnonzero(np.r_[True, starts[1:] > reach[:-1]])
        lengths = reach[np.r_[heads[1:], len(starts)] - 1] - starts[heads]
        starts = starts[heads]
        offsets = expand_runs(starts, lengths)
    return ExtractionPlan(offsets=offsets, run_starts=starts,
                          run_lengths=lengths, coords={}, itemsize=itemsize)


def slice_by_runs(buf: np.ndarray, run_pos: np.ndarray,
                  union_plan: ExtractionPlan,
                  plan: ExtractionPlan) -> np.ndarray:
    """``plan``'s answer out of ``buf``, which holds ``union_plan``'s
    elements, its i-th run from ``run_pos[i]`` on.  Each run of the plan
    lies inside one run of the union, found by its start, so the plan's
    positions come from its runs.  Where they form one stretch the
    answer is a copy of it, never a view: a view would keep the whole
    read buffer alive for as long as the answer."""
    u = np.searchsorted(union_plan.run_starts, plan.run_starts,
                        side="right") - 1
    pos = run_pos[u] + (plan.run_starts - union_plan.run_starts[u])
    if np.array_equal(pos[1:], pos[:-1] + plan.run_lengths[:-1]):
        return buf[pos[0]:pos[0] + plan.n_points].copy()
    return buf[expand_runs(pos, plan.run_lengths)]


def shared_union_gather(datacube: Datacube,
                        results: list[ServiceResult],
                        batch_plans: dict[str, ExtractionPlan],
                        flat_data: Any,
                        verify: bool = False) -> CacheStats:
    """Execute one coalesced union read for ``batch_plans`` and slice each
    result's values out of the shared buffer.

    Fills ``res.values`` in place and returns the batch's accounting as
    a :class:`CacheStats` delta: bytes requested and read (the union's,
    without the launch's padding, counted in ``gather_padded_elems``),
    ``gather_time_s`` and its four stages (union build, gather launch,
    copy back, slicing), which share boundary timestamps and so sum to
    it, and the union's runs.  The caller folds the delta into its own
    stats under its own lock.  The service in :mod:`repro.serve.sharded`
    funnels each window's distinct plans through exactly one such read.
    """
    delta = CacheStats()
    nonempty = {k: p for k, p in batch_plans.items() if p.n_points}
    if not nonempty:
        for res in results:
            res.values = np.empty(0, datacube.dtype)
        return delta
    with Stage("polytope.gather.union", delta, "union_time_s") as union_st:
        union_plan = union_of_runs(list(nonempty.values()),
                                   datacube.dtype.itemsize)
        if verify:
            from repro.analysis.plan_check import verify_plan

            verify_plan(union_plan, datacube=datacube)
    # A device payload's read launches at a bucket's length and is copied
    # back whole (a device-side trim would compile per length); a host
    # payload has no compile, so it reads the union exactly.
    with Stage("polytope.gather.launch", delta, "launch_time_s",
               start=union_st.end) as st:
        read_plan, lead = union_plan, 0
        if isinstance(flat_data, jax.Array):
            read_plan, lead = padded_read_plan(union_plan)
        out = gather(flat_data, read_plan)
    delta.gather_padded_elems = read_plan.n_points - union_plan.n_points
    # One device→host copy of the read; every answer is sliced from it.
    with Stage("polytope.gather.copy", delta, "copy_time_s",
               start=st.end) as st:
        buf = np.asarray(out)
    with Stage("polytope.gather.slice", delta, "slice_time_s",
               start=st.end) as st:
        lengths = union_plan.run_lengths
        run_pos = lead + np.cumsum(lengths) - lengths
        per_key = {key: slice_by_runs(buf, run_pos, union_plan, plan)
                   for key, plan in nonempty.items()}
        for res in results:
            if res.plan.n_points:
                res.values = per_key[res.key]
            else:
                res.values = np.empty(0, datacube.dtype)
            delta.bytes_requested += res.plan.nbytes
    delta.bytes_read = union_plan.nbytes
    delta.union_runs = union_plan.n_runs
    delta.gather_time_s = st.end - union_st.start
    return delta
