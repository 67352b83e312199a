"""The extraction service, with async admission in front (DESIGN.md §4).

Cache keys are *stable sha256 content hashes* and plans are
*immutable*.  Three layers build on that:

* :class:`ShardedPlanCache` — N independent :class:`PlanCache` shards
  behind a consistent-hash ring (:class:`repro.distributed.sharding.
  HashRing`).  A key's 64-bit hex prefix routes it to one shard, so
  concurrent requests for different geometries contend on different
  locks; adding a shard remaps only ~1/N of the key space and migrates
  exactly those entries.
* :class:`ShardedExtractionService` — the service: requests are
  deduped by canonical hash (found by the raw request's digest when its
  shapes were seen before), cache misses are spliced from a drifted
  neighbour or planned cold, and a batch's distinct plans are read
  through one shared union read.  Planning locks are per shard, so a
  cold miss serializes only against cold misses *on the same shard*;
  ``shards=1`` is the trivial case.  Gathers run lock-free (plans are
  immutable).  Replicas connected via :meth:`connect_peer`
  receive every cold plan over the pickled-plan wire format that
  ``repro.analysis.plan_check``'s CLI consumes, so one replica's
  planning work warms the whole fleet — verified on receipt.
* :class:`AdmissionQueue` — async admission in front of
  ``submit_batch``: callers get a ``Future`` immediately, a worker
  drains the arrival window (every ``window_s`` or at ``max_batch``)
  and serves the whole window as one batch — duplicate geometries
  coalesce into one plan lookup and one slice of one shared union read
  *across callers*, not just within a single caller's batch.

Concurrency is validated twice: statically by the lock-discipline
checker in ``repro.analysis`` (CI-gated) and dynamically by the
barrier-started thread swarms in ``tests/test_serve_concurrent.py``.
"""

from __future__ import annotations

import pickle
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, fields, replace
from typing import Any, Iterable, Sequence

from repro.core import PolytopeExtractor, Request
from repro.core.datacube import Datacube
from repro.core.delta_planner import DeltaPlanner
from repro.core.index_tree import ExtractionPlan
from repro.core.shapes import CANON_TOL
from repro.distributed.sharding import HashRing
from repro.serve.extraction import (CacheStats, KeyMemo,
                                    NeighborhoodIndex, PlanCache,
                                    ServiceResult, Stage, merge_stats,
                                    shared_union_gather)


# ---------------------------------------------------------------------------
# Plan shipping wire format
# ---------------------------------------------------------------------------

def serialize_plan(key: str, plan: ExtractionPlan,
                   n_elements: int | None = None) -> bytes:
    """Pickle a plan in the envelope ``repro.analysis --plan`` consumes
    (``{"plan": ..., "n_elements": ...}``), plus the cache key so the
    receiving replica can install it without re-canonicalizing."""
    return pickle.dumps({"plan": plan, "n_elements": n_elements,
                         "key": key})


def deserialize_plan(blob: bytes, verify: bool = True,
                     ) -> tuple[str, ExtractionPlan]:
    """Inverse of :func:`serialize_plan`; with ``verify`` the plan is
    machine-checked against its invariants before it can warm a cache —
    a corrupt or truncated shipment raises instead of installing."""
    obj = pickle.loads(blob)
    key, plan = obj["key"], obj["plan"]
    if verify:
        from repro.analysis.plan_check import verify_plan

        verify_plan(plan, n_elements=obj.get("n_elements"))
    return key, plan


# ---------------------------------------------------------------------------
# Consistent-hash-sharded plan cache
# ---------------------------------------------------------------------------

class ShardedPlanCache:
    """N :class:`PlanCache` shards behind a :class:`HashRing`.

    Reads/writes route by canonical-hash prefix and synchronize only on
    the owning shard's internal lock.  Topology changes
    (:meth:`add_shard`, :meth:`remove_shard`) are admin-plane: they
    serialize on ``_admin_lock`` and swap ring state atomically, so
    routing never observes a half-built ring.  The shard map itself is
    only ever grown via ``dict.update`` (atomic under the GIL) *before*
    the ring can route to the new shard.
    """

    def __init__(self, shards: Iterable[str] | int = 4,
                 capacity_per_shard: int = 1024):
        if isinstance(shards, int):
            shards = tuple(f"shard{i}" for i in range(shards))
        names = tuple(shards)
        if not names:
            raise ValueError("need at least one shard")
        self.capacity_per_shard = capacity_per_shard
        self._caches: dict[str, PlanCache] = {
            n: PlanCache(capacity_per_shard) for n in names}
        # Per-shard neighborhood indices, routed by *signature* hash —
        # drifted variants of one shape share a signature, so they all
        # route to the same shard's index regardless of which shards
        # their exact keys live on (parent plans fetch globally via
        # :meth:`peek`).
        self._hoods: dict[str, NeighborhoodIndex] = {
            n: NeighborhoodIndex(capacity_per_shard) for n in names}
        self.ring = HashRing(names)
        self._admin_lock = threading.Lock()

    # -- routing -----------------------------------------------------------
    @property
    def shard_names(self) -> tuple[str, ...]:
        return self.ring.nodes

    def entry_of(self, key: str) -> tuple[str, PlanCache]:
        """Route once: ``(owning shard name, its cache)``.  One route per
        operation, so a concurrent rebalance can't split an operation
        across two different owners."""
        shard = self.ring.route(key)
        return shard, self._caches[shard]

    # -- the PlanCache surface, sharded ------------------------------------
    def get(self, key: str) -> ExtractionPlan | None:
        return self.entry_of(key)[1].get(key)

    def peek(self, key: str) -> ExtractionPlan | None:
        """Uncounted cross-shard plan fetch (delta-planner parents)."""
        return self.entry_of(key)[1].peek(key)

    def hood_of(self, sig: str) -> NeighborhoodIndex:
        """Neighborhood index owning signature ``sig`` (consistent
        routing: every drifted variant of a shape resolves here)."""
        return self._hoods[self.ring.route(sig)]

    def put(self, key: str, plan: ExtractionPlan) -> None:
        self.entry_of(key)[1].put(key, plan)

    def __contains__(self, key: str) -> bool:
        return key in self.entry_of(key)[1]

    def __len__(self) -> int:
        return sum(len(c) for c in self._caches.values())

    def keys(self) -> list[str]:
        return [k for c in self._caches.values() for k in c.keys()]

    def shard_sizes(self) -> dict[str, int]:
        return {n: len(self._caches[n]) for n in self.ring.nodes}

    @property
    def stats(self) -> CacheStats:
        """Fleet-wide counters: field-wise sum of per-shard snapshots."""
        return merge_stats(c.snapshot() for c in self._caches.values())

    # -- topology ----------------------------------------------------------
    def add_shard(self, name: str) -> int:
        """Add a shard and migrate the ~1/N entries it now owns.

        Returns the number of migrated entries.  Entries planned
        concurrently with the migration may land on the old owner and be
        re-planned once on their new shard — plans are immutable and
        content-addressed, so a duplicate plan is benign.
        """
        with self._admin_lock:
            if name in self._caches:
                raise ValueError(f"shard {name!r} already exists")
            # publish the cache before the ring can route to it
            self._caches.update({name: PlanCache(self.capacity_per_shard)})
            self._hoods.update(
                {name: NeighborhoodIndex(self.capacity_per_shard)})
            self.ring.add_node(name)
            return self._migrate()

    def remove_shard(self, name: str) -> int:
        """Drain a shard: its entries migrate to their new owners.

        The drained shard's *counters* fold into a surviving shard
        before the cache object is dropped, so fleet-wide ``stats``
        conserve across topology changes (including the ``migrations``
        the drain itself just counted)."""
        with self._admin_lock:
            if name not in self._caches or len(self._caches) == 1:
                raise ValueError(f"cannot remove shard {name!r}")
            self.ring.remove_node(name)
            moved = self._migrate(drain=name)
            drained = self._caches.pop(name).snapshot()
            self._hoods.pop(name)
            survivor = self._caches[self.ring.nodes[0]]
            survivor.record(**{f.name: getattr(drained, f.name)
                               for f in fields(CacheStats)})
            return moved

    def _migrate(self, drain: str | None = None) -> int:
        """Move every entry whose ring owner changed (caller holds the
        admin mutex; per-entry moves use the shard caches' own locks).
        ``PlanCache.pop`` counts each move in the source shard's
        ``stats.migrations``; neighborhood entries reroute by signature
        alongside (uncounted — they index plans, they aren't plans)."""
        moved = 0
        for old_name in list(self._caches):
            cache = self._caches[old_name]
            for key in cache.keys():
                owner = self.ring.route(key)
                if owner == old_name and old_name != drain:
                    continue
                plan = cache.pop(key)
                if plan is not None:   # racing eviction — nothing to move
                    self._caches[owner].put(key, plan)
                    moved += 1
        for old_name in list(self._hoods):
            hood = self._hoods[old_name]
            for sig in hood.signatures():
                owner = self.ring.route(sig)
                if owner == old_name and old_name != drain:
                    continue
                entries = hood.pop_signature(sig)
                if entries:
                    self._hoods[owner].install(sig, entries)
        return moved


# ---------------------------------------------------------------------------
# Sharded service
# ---------------------------------------------------------------------------

class ShardedExtractionService:
    """Many concurrent polytope requests → deduped, cached, batched
    extraction over one datacube, with per-shard locking and
    cross-replica plan shipping.

    Plan lookups synchronize on the owning shard's cache lock, cold
    planning serializes on a per-shard planning lock (so concurrent
    misses of the *same* geometry plan once, while misses on different
    shards plan in parallel), and gather accounting takes a dedicated
    I/O lock.  Gathers themselves run lock-free — plans are immutable.

    ``verify=True`` machine-checks every cold, spliced and shared union
    plan against the invariants in ``repro.analysis.plan_check``
    (DESIGN.md §5).  ``delta=False`` plans every exact-cache miss cold:
    the reference the delta planner is held to.
    """

    def __init__(self, datacube: Datacube, shards: Iterable[str] | int = 4,
                 capacity_per_shard: int = 1024, verify: bool = False,
                 name: str = "replica0", delta: bool = True):
        self.datacube = datacube
        self.verify = verify
        self.name = name
        self.extractor = PolytopeExtractor(datacube, verify=verify)
        self.shards = ShardedPlanCache(shards, capacity_per_shard)
        # An exact-cache miss first tries a delta splice from the
        # signature-routed neighborhood before planning cold (DESIGN.md
        # §6); ineligible drifts fall through to the cold plan.
        self.delta_planner = None
        if delta:
            self.delta_planner = DeltaPlanner(datacube,
                                              slicer=self.extractor.slicer)
        self.tol = CANON_TOL
        # Cyclic-axis periods fold into the cache key: seam-straddling
        # requests shifted by whole periods hash identically, so the
        # plan cache hits across the seam (DESIGN.md §2.5).
        self.periods = datacube.axis_periods()
        # Repeated raw requests find their key without a decomposition;
        # as many keys as the plan cache holds plans.
        self._keys = KeyMemo(len(self.shards.shard_names)
                             * capacity_per_shard)
        self._plan_locks: dict[str, threading.Lock] = {
            n: threading.Lock() for n in self.shards.shard_names}
        self._peers: list[ShardedExtractionService] = []
        self._io_lock = threading.Lock()
        self.io_stats = CacheStats()

    # -- stats -------------------------------------------------------------
    @property
    def stats(self) -> CacheStats:
        """Service-wide counters: shard snapshots + gather accounting."""
        with self._io_lock:
            io = replace(self.io_stats)
        return merge_stats([self.shards.stats, io])

    # -- planning ----------------------------------------------------------
    def plan(self, request: Request) -> tuple[ExtractionPlan, bool, str]:
        res = self.submit_batch([request])[0]
        return res.plan, res.cached, res.key

    def _key_of(self, request: Request, delta: CacheStats) -> str:
        """The request's plan-cache key: from the memo where its raw
        shapes were seen before, else its canonical hash, then
        memoized.  Counts the memo's hit or miss in ``delta``."""
        digest = request.spec_digest(self.tol, self.periods)
        key = None if digest is None else self._keys.get(digest)
        if key is not None:
            delta.key_memo_hits += 1
            return key
        delta.key_memo_misses += 1
        key = request.canonical_hash(self.tol, self.periods)
        if digest is not None:
            self._keys.put(digest, key)
        return key

    def _plan_one(self, request: Request, key: str):
        shard, cache = self.shards.entry_of(key)
        # Uncounted membership probe first, so the counted lookup below
        # runs exactly once per request (a double-check get would score
        # every cold plan as two misses and skew the hit rate).
        if key in cache:
            plan = cache.get(key)
            if plan is not None:
                return plan, True, key, None
        lock = self._plan_locks.setdefault(shard, threading.Lock())
        with lock:
            plan = cache.get(key)   # counted; did a racing thread win?
            if plan is not None:
                return plan, True, key, None
            spliced = None
            if self.delta_planner is not None:
                spliced = self._try_delta(request, key, cache)
            if spliced is not None:
                plan, sstats = spliced
            else:
                with Stage("polytope.planner.cold") as st:
                    plan, sstats = self.extractor.plan(request)
                cache.record(plan_time_s=st.seconds)
                cache.put(key, plan)
                self._index_neighbor(request, key, sstats)
        self._ship(key, plan)
        return plan, False, key, sstats

    def _try_delta(self, request: Request, key: str, cache: PlanCache):
        """Splice from a drifted neighbor (caller holds the shard's
        plan lock).  The signature routes to one shard's neighborhood;
        parent plans fetch cross-shard by their exact keys.  Returns
        ``(plan, stats)`` or ``None`` (→ plan cold)."""
        with Stage("polytope.planner.delta") as st:
            sig, anchor = request.shape_signature(self.tol)
            hood = self.shards.hood_of(sig)
            for entry in hood.candidates(sig):
                shifts = self.delta_planner.axis_shifts(entry.anchor, anchor)
                if shifts is None:
                    continue
                parent = self.shards.peek(entry.key)
                if parent is None:
                    continue   # parent evicted under the index entry
                out = self.delta_planner.splice(request, entry.request,
                                                parent, entry.stats, shifts)
                if out is None:
                    continue
                plan, stats = out
                if self.verify:
                    from repro.analysis.plan_check import verify_plan

                    verify_plan(plan, datacube=self.datacube, stats=stats)
                cache.put(key, plan)
                hood.add(sig, key, anchor, request, stats)
                cache.record(delta_hits=1,
                             delta_time_s=time.perf_counter() - st.start)
                return plan, stats
            cache.record(delta_misses=1)
            return None

    def _index_neighbor(self, request: Request, key: str,
                        stats) -> None:
        if self.delta_planner is None or stats is None:
            return
        sig, anchor = request.shape_signature(self.tol)
        self.shards.hood_of(sig).add(sig, key, anchor, request, stats)

    # -- batched serving ---------------------------------------------------
    def extract(self, request: Request,
                flat_data: Any | None = None) -> ServiceResult:
        return self.submit_batch([request], flat_data)[0]

    def submit_batch(self, requests: Sequence[Request],
                     flat_data: Any | None = None) -> list[ServiceResult]:
        """Serve a batch of concurrent requests.

        Requests are deduped by canonical hash (one plan per distinct
        geometry), missed plans are spliced or planned once, and — when
        ``flat_data`` is given — all distinct plans are gathered through
        a single coalesced union read shared across the batch.  No
        global lock is held on the planning path."""
        results: list[ServiceResult] = []
        batch_plans: dict[str, ExtractionPlan] = {}
        delta = CacheStats()
        with Stage("polytope.plan_cache.lookup", delta, "lookup_time_s"):
            for req in requests:
                key = self._key_of(req, delta)
                if key in batch_plans:
                    self.shards.entry_of(key)[1].record(batch_dedup=1)
                    results.append(ServiceResult(
                        request=req, key=key, plan=batch_plans[key],
                        cached=True))
                    continue
                plan, cached, key, sstats = self._plan_one(req, key)
                batch_plans[key] = plan
                results.append(ServiceResult(
                    request=req, key=key, plan=plan, cached=cached,
                    stats=sstats))
        parts = [delta]
        if flat_data is not None:
            parts.append(shared_union_gather(
                self.datacube, results, batch_plans, flat_data,
                verify=self.verify))
        with self._io_lock:
            merge_stats(parts, into=self.io_stats)
        return results

    # -- topology ----------------------------------------------------------
    def add_shard(self, name: str) -> int:
        """Grow the ring; returns the number of migrated cache entries."""
        self._plan_locks.setdefault(name, threading.Lock())
        return self.shards.add_shard(name)

    # -- cross-replica plan shipping ---------------------------------------
    def connect_peer(self, peer: "ShardedExtractionService") -> None:
        """Subscribe ``peer`` to this replica's cold plans (one-way;
        call on both services for symmetric warming)."""
        if peer is self:
            raise ValueError("a replica cannot peer with itself")
        self._peers.append(peer)

    def _ship(self, key: str, plan: ExtractionPlan) -> None:
        if not self._peers:
            return
        blob = serialize_plan(key, plan,
                              n_elements=self.datacube.n_elements)
        shipped = 0
        for peer in tuple(self._peers):
            peer.receive_plan(blob)
            shipped += 1
        self.shards.entry_of(key)[1].record(plans_shipped=shipped)

    def receive_plan(self, blob: bytes) -> str:
        """Install a peer's shipped plan (verified when ``verify``);
        returns the installed cache key."""
        key, plan = deserialize_plan(blob, verify=self.verify)
        _, cache = self.shards.entry_of(key)
        cache.put(key, plan)
        cache.record(plans_received=1)
        return key


# ---------------------------------------------------------------------------
# Async admission
# ---------------------------------------------------------------------------

@dataclass
class AdmissionStats:
    """Arrival-window coalescing instrumentation."""

    submitted: int = 0      # requests accepted into the queue
    served: int = 0         # futures resolved
    windows: int = 0        # batches drained
    coalesced: int = 0      # duplicate geometries folded within windows
    window_max: int = 0     # largest window drained
    wait_s: float = 0.0     # summed submit → drain wait of drained requests

    @property
    def coalescing_factor(self) -> float:
        """served / distinct-planned ≥ 1: cross-caller sharing per
        window (1.0 = no duplicate geometry ever coalesced)."""
        distinct = self.served - self.coalesced
        return self.served / distinct if distinct else 1.0


class AdmissionQueue:
    """Async admission in front of a service's ``submit_batch``.

    Callers :meth:`submit` a request and immediately get a ``Future``.
    A worker thread drains the pending window whenever ``window_s``
    elapses or ``max_batch`` requests accumulate, and serves the whole
    window as one batch — so identical geometries arriving from
    *different* callers within a window coalesce into one plan lookup
    and one slice of one shared union read.
    """

    def __init__(self, service: Any, flat_data: Any | None = None,
                 window_s: float = 0.002, max_batch: int = 64):
        if window_s <= 0:
            raise ValueError("window_s must be positive")
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.service = service
        self.flat_data = flat_data
        self.window_s = window_s
        self.max_batch = max_batch
        self.stats = AdmissionStats()
        self._pending: list[tuple[Request, Future]] = []
        self._stamps: list[float] = []   # perf_counter at each submit
        self._closed = False
        self._lock = threading.Condition()
        self._worker = threading.Thread(target=self._run,
                                        name="admission-worker",
                                        daemon=True)
        self._worker.start()

    # -- caller side -------------------------------------------------------
    def submit(self, request: Request) -> "Future[ServiceResult]":
        """Enqueue; the future resolves with the window's
        :class:`ServiceResult` for this request."""
        fut: Future = Future()
        with self._lock:
            if self._closed:
                raise RuntimeError("AdmissionQueue is closed")
            self._pending.append((request, fut))
            self._stamps.append(time.perf_counter())
            self._lock.notify_all()
        return fut

    def extract(self, request: Request,
                timeout: float | None = None) -> ServiceResult:
        """Synchronous convenience: submit and wait."""
        return self.submit(request).result(timeout)

    def snapshot(self) -> AdmissionStats:
        with self._lock:
            return replace(self.stats)

    # -- worker side -------------------------------------------------------
    def _run(self) -> None:
        while True:
            with self._lock:
                while not self._pending and not self._closed:
                    self._lock.wait()
                if self._closed and not self._pending:
                    return
                # Window open: wait out the arrival window (or fill up),
                # then drain everything that accumulated.
                with Stage("polytope.admission.collect"):
                    deadline = time.monotonic() + self.window_s
                    while (len(self._pending) < self.max_batch
                           and not self._closed):
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._lock.wait(remaining)
                drained = time.perf_counter()
                window, stamps = self._pending, self._stamps
                self._pending, self._stamps = [], []
                self.stats.submitted += len(window)
                self.stats.wait_s += sum(drained - t for t in stamps)
                self.stats.windows += 1
                self.stats.window_max = max(self.stats.window_max,
                                            len(window))
                n = self.stats.windows
            with Stage("polytope.window", window=n, requests=len(window)):
                self._serve_window(window)

    def _serve_window(self,
                      window: list[tuple[Request, Future]]) -> None:
        """Serve one drained window as a single batch (no admission lock
        held: planning/gather contend only on the service's locks)."""
        requests = [req for req, _ in window]
        try:
            results = self.service.submit_batch(requests, self.flat_data)
        except BaseException as e:
            for _, fut in window:
                fut.set_exception(e)
            return
        distinct = len({r.key for r in results})
        with self._lock:
            self.stats.served += len(results)
            self.stats.coalesced += len(results) - distinct
        for (_, fut), res in zip(window, results):
            fut.set_result(res)

    # -- lifecycle ---------------------------------------------------------
    def close(self, timeout: float | None = 10.0) -> None:
        """Drain remaining requests, then stop the worker."""
        with self._lock:
            self._closed = True
            self._lock.notify_all()
        self._worker.join(timeout)

    def __enter__(self) -> "AdmissionQueue":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()
