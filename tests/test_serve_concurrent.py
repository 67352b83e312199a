"""Concurrency stress suite for the sharded extraction service
(DESIGN.md §4).

Barrier-started thread swarms hammer ``submit_batch`` and the
``AdmissionQueue`` with duplicate, seam-shifted, and disjoint requests;
every served value must be byte-identical to a fresh single-threaded
``PolytopeExtractor`` extraction, and the stats accounting must stay
consistent under contention (``lookups == hits + misses``,
coalesced ≤ submitted).  The shard-rebalance tests pin the consistent
hashing guarantee: adding a shard remaps only ~1/N of the key space,
and every remapped key moves *to the new shard*.

Swarm scale comes from env knobs so the CI fast lane runs a reduced
swarm while the scheduled lane runs the full one:

    REPRO_STRESS_THREADS   threads per swarm (default 8)
    REPRO_STRESS_ITERS     batches per thread (default 4)
"""

import hashlib
import os
import sys
import threading

import numpy as np
import pytest

from repro.core import PolytopeExtractor, Request, gather
from repro.dataplane.weather import (IrregularWeatherCube, WeatherCube,
                                     request_population)
from repro.serve.extraction import PlanCache
from repro.serve.sharded import (AdmissionQueue, ShardedExtractionService,
                                 ShardedPlanCache, deserialize_plan,
                                 serialize_plan)

N_THREADS = max(int(os.environ.get("REPRO_STRESS_THREADS", "8")), 2)
N_ITERS = max(int(os.environ.get("REPRO_STRESS_ITERS", "4")), 1)


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------

def run_swarm(n_threads, fn):
    """Start ``n_threads`` threads on a barrier (maximal contention at
    t=0) and re-raise the first exception any of them hit."""
    barrier = threading.Barrier(n_threads)
    errors = []

    def wrapped(tid):
        try:
            barrier.wait(timeout=30)
            fn(tid)
        except BaseException as e:   # noqa: BLE001 — surface everything
            errors.append(e)

    threads = [threading.Thread(target=wrapped, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads), "swarm deadlocked"
    if errors:
        raise errors[0]


def reference_values(cube, data, requests):
    """Fresh single-threaded extractions — the byte-identity oracle."""
    ex = PolytopeExtractor(cube)
    out = []
    for req in requests:
        plan, _ = ex.plan(req)
        out.append(gather(data, plan))
    return out


@pytest.fixture(scope="module")
def weather():
    wc = WeatherCube(n=16, n_times=2, n_levels=2)
    return wc, wc.field_data(seed=0), request_population(wc)


@pytest.fixture(scope="module")
def irregular():
    icw = IrregularWeatherCube(n_lat=24, n_lon=48)
    return icw, icw.field_data(seed=1)


# ---------------------------------------------------------------------------
# submit_batch under contention
# ---------------------------------------------------------------------------

class TestSubmitBatchSwarm:
    def _mix(self, population, tid):
        """Per-thread request mix: duplicates + disjoint geometries,
        rotated per thread so threads collide on *some* keys."""
        k = len(population)
        picks = [population[(tid + j) % k] for j in range(6)]
        return picks + picks[:2]   # in-batch duplicates

    @pytest.mark.parametrize("shards", [1, 4])
    def test_sharded_byte_identity_and_stats(self, weather, shards):
        """One shard is the trivial case of the same service: one cache
        and one planning lock, raced by every thread."""
        wc, data, population = weather
        svc = ShardedExtractionService(wc.cube, shards=shards)
        refs = {id(r): v for r, v in
                zip(population, reference_values(wc.cube, data, population))}

        def worker(tid):
            for _ in range(N_ITERS):
                batch = self._mix(population, tid)
                results = svc.submit_batch(batch, data)
                assert len(results) == len(batch)
                for req, res in zip(batch, results):
                    assert res.request is req
                    assert np.array_equal(res.values, refs[id(req)])

        run_swarm(N_THREADS, worker)
        s = svc.stats
        assert s.lookups == s.hits + s.misses
        # per-shard planning locks: each distinct geometry planned once,
        # no matter how many threads raced on it
        covered = {(tid + j) % len(population)
                   for tid in range(N_THREADS) for j in range(6)}
        distinct = len({population[i].canonical_hash(svc.tol, svc.periods)
                        for i in covered})
        assert s.misses == distinct
        assert len(svc.shards) == distinct
        # 2 in-batch duplicates per batch, every batch
        assert s.batch_dedup == 2 * N_THREADS * N_ITERS
        # every request's key came from the memo or from its hash
        assert (s.key_memo_hits + s.key_memo_misses
                == s.lookups + s.batch_dedup == 8 * N_THREADS * N_ITERS)

    def test_fresh_request_objects_find_memoized_keys(self, weather):
        """Each submission a new ``Request`` over the same shapes, as
        arrivals are: keys come from the memo, not from a decomposition
        of each object, and the counts still add up under contention."""
        wc, data, population = weather
        svc = ShardedExtractionService(wc.cube, shards=4)
        hot = population[:6]
        refs = reference_values(wc.cube, data, hot)
        keys = [r.canonical_hash(svc.tol, svc.periods) for r in hot]

        def worker(tid):
            for j in range(N_ITERS):
                picks = [(tid + j + k) % len(hot) for k in range(4)]
                batch = [Request(list(hot[i].shapes)) for i in picks]
                for i, res in zip(picks, svc.submit_batch(batch, data)):
                    assert res.key == keys[i]
                    assert np.array_equal(res.values, refs[i])

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            run_swarm(N_THREADS, worker)
        finally:
            sys.setswitchinterval(interval)
        s = svc.stats
        total = 4 * N_THREADS * N_ITERS
        assert (s.key_memo_hits + s.key_memo_misses
                == s.lookups + s.batch_dedup == total)
        assert s.key_memo_misses >= len(hot)
        assert s.key_memo_hits > 0 and len(svc._keys) == len(hot)

    @pytest.mark.parametrize("shards", [1, 4])
    def test_seam_shifted_requests_share_one_plan(self, irregular, shards):
        """Period-shifted seam crops hash identically, so a swarm half
        on lon −15…15 and half on lon 345…375 contends on ONE cache
        entry — and both halves read byte-identical values."""
        icw, data = irregular
        svc = ShardedExtractionService(icw.cube, shards=shards)
        base = icw.seam_box_request(40.0, 60.0, -15.0, 15.0)
        shifted = icw.seam_box_request(40.0, 60.0, 345.0, 375.0)
        assert (base.canonical_hash(svc.tol, svc.periods)
                == shifted.canonical_hash(svc.tol, svc.periods))
        ref = reference_values(icw.cube, data, [base])[0]
        assert ref.size > 0

        def worker(tid):
            req = base if tid % 2 == 0 else shifted
            for _ in range(N_ITERS):
                res = svc.extract(req, data)
                assert np.array_equal(res.values, ref)

        run_swarm(N_THREADS, worker)
        s = svc.stats
        assert s.misses == 1           # one plan for both seam phrasings
        assert s.hits == N_THREADS * N_ITERS - 1
        assert s.lookups == s.hits + s.misses
        assert s.key_memo_hits + s.key_memo_misses == N_THREADS * N_ITERS


# ---------------------------------------------------------------------------
# Async admission
# ---------------------------------------------------------------------------

class TestAdmissionQueue:
    def test_swarm_coalesces_across_callers(self, weather):
        wc, data, population = weather
        hot = population[:4]
        refs = reference_values(wc.cube, data, hot)
        svc = ShardedExtractionService(wc.cube, shards=4)

        with AdmissionQueue(svc, flat_data=data, window_s=0.005,
                            max_batch=256) as queue:
            def worker(tid):
                for j in range(N_ITERS):
                    i = (tid + j) % len(hot)
                    res = queue.extract(hot[i], timeout=60)
                    assert np.array_equal(res.values, refs[i])

            run_swarm(N_THREADS, worker)
            adm = queue.snapshot()

        total = N_THREADS * N_ITERS
        assert adm.submitted == total
        assert adm.served == total
        s = svc.stats
        assert (s.key_memo_hits + s.key_memo_misses
                == s.lookups + s.batch_dedup == total)
        assert 0 <= adm.coalesced <= adm.submitted
        assert adm.windows >= 1
        assert adm.coalescing_factor >= 1.0
        # N_THREADS barrier-released threads over 4 hot keys: the first
        # window alone must fold duplicates across callers
        if N_THREADS > len(hot):
            assert adm.coalesced > 0

    def test_futures_resolve_out_of_band(self, weather):
        wc, data, population = weather
        svc = ShardedExtractionService(wc.cube, shards=2)
        queue = AdmissionQueue(svc, flat_data=data, window_s=0.001)
        futs = [queue.submit(population[i % 5]) for i in range(16)]
        refs = reference_values(wc.cube, data, population[:5])
        # futures resolve to ServiceResults carrying the right bytes
        for i, fut in enumerate(futs):
            assert np.array_equal(fut.result(timeout=60).values,
                                  refs[i % 5])
        queue.close()

    def test_submit_after_close_raises(self, weather):
        wc, data, population = weather
        queue = AdmissionQueue(ShardedExtractionService(wc.cube, shards=2),
                               flat_data=data)
        queue.close()
        with pytest.raises(RuntimeError):
            queue.submit(population[0])

    def test_service_error_propagates_to_futures(self, weather):
        _, _, population = weather

        class Exploding:
            def submit_batch(self, requests, flat_data=None):
                raise ValueError("boom")

        with AdmissionQueue(Exploding(), window_s=0.001) as queue:
            fut = queue.submit(population[0])
            with pytest.raises(ValueError, match="boom"):
                fut.result(timeout=30)


# ---------------------------------------------------------------------------
# verify=True under the admission path (plan_check on every union plan)
# ---------------------------------------------------------------------------

class TestVerifiedAdmission:
    def test_irregular_stress_roundtrip_verified(self, irregular):
        """Every cold plan AND every coalesced window's union plan runs
        ``plan_check.verify_plan`` (verify=True raises on violation) —
        the async analogue of PR 4's synchronous verify coverage."""
        icw, data = irregular
        svc = ShardedExtractionService(icw.cube, shards=4, verify=True)
        requests = [
            icw.country_request("uk"),
            icw.country_request("france"),
            icw.seam_box_request(40.0, 60.0, -15.0, 15.0),
            icw.seam_box_request(40.0, 60.0, 345.0, 375.0),
            icw.timeseries_request(float(icw.latitudes[5]),
                                   float(icw.lon_values[4]),
                                   0.0, 100000.0),
        ]
        refs = reference_values(icw.cube, data, requests)

        with AdmissionQueue(svc, flat_data=data, window_s=0.005,
                            max_batch=128) as queue:
            def worker(tid):
                for j in range(N_ITERS):
                    i = (tid + j) % len(requests)
                    res = queue.extract(requests[i], timeout=60)
                    assert np.array_equal(res.values, refs[i])

            run_swarm(N_THREADS, worker)
            adm = queue.snapshot()
        assert adm.served == N_THREADS * N_ITERS
        assert svc.stats.lookups == svc.stats.hits + svc.stats.misses


# ---------------------------------------------------------------------------
# Shard rebalance: the consistent-hashing contract
# ---------------------------------------------------------------------------

def _synthetic_keys(n, seed):
    rng = np.random.default_rng(seed)
    return [hashlib.sha256(rng.bytes(16)).hexdigest() for _ in range(n)]


class TestShardRebalance:
    N_KEYS = 2000
    SEED = 1234

    def test_add_shard_remaps_about_one_over_n(self):
        cache = ShardedPlanCache(shards=4, capacity_per_shard=self.N_KEYS)
        keys = _synthetic_keys(self.N_KEYS, self.SEED)
        for i, k in enumerate(keys):
            cache.put(k, f"plan-{i}")
        before = {k: cache.entry_of(k)[0] for k in keys}

        moved = cache.add_shard("shard4")
        after = {k: cache.entry_of(k)[0] for k in keys}

        remapped = [k for k in keys if before[k] != after[k]]
        frac = len(remapped) / self.N_KEYS
        # ideal 1/5 = 0.20; 64 virtual points keeps it in a tight band
        assert 0.10 <= frac <= 0.35, f"remap fraction {frac:.3f}"
        # consistent hashing: keys only ever move TO the new shard
        assert all(after[k] == "shard4" for k in remapped)
        assert moved == len(remapped)
        # no entry lost in migration
        for i, k in enumerate(keys):
            assert cache.get(k) == f"plan-{i}"
        assert len(cache) == self.N_KEYS

    def test_migrations_counter_matches_moved(self):
        """Regression: ``PlanCache.pop`` must count each entry actually
        drained during rebalance, so fleet-wide ``stats.migrations``
        equals the migration report — and nothing else inflates it."""
        cache = ShardedPlanCache(shards=4, capacity_per_shard=self.N_KEYS)
        keys = _synthetic_keys(500, self.SEED + 2)
        for i, k in enumerate(keys):
            cache.put(k, i)
        assert cache.stats.migrations == 0
        moved = cache.add_shard("shard4")
        assert cache.stats.migrations == moved
        moved_back = cache.remove_shard("shard4")
        # remove_shard folds the drained shard's counters into a
        # survivor, so the add-phase migrations are preserved too
        assert cache.stats.migrations == moved + moved_back
        for i, k in enumerate(keys):
            assert cache.get(k) == i

    def test_remove_shard_conserves_stats(self):
        cache = ShardedPlanCache(shards=3, capacity_per_shard=self.N_KEYS)
        keys = _synthetic_keys(300, self.SEED + 3)
        for i, k in enumerate(keys):
            cache.put(k, i)
        for k in keys:
            cache.get(k)
        hits_before = cache.stats.hits
        cache.add_shard("doomed")
        cache.remove_shard("doomed")
        assert cache.stats.hits == hits_before

    def test_last_shard_cannot_be_removed(self):
        """One shard is the least a cache holds: draining it would leave
        the ring with nowhere to route, so it is refused and the entry
        stays where it was."""
        cache = ShardedPlanCache(1)
        key = _synthetic_keys(1, self.SEED)[0]
        cache.put(key, "p")
        with pytest.raises(ValueError, match="shard0"):
            cache.remove_shard("shard0")
        assert cache.shard_names == ("shard0",)
        assert cache.get(key) == "p"

    def test_add_then_remove_restores_routing(self):
        cache = ShardedPlanCache(shards=4, capacity_per_shard=self.N_KEYS)
        keys = _synthetic_keys(500, self.SEED + 1)
        for i, k in enumerate(keys):
            cache.put(k, i)
        before = {k: cache.entry_of(k)[0] for k in keys}
        cache.add_shard("extra")
        cache.remove_shard("extra")
        assert {k: cache.entry_of(k)[0] for k in keys} == before
        for i, k in enumerate(keys):
            assert cache.get(k) == i

    def test_rebalance_under_concurrent_service_load(self, weather):
        """Adding a shard mid-swarm never serves wrong bytes."""
        wc, data, population = weather
        svc = ShardedExtractionService(wc.cube, shards=3)
        refs = reference_values(wc.cube, data, population)
        stop = threading.Event()

        def admin(tid):
            if tid == 0:
                svc.add_shard("late-shard")
                stop.set()
                return
            j = 0
            while not stop.is_set() or j < len(population):
                i = (tid + j) % len(population)
                res = svc.extract(population[i], data)
                assert np.array_equal(res.values, refs[i])
                j += 1
                if j > 10 * len(population):
                    break

        run_swarm(max(N_THREADS, 3), admin)
        assert "late-shard" in svc.shards.shard_names


# ---------------------------------------------------------------------------
# PlanCache reader/writer races (regression for the unsynchronized
# keys()/__contains__ reads — the static fixture lives in
# tests/test_analysis.py, this is the live hammer)
# ---------------------------------------------------------------------------

class TestPlanCacheConcurrentReads:
    def test_keys_and_contains_race_concurrent_eviction(self):
        cache = PlanCache(capacity=8)

        def worker(tid):
            if tid % 2 == 0:
                for i in range(500):
                    cache.put(f"k{tid}-{i}", i)
            else:
                for _ in range(500):
                    ks = cache.keys()       # iterates the OrderedDict
                    assert len(ks) <= 8
                    for k in ks[:2]:
                        k in cache          # noqa: B015 — probe only
                    len(cache)

        # pre-lock, this raised "OrderedDict mutated during iteration"
        run_swarm(N_THREADS, worker)
        assert len(cache) <= 8
        s = cache.snapshot()
        assert s.evictions > 0


# ---------------------------------------------------------------------------
# Cross-replica plan shipping
# ---------------------------------------------------------------------------

class TestPlanShipping:
    def test_wire_roundtrip(self, weather):
        wc, _, population = weather
        svc = ShardedExtractionService(wc.cube, shards=2)
        plan, _, key = svc.plan(population[0])
        key2, plan2 = deserialize_plan(
            serialize_plan(key, plan, n_elements=wc.cube.n_elements))
        assert key2 == key
        assert np.array_equal(plan2.offsets, plan.offsets)

    def test_corrupt_shipment_rejected(self, weather):
        from repro.analysis.plan_check import PlanVerificationError

        wc, _, population = weather
        svc = ShardedExtractionService(wc.cube, shards=2)
        plan, _, key = svc.plan(population[0])
        bad = type(plan)(
            offsets=plan.offsets + wc.cube.n_elements,   # out of bounds
            run_starts=plan.run_starts, run_lengths=plan.run_lengths,
            coords={}, itemsize=plan.itemsize)
        blob = serialize_plan(key, bad, n_elements=wc.cube.n_elements)
        with pytest.raises(PlanVerificationError):
            deserialize_plan(blob, verify=True)

    def test_swarm_on_one_replica_warms_the_peer(self, weather):
        wc, data, population = weather
        primary = ShardedExtractionService(wc.cube, shards=4,
                                           name="replica0")
        peer = ShardedExtractionService(wc.cube, shards=4,
                                        name="replica1")
        primary.connect_peer(peer)

        def worker(tid):
            for j in range(N_ITERS):
                primary.extract(population[(tid + j) % len(population)])

        run_swarm(N_THREADS, worker)
        covered = sorted({(tid + j) % len(population)
                          for tid in range(N_THREADS)
                          for j in range(N_ITERS)})
        expected_keys = {population[i].canonical_hash(primary.tol,
                                                      primary.periods)
                         for i in covered}
        assert peer.stats.plans_received == len(expected_keys)
        assert primary.stats.plans_shipped == peer.stats.plans_received
        # the peer never plans: every request the primary saw is warm
        refs = reference_values(wc.cube, data, population)
        for i in covered:
            res = peer.extract(population[i], data)
            assert res.cached
            assert np.array_equal(res.values, refs[i])
        assert peer.stats.misses == 0
