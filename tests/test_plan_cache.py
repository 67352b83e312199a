"""Plan-cache semantics: canonical hashing, LRU behaviour, and the
extraction service (DESIGN.md §4).

The canonical-hash invariants are also checked property-style at the
bottom of this module: a seeded-rng class that always runs, and a
hypothesis class that deepens the search when hypothesis is installed
(skipped cleanly otherwise — the container does not ship it)."""

import numpy as np
import pytest

from repro.core import (Box, ConvexPolytope, Disk, OrderedAxis, Request,
                        Select, Slicer, Span, TensorDatacube, Union)
from repro.dataplane.pipeline import CachedExtractionSource, Prefetcher
from repro.serve.extraction import PlanCache
from repro.serve.sharded import ShardedExtractionService

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def small_cube(n=12, names=("a", "b", "c")):
    return TensorDatacube(
        [OrderedAxis(nm, np.arange(float(n))) for nm in names])


def tri_request(shift=0.0):
    verts = np.array([[0.0, 0.0], [8.0, 0.0], [0.0, 8.0]]) + shift
    return Request([ConvexPolytope(("a", "b"), verts),
                    Select("c", [1.0, 3.0])])


class TestCanonicalHash:
    def test_permuted_union_members_collide(self):
        s1 = Box(("a", "b"), [0, 0], [3, 3])
        s2 = Disk(("a", "b"), (6.0, 6.0), 2.0)
        r_ab = Request([Union([s1, s2])])
        r_ba = Request([Union([s2, s1])])
        assert r_ab.canonical_hash() == r_ba.canonical_hash()
        assert r_ab.canonical_form() == r_ba.canonical_form()

    def test_permuted_select_values_collide(self):
        r1 = Request([Select("c", [3.0, 1.0, 2.0])])
        r2 = Request([Select("c", [1.0, 2.0, 3.0])])
        r3 = Request([Select("c", [1.0]), Select("c", [3.0, 2.0])])
        assert r1.canonical_hash() == r2.canonical_hash()
        assert r1.canonical_hash() == r3.canonical_hash()

    def test_duplicate_members_and_values_collide(self):
        s = Box(("a", "b"), [0, 0], [3, 3])
        assert (Request([Union([s, s]), Select("c", [1, 1])])
                .canonical_hash() ==
                Request([s, Select("c", [1])]).canonical_hash())

    def test_tolerance_quantized_vertices_collide(self):
        base = np.array([[0.0, 0.0], [8.0, 0.0], [0.0, 8.0]])
        jitter = base + 1e-13          # far below the quantum
        assert (Request([ConvexPolytope(("a", "b"), base)]).canonical_hash()
                == Request([ConvexPolytope(("a", "b"),
                                           jitter)]).canonical_hash())

    def test_geometrically_distinct_differ(self):
        assert (tri_request(0.0).canonical_hash()
                != tri_request(1.0).canonical_hash())
        assert (Request([Span("a", 0, 5)]).canonical_hash()
                != Request([Span("b", 0, 5)]).canonical_hash())
        assert (Request([Select("c", [1.0])]).canonical_hash()
                != Request([Select("c", [2.0])]).canonical_hash())

    def test_box_and_equivalent_polytope_collide(self):
        # is_box is an execution detail, not geometry — same plan bytes.
        box = Box(("a", "b"), [1, 2], [4, 5])
        verts = np.array([[1, 2], [1, 5], [4, 2], [4, 5]], float)
        assert (Request([box]).canonical_hash()
                == Request([ConvexPolytope(("a", "b"),
                                           verts)]).canonical_hash())

    def test_hash_is_stable_content_hash(self):
        # Process-independent: a fixed request pins its digest format.
        h = Request([Span("a", 0.0, 2.0)]).canonical_hash()
        assert isinstance(h, str) and len(h) == 64
        assert h == Request([Span("a", 0.0, 2.0)]).canonical_hash()


class TestPlanCacheLRU:
    def test_eviction_order_is_lru(self):
        pc = PlanCache(capacity=2)
        pc.put("k1", "p1")
        pc.put("k2", "p2")
        assert pc.get("k1") == "p1"        # k1 becomes MRU
        pc.put("k3", "p3")                 # evicts k2, not k1
        assert "k2" not in pc
        assert "k1" in pc and "k3" in pc
        assert pc.stats.evictions == 1

    def test_counters(self):
        pc = PlanCache(capacity=4)
        assert pc.get("missing") is None
        pc.put("k", "p")
        assert pc.get("k") == "p"
        assert pc.stats.hits == 1
        assert pc.stats.misses == 1
        assert pc.stats.hit_rate == 0.5


class TestExtractionService:
    def test_repeat_request_served_from_cache(self):
        svc = ShardedExtractionService(small_cube(), shards=1)
        cold = svc.extract(tri_request())
        assert not cold.cached
        assert cold.stats is not None            # cold plan ran Alg. 1
        warm = svc.extract(tri_request())
        assert warm.cached
        assert warm.stats is None                # no new SliceStats
        assert svc.stats.hits == 1 and svc.stats.misses == 1
        # byte-identical offsets: the exact plan object is shared
        assert warm.plan is cold.plan
        np.testing.assert_array_equal(warm.plan.offsets, cold.plan.offsets)

    def test_hit_offsets_match_independent_cold_plan(self):
        cube = small_cube()
        svc = ShardedExtractionService(cube, shards=1)
        svc.extract(tri_request())
        hit = svc.extract(tri_request())
        ref, _ = Slicer(cube).extract_plan(tri_request())
        np.testing.assert_array_equal(hit.plan.offsets, ref.offsets)

    def test_batch_dedupes_and_shares_reads(self):
        cube = small_cube()
        data = np.arange(cube.n_elements, dtype=np.float64)
        svc = ShardedExtractionService(cube, shards=1)
        reqs = [tri_request(), tri_request(), tri_request(1.0)]
        results = svc.submit_batch(reqs, data)
        assert svc.stats.misses == 2             # two distinct geometries
        assert svc.stats.batch_dedup == 1        # in-batch duplicate
        assert results[1].plan is results[0].plan
        for res in results:
            np.testing.assert_array_equal(res.values,
                                          data[res.plan.offsets])
        # overlapping requests read shared bytes once
        assert svc.stats.bytes_read < svc.stats.bytes_requested
        assert svc.stats.sharing_factor > 1.0

    def test_equivalent_permuted_batch_members_hit(self):
        svc = ShardedExtractionService(small_cube(), shards=1)
        s1 = Box(("a", "b"), [0, 0], [3, 3])
        s2 = Disk(("a", "b"), (6.0, 6.0), 2.0)
        svc.extract(Request([Union([s1, s2])]))
        res = svc.extract(Request([Union([s2, s1])]))
        assert res.cached

    def test_lru_eviction_end_to_end(self):
        svc = ShardedExtractionService(small_cube(), shards=1,
                                       capacity_per_shard=2)
        r1, r2, r3 = tri_request(0.0), tri_request(1.0), tri_request(2.0)
        svc.extract(r1)
        svc.extract(r2)
        svc.extract(r1)                  # r1 MRU → order [r2, r1]
        svc.extract(r3)                  # evicts LRU r2 → [r1, r3]
        assert svc.stats.evictions == 1
        assert svc.extract(r1).cached
        assert svc.extract(r3).cached
        assert not svc.extract(r2).cached    # r2 was evicted

    def test_empty_plan_values(self):
        cube = small_cube()
        data = np.arange(cube.n_elements, dtype=np.float64)
        svc = ShardedExtractionService(cube, shards=1)
        # box entirely outside the grid → empty plan
        res = svc.extract(Request([Box(("a", "b"), [50, 50], [60, 60])]),
                          data)
        assert res.plan.n_points == 0
        assert len(res.values) == 0


class TestPrefetcherReusesPlans:
    def test_plans_cached_across_steps(self):
        cube = small_cube()
        data = np.arange(cube.n_elements, dtype=np.float64)
        svc = ShardedExtractionService(cube, shards=1)
        # recurring production mix: step alternates between two crops
        crops = [tri_request(0.0), tri_request(2.0)]
        src = CachedExtractionSource(svc, lambda s: crops[s % 2], data)
        pf = Prefetcher(src, depth=2)
        out = [next(pf) for _ in range(6)]
        pf.close()
        assert [s for s, _ in out] == list(range(6))
        assert svc.stats.misses == 2             # one cold plan per crop
        assert svc.stats.hits >= 4               # later steps all hit
        ref, _ = Slicer(cube).extract_plan(crops[0])
        np.testing.assert_array_equal(out[4][1].values, data[ref.offsets])


# ---------------------------------------------------------------------------
# Property-style canonical-hash invariants (ROADMAP: cache-key hardening).
# Base coordinates sit on the integer grid so CANON_TOL (1e-9) quantization
# is exact; jitter ≤ 2e-10 stays inside one quantum, 1e-6 jumps ~1000.
# ---------------------------------------------------------------------------

def _member(kind, p):
    """Small 2-D shape from 4 integer params (quantization-stable)."""
    p = [float(v) for v in p]
    if kind == 0:
        return Box(("a", "b"), [p[0], p[1]], [p[0] + p[2], p[1] + p[3]])
    if kind == 1:
        return Disk(("a", "b"), (p[0], p[1]), 1.0 + p[2])
    return ConvexPolytope(("a", "b"), np.array(
        [[p[0], p[1]], [p[0] + p[2], p[1]], [p[0], p[1] + p[3]]]))


_TRI = np.array([[0.0, 0.0], [7.0, 0.0], [0.0, 7.0]])


class TestCanonicalHashSeededProperties:
    """Seeded-rng versions of the hypothesis properties below — always run."""

    def test_union_member_permutation_collides(self):
        rng = np.random.default_rng(7)
        for _ in range(25):
            n = int(rng.integers(2, 5))
            members = [_member(int(rng.integers(0, 3)),
                               rng.integers(0, 6, size=4))
                       for _ in range(n)]
            perm = [members[i] for i in rng.permutation(n)]
            assert (Request([Union(members), Select("c", [1.0])])
                    .canonical_hash() ==
                    Request([Union(perm), Select("c", [1.0])])
                    .canonical_hash())

    def test_duplicate_select_labels_collide(self):
        rng = np.random.default_rng(8)
        for _ in range(25):
            vals = [float(v) for v in
                    rng.integers(0, 6, size=int(rng.integers(1, 6)))]
            dup = vals + [vals[int(rng.integers(0, len(vals)))]]
            rng.shuffle(dup)
            assert (Request([Select("c", sorted(set(vals)))]).canonical_hash()
                    == Request([Select("c", dup)]).canonical_hash())

    def test_sub_tolerance_jitter_collides(self):
        rng = np.random.default_rng(9)
        h0 = Request([ConvexPolytope(("a", "b"), _TRI)]).canonical_hash()
        for _ in range(25):
            jitter = rng.uniform(-2e-10, 2e-10, size=_TRI.shape)
            assert (Request([ConvexPolytope(("a", "b"), _TRI + jitter)])
                    .canonical_hash() == h0)

    def test_super_tolerance_perturbation_differs(self):
        rng = np.random.default_rng(10)
        h0 = Request([ConvexPolytope(("a", "b"), _TRI)]).canonical_hash()
        for _ in range(25):
            shift = np.zeros_like(_TRI)
            shift[rng.integers(0, 3), rng.integers(0, 2)] = (
                float(rng.choice([-1.0, 1.0])) * rng.uniform(1e-6, 1e-3))
            assert (Request([ConvexPolytope(("a", "b"), _TRI + shift)])
                    .canonical_hash() != h0)


if HAVE_HYPOTHESIS:
    _coord = st.integers(0, 6)
    _params = st.tuples(_coord, _coord,
                        st.integers(1, 5), st.integers(1, 5))
    _members = st.lists(st.tuples(st.integers(0, 2), _params),
                        min_size=2, max_size=4)
    _props = settings(deadline=None, max_examples=40)

    class TestCanonicalHashHypothesis:
        @_props
        @given(specs=_members, data=st.data())
        def test_union_member_permutation_collides(self, specs, data):
            members = [_member(k, p) for k, p in specs]
            order = data.draw(st.permutations(range(len(members))))
            perm = [members[i] for i in order]
            assert (Request([Union(members)]).canonical_hash()
                    == Request([Union(perm)]).canonical_hash())

        @_props
        @given(vals=st.lists(st.integers(0, 6), min_size=1, max_size=5),
               data=st.data())
        def test_duplicate_select_labels_collide(self, vals, data):
            vals = [float(v) for v in vals]
            dup = vals + [data.draw(st.sampled_from(vals))]
            dup = data.draw(st.permutations(dup))
            assert (Request([Select("c", sorted(set(vals)))]).canonical_hash()
                    == Request([Select("c", list(dup))]).canonical_hash())

        @_props
        @given(jitter=st.lists(
            st.floats(-2e-10, 2e-10, allow_nan=False, allow_infinity=False),
            min_size=6, max_size=6))
        def test_sub_tolerance_jitter_collides(self, jitter):
            j = np.array(jitter).reshape(3, 2)
            assert (Request([ConvexPolytope(("a", "b"), _TRI + j)])
                    .canonical_hash() ==
                    Request([ConvexPolytope(("a", "b"), _TRI)])
                    .canonical_hash())

        @_props
        @given(vi=st.integers(0, 2), ci=st.integers(0, 1),
               sign=st.sampled_from([-1.0, 1.0]),
               delta=st.floats(1e-6, 1e-3, allow_nan=False,
                               allow_infinity=False))
        def test_super_tolerance_perturbation_differs(self, vi, ci, sign,
                                                      delta):
            shift = np.zeros_like(_TRI)
            shift[vi, ci] = sign * delta
            assert (Request([ConvexPolytope(("a", "b"), _TRI + shift)])
                    .canonical_hash() !=
                    Request([ConvexPolytope(("a", "b"), _TRI)])
                    .canonical_hash())


class TestCacheStatsEdges:
    """Regressions for the sharing_factor division edge cases."""

    def test_sharing_factor_no_reads(self):
        from repro.serve.extraction import CacheStats
        assert CacheStats().sharing_factor == 1.0

    def test_sharing_factor_requested_but_nothing_read(self):
        # fully deduped batch: bytes were requested yet none hit storage
        from repro.serve.extraction import CacheStats
        st = CacheStats(bytes_requested=4096, bytes_read=0)
        assert st.sharing_factor == float("inf")

    def test_sharing_factor_ratio(self):
        from repro.serve.extraction import CacheStats
        st = CacheStats(bytes_requested=300, bytes_read=100)
        assert st.sharing_factor == 3.0


class TestPlanCachePeekAndPop:
    def test_peek_is_uncounted_and_preserves_lru_order(self):
        pc = PlanCache(capacity=2)
        pc.put("k1", "p1")
        pc.put("k2", "p2")
        assert pc.peek("k1") == "p1"
        assert pc.peek("missing") is None
        assert pc.stats.lookups == 0          # not a request-path lookup
        pc.put("k3", "p3")                    # k1 still LRU → evicted
        assert "k1" not in pc
        assert "k2" in pc and "k3" in pc

    def test_pop_counts_migrations_only_when_present(self):
        pc = PlanCache(capacity=4)
        pc.put("k", "p")
        assert pc.pop("k") == "p"
        assert pc.stats.migrations == 1
        assert pc.pop("k") is None            # second pop is a no-op
        assert pc.stats.migrations == 1


class TestQuantizeStraddle:
    """Two requests 0.75e-9 apart can quantize to *different* exact
    cache keys (the 1e-9 quantum boundary falls between them) while
    selecting identical cells.  The translation-invariant signature is
    immune — relative coordinates cancel the jitter — so the
    neighborhood index recovers the miss as a zero-shift delta hit that
    reuses the parent plan object outright."""

    JITTER = 0.75e-9

    def box_req(self, j=0.0):
        return Request([Box(("a", "b"), [3.0 + j, 3.0 + j],
                            [7.0 + j, 7.0 + j]),
                        Select("c", [1.0])])

    def test_straddled_keys_differ_but_signature_matches(self):
        r0, r1 = self.box_req(), self.box_req(self.JITTER)
        assert r0.canonical_hash() != r1.canonical_hash()
        assert r0.shape_signature()[0] == r1.shape_signature()[0]

    def test_neighborhood_recovers_straddled_miss(self):
        svc = ShardedExtractionService(small_cube(), shards=1,
                                       verify=True)
        r0, r1 = self.box_req(), self.box_req(self.JITTER)
        p0, cached0, _ = svc.plan(r0)
        p1, cached1, _ = svc.plan(r1)
        assert not cached0 and not cached1
        assert svc.stats.delta_hits == 1
        assert p1 is p0                       # zero-shift passthrough
        np.testing.assert_array_equal(p1.offsets, p0.offsets)

    def test_off_by_one_quantum_anchor_tolerance(self):
        # a whole-step drift plus sub-quantum jitter still resolves to
        # an integral step count (the ratio check absorbs the jitter)
        svc = ShardedExtractionService(small_cube(), shards=1,
                                       verify=True)
        svc.plan(self.box_req())
        plan, cached, _ = svc.plan(self.box_req(1.0 + self.JITTER))
        assert not cached
        assert svc.stats.delta_hits == 1
        cold = Slicer(small_cube()).extract_plan(
            self.box_req(1.0 + self.JITTER))[0]
        np.testing.assert_array_equal(plan.offsets, cold.offsets)
