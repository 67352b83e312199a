"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against
the pure-jnp oracle (interpret mode executes the Pallas kernel body)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gather import kernel as gk, ref as gr
from repro.kernels.paged_attn import kernel as pk, ref as pr
from repro.kernels.segment import kernel as sk, ref as sr
from repro.kernels.slice import kernel as slk, ops as slo, ref as slr

pytestmark = pytest.mark.slow  # JAX-compile heavy; fast lane runs -m 'not slow'


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


class TestGatherRows:
    @pytest.mark.parametrize("n,d,m", [(16, 8, 4), (128, 64, 100),
                                       (64, 128, 7), (33, 16, 33)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, n, d, m, dtype):
        rng = np.random.default_rng(n * d + m)
        table = jnp.asarray(rng.normal(size=(n, d)), dtype=dtype)
        idx = jnp.asarray(rng.integers(0, n, m).astype(np.int32))
        np.testing.assert_allclose(
            np.asarray(gk.gather_rows(table, idx), np.float32),
            np.asarray(gr.gather_rows(table, idx), np.float32), **tol(dtype))

    def test_repeated_indices(self):
        table = jnp.arange(40.0).reshape(10, 4)
        idx = jnp.asarray([3, 3, 3, 0], dtype=jnp.int32)
        out = gk.gather_rows(table, idx)
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))

    def test_split_across_calls(self, monkeypatch):
        monkeypatch.setattr(gk, "MAX_ROWS_PER_CALL", 16)
        table = jnp.arange(64.0 * 8).reshape(64, 8)
        idx = (np.arange(45) * 7) % 64
        np.testing.assert_array_equal(np.asarray(gk.gather_rows(table, idx)),
                                      np.asarray(table)[idx])


class TestBurstGather:
    def test_unaligned_payload(self):
        from repro.kernels.gather import ops as go

        flat = jnp.arange(300.0)
        starts, lens = np.array([5, 250, 290]), np.array([10, 30, 10])
        want = np.concatenate([np.arange(s, s + n)
                               for s, n in zip(starts, lens)])
        np.testing.assert_array_equal(
            np.asarray(go.gather_plan_runs(flat, starts, lens)), want)
        with pytest.raises(ValueError, match="divisible"):
            go.gather_plan_runs(flat, starts, lens, use_pallas=True)


class TestGatherBag:
    @pytest.mark.parametrize("n,d,b,l", [(32, 8, 4, 3), (64, 32, 16, 8),
                                         (128, 16, 5, 1)])
    @pytest.mark.parametrize("dtype", [jnp.float32])
    def test_matches_ref(self, n, d, b, l, dtype):
        rng = np.random.default_rng(n + d + b + l)
        table = jnp.asarray(rng.normal(size=(n, d)), dtype=dtype)
        bags = jnp.asarray(rng.integers(-1, n, (b, l)).astype(np.int32))
        np.testing.assert_allclose(
            np.asarray(gk.gather_rows_bag(table, bags)),
            np.asarray(gr.gather_rows_bag(table, bags)), **tol(dtype))

    def test_all_padding_row_is_zero(self):
        table = jnp.ones((8, 4))
        bags = jnp.full((2, 3), -1, dtype=jnp.int32)
        out = gk.gather_rows_bag(table, bags)
        np.testing.assert_array_equal(np.asarray(out), np.zeros((2, 4)))


class TestSliceBatch:
    @pytest.mark.parametrize("p,v,d,k", [(4, 6, 3, 0), (10, 8, 4, 2),
                                         (1, 4, 2, 1), (9, 12, 5, 4)])
    def test_matches_ref(self, p, v, d, k):
        rng = np.random.default_rng(p * v + d + k)
        verts = jnp.asarray(rng.uniform(0, 10, (p, v, d)).astype(np.float32))
        nvalid = rng.integers(2, v + 1, p)
        valid = jnp.asarray(np.arange(v)[None, :] < nvalid[:, None])
        planes = jnp.asarray(rng.uniform(0, 10, p).astype(np.float32))
        ok, mk = slk.slice_batch(verts, valid, planes, k=k)
        orf, mrf = slr.slice_batch(verts, valid, planes, k=k)
        np.testing.assert_array_equal(np.asarray(mk), np.asarray(mrf))
        np.testing.assert_allclose(np.asarray(ok), np.asarray(orf),
                                   rtol=1e-5, atol=1e-5)

    def test_agrees_with_host_slicer(self):
        from repro.core.geometry import Polytope, slice_vertices
        from repro.core.hull import convex_hull_prune

        rng = np.random.default_rng(7)
        polys = [Polytope(("x", "y", "z"), rng.uniform(0, 10, (6, 3)))
                 for _ in range(12)]
        verts, valid = slo.pack_polytopes(polys, v_max=8)
        planes = jnp.asarray(rng.uniform(3, 7, 12).astype(np.float32))
        out, mask = slk.slice_batch(verts, valid, planes, k=1)
        subs = slo.unpack_sliced(out, mask, ("x", "y", "z"), k=1)
        for poly, sub, c in zip(polys, subs, np.asarray(planes)):
            host = slice_vertices(poly.points, 1, float(c), tol=1e-6)
            if host is None:
                continue
            hp = convex_hull_prune(host)
            assert sub is not None
            a = np.asarray(sorted(map(tuple, np.round(hp, 3))))
            b = np.asarray(sorted(map(tuple, np.round(sub.points, 3))))
            assert len(a) == len(b)
            np.testing.assert_allclose(a, b, atol=2e-3)


class TestPagedAttention:
    @pytest.mark.parametrize("b,h,kvh,dh,ps,pmax",
                             [(2, 4, 4, 8, 4, 3),    # MHA
                              (3, 8, 2, 16, 4, 6),   # GQA
                              (1, 8, 1, 32, 8, 4)])  # MQA
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, b, h, kvh, dh, ps, pmax, dtype):
        rng = np.random.default_rng(b * h + dh)
        n_pages = b * pmax + 3
        q = jnp.asarray(rng.normal(size=(b, h, dh)), dtype=dtype)
        kp = jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh)),
                         dtype=dtype)
        vp = jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh)),
                         dtype=dtype)
        lens = rng.integers(1, ps * pmax + 1, b).astype(np.int32)
        bt = np.full((b, pmax), -1, np.int32)
        free = list(rng.permutation(n_pages))
        for i in range(b):
            need = int(np.ceil(lens[i] / ps))
            for j in range(need):
                bt[i, j] = free.pop()
        out_k = pk.paged_decode_attention(q, kp, vp, jnp.asarray(bt),
                                          jnp.asarray(lens))
        out_r = pr.paged_decode_attention(q, kp, vp, jnp.asarray(bt),
                                          jnp.asarray(lens))
        np.testing.assert_allclose(np.asarray(out_k, np.float32),
                                   np.asarray(out_r, np.float32),
                                   **tol(dtype))

    def test_reads_only_planned_pages(self):
        """Poisoning un-planned pages must not change the output — the
        kernel provably reads only the extraction plan's bytes."""
        rng = np.random.default_rng(0)
        b, h, kvh, dh, ps, pmax, n_pages = 1, 4, 2, 8, 4, 2, 8
        q = jnp.asarray(rng.normal(size=(b, h, dh)).astype(np.float32))
        kp = rng.normal(size=(n_pages, kvh, ps, dh)).astype(np.float32)
        vp = rng.normal(size=(n_pages, kvh, ps, dh)).astype(np.float32)
        bt = jnp.asarray([[2, 5]], dtype=jnp.int32)
        lens = jnp.asarray([7], dtype=jnp.int32)
        out1 = pk.paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                         bt, lens)
        kp2, vp2 = kp.copy(), vp.copy()
        for pg in range(n_pages):
            if pg not in (2, 5):
                kp2[pg] = 1e9
                vp2[pg] = -1e9
        out2 = pk.paged_decode_attention(q, jnp.asarray(kp2),
                                         jnp.asarray(vp2), bt, lens)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


class TestSegmentSum:
    @pytest.mark.parametrize("e,d,s", [(100, 8, 10), (1000, 16, 40),
                                       (256, 128, 4), (7, 4, 3)])
    @pytest.mark.parametrize("dtype", [jnp.float32])
    def test_matches_ref(self, e, d, s, dtype):
        rng = np.random.default_rng(e + d + s)
        msg = jnp.asarray(rng.normal(size=(e, d)), dtype=dtype)
        seg = jnp.asarray(rng.integers(-1, s, e).astype(np.int32))
        np.testing.assert_allclose(
            np.asarray(sk.segment_sum(msg, seg, s)),
            np.asarray(sr.segment_sum(msg, seg, s)), rtol=1e-4, atol=1e-4)

    def test_empty_segments_zero(self):
        msg = jnp.ones((4, 2))
        seg = jnp.asarray([0, 0, 0, 0], dtype=jnp.int32)
        out = sk.segment_sum(msg, seg, 3)
        np.testing.assert_array_equal(np.asarray(out[1:]), np.zeros((2, 2)))
