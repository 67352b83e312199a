import numpy as np
import pytest

from repro.dataplane.graph import (CSRGraph, full_graph_batch, minibatch,
                                   molecule_batch, sample_neighbors,
                                   synthetic_graph)
from repro.dataplane.pipeline import Prefetcher
from repro.dataplane.recsys import ClickStream, InteractionStream
from repro.dataplane.tokens import TokenCube
from repro.dataplane.weather import (COUNTRIES, WeatherCube,
                                     paris_newyork_path)
from repro.core import Slicer


class TestTokenCube:
    def test_batch_deterministic(self):
        tc = TokenCube(n_docs=8, doc_len=256)
        b1 = tc.batch(3, 4, 32)
        b2 = tc.batch(3, 4, 32)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_replayed_batch_is_served_from_the_plan_cache(self):
        """A step replayed (fault-tolerant restore) gets the same tokens,
        each row the document's own window, with every row's plan a
        cache hit: nothing is planned again."""
        tc = TokenCube(n_docs=8, doc_len=256)
        step, rows, seq_len = 5, 6, 32
        first = tc.batch(step, rows, seq_len)
        before = tc.service.stats
        replay = tc.batch(step, rows, seq_len)
        after = tc.service.stats
        np.testing.assert_array_equal(replay["tokens"], first["tokens"])
        np.testing.assert_array_equal(replay["labels"], first["labels"])
        assert after.misses == before.misses
        assert (after.hits - before.hits
                + after.batch_dedup - before.batch_dedup) == rows
        docs = tc.materialize().reshape(tc.n_docs, tc.doc_len)
        windows = np.lib.stride_tricks.sliding_window_view(
            docs, seq_len + 1, axis=1)
        for tok, lab in zip(first["tokens"], first["labels"]):
            row = np.append(tok, lab[-1])
            assert (windows == row).all(axis=-1).any(), \
                "a row is not a window of one document"

    def test_labels_are_shifted_tokens(self):
        tc = TokenCube(n_docs=4, doc_len=128)
        b = tc.batch(0, 2, 16)
        np.testing.assert_array_equal(b["tokens"][:, 1:],
                                      b["labels"][:, :-1])

    def test_markov_structure_learnable(self):
        tc = TokenCube(n_docs=4, doc_len=512)
        b = tc.batch(0, 4, 256)
        # ~90% of transitions follow the deterministic permutation
        nxt = tc._next[b["tokens"]]
        agree = (nxt == b["labels"]).mean()
        assert agree > 0.7

    def test_sharded_batches_disjoint_rows(self):
        tc = TokenCube(n_docs=16, doc_len=128)
        b0 = tc.batch(0, 8, 32, shard=0, n_shards=2)
        b1 = tc.batch(0, 8, 32, shard=1, n_shards=2)
        assert b0["tokens"].shape[0] == 4
        assert not np.array_equal(b0["tokens"], b1["tokens"])


class TestGraphPlane:
    def test_synthetic_graph_sizes(self):
        g = synthetic_graph(500, 8, 16, 5)
        assert g.n_nodes == 500
        assert g.indptr[-1] == g.n_edges
        assert g.node_feat.shape == (500, 16)

    def test_sampler_fanout_bound(self):
        g = synthetic_graph(300, 10, 8, 3)
        rng = np.random.default_rng(0)
        seeds = np.arange(16)
        nodes, ei = sample_neighbors(g, seeds, [5, 3], rng)
        assert ei.shape[1] <= 16 * 5 + 16 * 5 * 3
        assert ei.max() < len(nodes)

    def test_minibatch_padded_shapes(self):
        g = synthetic_graph(300, 10, 8, 3)
        b = minibatch(g, 32, [5, 3], pad_nodes=512, pad_edges=1024)
        assert b["node_feat"].shape == (512, 8)
        assert b["edge_index"].shape == (2, 1024)
        assert b["label_mask"].sum() == 32

    def test_molecule_energy_invariant(self):
        b1 = molecule_batch(4, 10, 20, 8, step=5)
        b2 = molecule_batch(4, 10, 20, 8, step=5)
        np.testing.assert_array_equal(b1["energy"], b2["energy"])
        assert np.isfinite(b1["energy"]).all()


class TestClickStream:
    def test_labels_correlate_with_features(self):
        cs = ClickStream(rows=10_000, seed=0)
        b = cs.batch(0, 8192)
        # the hidden model must make labels predictable from dense feats
        w = np.linalg.lstsq(b["dense"], b["labels"] - 0.5,
                            rcond=None)[0]
        pred = b["dense"] @ w > 0
        acc = (pred == (b["labels"] > 0.5)).mean()
        assert acc > 0.55

    def test_zipf_ids_skewed(self):
        cs = ClickStream(rows=10_000)
        b = cs.batch(0, 4096)
        assert (b["bags"] == 0).mean() > 0.2   # head-heavy

    def test_interactions(self):
        s = InteractionStream(n_users=1000, n_items=1000)
        p = s.pairs(0, 64)
        assert p["user_ids"].shape == (64,)
        q = s.sequences(0, 8, 32, mask_token=1000)
        assert ((q["items"] == 1000) == (q["mask"] > 0)).all()


class TestWeatherPlane:
    def test_country_polygons_closed_and_sane(self):
        for name, poly in COUNTRIES.items():
            assert poly.shape[1] == 2
            assert len(poly) >= 9
            assert (np.abs(poly[:, 0]) <= 90).all()

    def test_country_vs_bbox_reduction(self):
        wc = WeatherCube(n=64, n_times=2, n_levels=3)
        from repro.core import BoundingBoxExtractor, PolytopeExtractor

        req = wc.country_request("norway")
        poly_plan, _ = PolytopeExtractor(wc.cube).plan(req)
        box_plan = BoundingBoxExtractor(wc.cube).plan(req)
        # Norway is paper Table 1's 6× case — elongated vs its bbox
        assert box_plan.n_points > 2.5 * poly_plan.n_points

    def test_timeseries_points(self):
        wc = WeatherCube(n=32, n_times=8, n_levels=3)
        req = wc.timeseries_request(51.5, 0.0, 0.0, 7 * 3600.0)
        plan, _ = Slicer(wc.cube).extract_plan(req)
        assert plan.n_points == 8      # one point per timestep

    def test_flight_path_extracts_tube(self):
        wc = WeatherCube(n=32, n_times=4, n_levels=5)
        req = wc.flight_path_request(paris_newyork_path(wc), width=6.0)
        plan, _ = Slicer(wc.cube).extract_plan(req)
        assert plan.n_points > 0


class TestPrefetcher:
    def test_orders_and_prefetches(self):
        pf = Prefetcher(lambda s: {"x": np.full(2, s)}, depth=2)
        out = [next(pf) for _ in range(5)]
        pf.close()
        assert [s for s, _ in out] == list(range(5))
        np.testing.assert_array_equal(out[3][1]["x"], 3.0)

    def test_error_propagates(self):
        def bad(step):
            if step == 2:
                raise ValueError("boom")
            return step

        pf = Prefetcher(bad, depth=1)
        assert next(pf)[0] == 0
        assert next(pf)[0] == 1
        with pytest.raises(ValueError):
            next(pf)
            next(pf)
        pf.close()
