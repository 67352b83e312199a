"""LM token pipeline over a document datacube.

The corpus is a 2-D datacube (document × position); a training batch is
a Polytope extraction: a box request over a document range × position
window, planned by the slicer and gathered with the exact-byte path.
Sharded loading: each data-parallel host plans and reads only its batch
rows (plan-first ethos end-to-end).  All rows of a batch are submitted
as one :class:`~repro.serve.sharded.ShardedExtractionService` batch, so
duplicate windows plan once and recurring windows across steps/epochs
hit the plan cache (DESIGN.md §4).

Tokens are synthetic but *learnable*: a fixed-seed order-2 Markov chain,
so small LMs show decreasing loss in the examples/tests.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core import Box, OrderedAxis, Request, TensorDatacube


@dataclass
class TokenCube:
    vocab: int = 256
    n_docs: int = 1024
    doc_len: int = 2048
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        # order-1 Markov transition with strong structure
        perm = rng.permutation(self.vocab)
        self._next = perm
        self._noise = rng
        doc_axis = OrderedAxis("doc", np.arange(self.n_docs, dtype=float))
        pos_axis = OrderedAxis("pos", np.arange(self.doc_len,
                                                dtype=float))
        self.cube = TensorDatacube([doc_axis, pos_axis],
                                   dtype=np.dtype(np.int32))
        from repro.serve.sharded import ShardedExtractionService

        # Random windows mostly miss the cache in normal training; the
        # cache pays off on exact-step replay (fault-tolerant restore)
        # and epoch revisits, so keep it small — plans are per-row and
        # cheap to rebuild.
        self.service = ShardedExtractionService(self.cube, shards=1,
                                                capacity_per_shard=512)

    def _doc(self, doc_id: int) -> np.ndarray:
        rng = np.random.default_rng(self.seed * 100_003 + doc_id)
        toks = np.empty(self.doc_len, np.int32)
        toks[0] = rng.integers(self.vocab)
        flip = rng.random(self.doc_len) < 0.1
        rand = rng.integers(0, self.vocab, self.doc_len)
        for i in range(1, self.doc_len):
            toks[i] = rand[i] if flip[i] else self._next[toks[i - 1]]
        return toks

    def materialize(self) -> np.ndarray:
        """Flat datacube payload (lazy docs for big cubes)."""
        if not hasattr(self, "_flat"):
            self._flat = np.concatenate(
                [self._doc(d) for d in range(self.n_docs)])
        return self._flat

    def batch(self, step: int, batch_size: int, seq_len: int,
              shard: int = 0, n_shards: int = 1) -> dict:
        """Step-addressable batch (deterministic replay for FT restore).

        The batch IS a polytope request: box over (doc range × window).
        """
        flat = self.materialize()
        rng = np.random.default_rng(step * 7919 + shard)
        rows = batch_size // n_shards
        docs = rng.integers(0, self.n_docs, rows)
        starts = rng.integers(0, self.doc_len - seq_len - 1, rows)
        if rows == 0:
            toks = np.empty((0, seq_len + 1), np.int32)
            return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        reqs = [Request([Box(("doc", "pos"), [d, s0], [d, s0 + seq_len])])
                for d, s0 in zip(docs, starts)]
        results = self.service.submit_batch(reqs, flat)
        toks = np.stack([res.values for res in results]).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
