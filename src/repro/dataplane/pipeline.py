"""Sharded prefetching pipeline.

Each host plans + reads only its own batch shard (the extraction plan
is per-host); a background thread keeps ``depth`` batches ahead so the
accelerator never waits on the planner.  Step-addressable sources make
fault-tolerant replay deterministic (``repro.train.fault``).

:class:`CachedExtractionSource` routes a step's polytope requests
through a shared :class:`~repro.serve.sharded.ShardedExtractionService`,
so recurring request geometry across steps is served from the plan
cache instead of re-running Algorithm 1 (DESIGN.md §4).
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterator

import jax


class Prefetcher:
    def __init__(self, source: Callable[[int], Any], depth: int = 2,
                 start_step: int = 0, put_fn: Callable | None = None):
        self.source = source
        self.depth = depth
        self.put_fn = put_fn or (lambda x: x)
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._step = start_step
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def _work(self):
        step = self._step
        while not self._stop.is_set():
            try:
                batch = self.put_fn(self.source(step))
            except Exception as e:  # surface errors on the main thread
                self._q.put(e)
                return
            self._q.put((step, batch))
            step += 1

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        item = self._q.get()
        if isinstance(item, Exception):
            raise item
        return item

    def close(self):
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass


class CachedExtractionSource:
    """Step-addressable batch source planned through a shared service.

    ``request_fn(step)`` returns the step's polytope request(s); the
    whole list is submitted as ONE service batch, so duplicate geometry
    inside a step is planned once and overlapping reads coalesce, while
    geometry repeated *across* steps (the common production pattern —
    same crops every cycle) hits the LRU plan cache.  Designed to be the
    ``source`` of a :class:`Prefetcher`: the service is thread-safe, so
    planning happens on the prefetch thread while the accelerator runs.
    """

    def __init__(self, service, request_fn: Callable[[int], Any],
                 flat_data: Any | None = None,
                 collate: Callable[[int, list], Any] | None = None):
        self.service = service
        self.request_fn = request_fn
        self.flat_data = flat_data
        self.collate = collate

    def __call__(self, step: int) -> Any:
        reqs = self.request_fn(step)
        single = not isinstance(reqs, (list, tuple))
        batch = [reqs] if single else list(reqs)
        results = self.service.submit_batch(batch, self.flat_data)
        if self.collate is not None:
            return self.collate(step, results)
        return results[0] if single else results


def device_put_sharded(batch: Any, sharding) -> Any:
    """Place a host batch onto the mesh with the given sharding tree."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, s), batch, sharding)
