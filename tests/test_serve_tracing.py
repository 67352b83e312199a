"""Spans and counters on the served path (``serve/extraction.py``
``Stage``): a tiny cube served through an ``AdmissionQueue`` from a
device payload (a JAX array on the CPU backend).

The gather's four stages share boundary timestamps, so they sum to
``gather_time_s``; the queue counts each request's wait from submit to
drain; the batch loop counts its lookups; every stage is also a
``jax.profiler.TraceAnnotation`` span on the worker thread, nested in
the window's span.
"""

import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Box, Request, Select
from repro.dataplane.weather import IrregularWeatherCube
from repro.serve.extraction import CacheStats, Stage, merge_stats
from repro.serve.sharded import (AdmissionQueue, ShardedExtractionService,
                                 ShardedPlanCache)

LON_STEP = 10.0      # 360 / 36 columns
WINDOW_S = 0.005
GATHER_STAGES = ("union_time_s", "launch_time_s", "copy_time_s",
                 "slice_time_s")
NEW_FIELDS = ("lookup_time_s",) + GATHER_STAGES
SPANS = ("polytope.admission.collect", "polytope.window",
         "polytope.plan_cache.lookup", "polytope.planner.cold",
         "polytope.planner.delta", "polytope.gather.union",
         "polytope.gather.launch", "polytope.gather.copy",
         "polytope.gather.slice")
NESTED = ("polytope.plan_cache.lookup", "polytope.gather.union",
          "polytope.gather.launch", "polytope.gather.copy",
          "polytope.gather.slice")


@pytest.fixture(scope="module")
def cube():
    icw = IrregularWeatherCube(n_dates=2, times_per_day=3, n_levels=4,
                               n_lat=24, n_lon=36)
    payload = jnp.asarray(icw.field_data(seed=3), jnp.float32)
    return icw.cube, payload, np.asarray(payload)


def lon_box(k: int, level: float = 1.0) -> Request:
    """A box drifted ``k`` whole longitude steps east (delta-eligible)."""
    return Request([Select("datetime", [0.0]), Select("level", [level]),
                    Box(("lat", "lon"), [20.0, 34.0 + k * LON_STEP],
                        [70.0, 76.0 + k * LON_STEP])])


def serve_windows(queue, batches):
    """One window per batch: submit the batch, wait for every answer."""
    out = []
    for batch in batches:
        futs = [queue.submit(r) for r in batch]
        out += [f.result(timeout=120) for f in futs]
    return out


SERVICES = {"sharded": lambda c: ShardedExtractionService(c, shards=3),
            "one-shard": lambda c: ShardedExtractionService(c, shards=1)}


@pytest.mark.parametrize("kind", sorted(SERVICES))
def test_gather_stages_sum_to_gather_time(cube, kind):
    dc, payload, host = cube
    svc = SERVICES[kind](dc)
    batches = [[lon_box(0), lon_box(0, 2.0)], [lon_box(0), lon_box(3)],
               [lon_box(1), lon_box(3), lon_box(3, 2.0)]]
    with AdmissionQueue(svc, flat_data=payload, window_s=WINDOW_S) as q:
        answers = serve_windows(q, batches)
    for res in answers:
        np.testing.assert_array_equal(res.values, host[res.plan.offsets])
    s = svc.stats
    parts = [getattr(s, f) for f in GATHER_STAGES]
    assert all(p > 0 for p in parts), dict(zip(GATHER_STAGES, parts))
    assert sum(parts) == pytest.approx(s.gather_time_s, rel=1e-9,
                                       abs=1e-12)
    assert s.lookup_time_s > 0


def test_wait_and_lookup_on_cache_hits(cube):
    dc, payload, _ = cube
    svc = ShardedExtractionService(dc, shards=3)
    hot = [lon_box(0), lon_box(2)]
    with AdmissionQueue(svc, flat_data=payload, window_s=WINDOW_S) as q:
        serve_windows(q, [hot])
        before, adm0 = svc.stats, q.snapshot()
        serve_windows(q, [hot, hot])
        after, adm = svc.stats, q.snapshot()
    assert after.misses == before.misses
    assert after.hits > before.hits
    assert after.lookup_time_s > before.lookup_time_s
    # the first request of each window waits out the whole collection
    windows = adm.windows - adm0.windows
    assert adm.wait_s - adm0.wait_s >= 0.9 * WINDOW_S * windows
    assert adm.submitted == 3 * len(hot)


@pytest.mark.parametrize("kind", sorted(SERVICES))
def test_concurrent_batches_lose_no_stage_time(cube, kind):
    """Threads racing ``submit_batch`` fold their deltas under the
    service's lock: the stages still sum to the gather, and the bytes
    requested are every batch's."""
    dc, payload, _ = cube
    svc = SERVICES[kind](dc)
    batch = [lon_box(0), lon_box(1), lon_box(1, 2.0)]
    per_batch = sum(svc.plan(r)[0].nbytes for r in batch)
    n_threads, n_iters = 8, 4
    barrier = threading.Barrier(n_threads)
    errors = []

    def worker():
        try:
            barrier.wait(timeout=30)
            for _ in range(n_iters):
                svc.submit_batch(batch, payload)
        except BaseException as e:   # noqa: BLE001 — re-raised below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
    s = svc.stats
    assert s.bytes_requested == per_batch * n_threads * n_iters
    assert sum(getattr(s, f) for f in GATHER_STAGES) == \
        pytest.approx(s.gather_time_s, rel=1e-9)
    assert s.lookup_time_s > 0


@pytest.mark.parametrize("name", NEW_FIELDS)
def test_new_counters_merge_field_wise(name):
    a = CacheStats(**{name: 1.25, "hits": 2})
    b = CacheStats(**{name: 0.5, "hits": 1})
    merged = merge_stats([a, b])
    assert getattr(merged, name) == 1.75 and merged.hits == 3
    into = CacheStats(**{name: 1.0})
    assert merge_stats([a, b], into=into) is into
    assert getattr(into, name) == 2.75


@pytest.mark.parametrize("name", NEW_FIELDS)
def test_remove_shard_conserves_new_counters(name):
    cache = ShardedPlanCache(shards=3, capacity_per_shard=64)
    cache.add_shard("doomed")
    keys = [f"{i:064x}" for i in range(1, 400, 7)]
    assert any(cache.entry_of(k)[0] == "doomed" for k in keys)
    for k in keys:
        cache.entry_of(k)[1].record(**{name: 0.25})
    total = getattr(cache.stats, name)
    assert total == pytest.approx(0.25 * len(keys))
    cache.remove_shard("doomed")
    assert getattr(cache.stats, name) == pytest.approx(total)


def test_chained_stages_share_their_boundaries():
    stats = CacheStats()
    with Stage("polytope.test.a", stats, "union_time_s") as a:
        sum(range(1000))
    with Stage("polytope.test.b", stats, "launch_time_s",
               start=a.end) as b:
        sum(range(1000))
    assert b.start == a.end
    assert stats.union_time_s + stats.launch_time_s == \
        pytest.approx(b.end - a.start, rel=1e-12)
    with Stage("polytope.test.c") as c:   # span only, no counter
        pass
    assert c.seconds >= 0


def _host_spans(trace_dir: Path) -> dict[str, list[tuple[str, int, int]]]:
    """``line name → [(span name, start ns, end ns)]`` of the program's
    spans on the host plane of the trace under ``trace_dir``."""
    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    assert files, f"no trace under {trace_dir}"
    profile = jax.profiler.ProfileData.from_file(str(files[-1]))
    out: dict[str, list] = {}
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans = [(e.name, int(e.start_ns),
                      int(e.start_ns + e.duration_ns))
                     for e in line.events if e.name.startswith("polytope.")]
            if spans:
                out.setdefault(line.name, []).extend(spans)
    return out


def test_spans_reach_the_profiler_nested_in_their_window(cube, tmp_path):
    dc, payload, _ = cube
    svc = ShardedExtractionService(dc, shards=3)
    with jax.profiler.trace(str(tmp_path)):
        with AdmissionQueue(svc, flat_data=payload,
                            window_s=WINDOW_S) as q:
            # cold, then a drifted neighbour (delta), then a hit
            serve_windows(q, [[lon_box(0)], [lon_box(2)], [lon_box(0)]])
    assert svc.stats.delta_hits == 1 and svc.stats.hits >= 1
    lines = _host_spans(tmp_path)
    names = {n for spans in lines.values() for n, _, _ in spans}
    assert set(SPANS) <= names, set(SPANS) - names
    checked = 0
    for spans in lines.values():
        windows = [(s, e) for n, s, e in spans if n == "polytope.window"]
        for name, s, e in spans:
            if name in NESTED:
                assert any(ws <= s and e <= we for ws, we in windows), name
                checked += 1
    assert checked >= 3 * len(NESTED)
