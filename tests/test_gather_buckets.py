"""The served union read at bucketed lengths (``serve/extraction.py``
``bucket`` and ``padded_read_plan``): on a device payload (a JAX array on
the CPU backend) ``jnp.take`` runs at a power-of-two length of at least
1024, so windows whose unions differ in length but share a bucket share
one compiled program; the answers, ``bytes_read`` and the exact read of
a host payload are what they were.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index_tree import ExtractionPlan, coalesce_runs
from repro.dataplane.weather import IrregularWeatherCube
from repro.serve.extraction import (UNION_BUCKET_FLOOR, ServiceResult,
                                    bucket, padded_read_plan,
                                    shared_union_gather)
from repro.serve.sharded import AdmissionQueue, ShardedExtractionService

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
N_ELEMENTS = 50_021          # a payload length no other test file uses


@pytest.fixture(scope="module")
def cube():
    return IrregularWeatherCube(n_dates=2, times_per_day=3, n_levels=4,
                                n_lat=24, n_lon=36).cube


@pytest.fixture(scope="module")
def host_payload():
    rng = np.random.default_rng(11)
    return rng.standard_normal(N_ELEMENTS).astype(np.float32)


@pytest.fixture(scope="module")
def payload(host_payload):
    return jnp.asarray(host_payload)


def plan_of(offsets) -> ExtractionPlan:
    offsets = np.asarray(offsets, np.int64)
    starts, lengths = coalesce_runs(offsets)
    return ExtractionPlan(offsets=offsets, run_starts=starts,
                          run_lengths=lengths, coords={}, itemsize=4)


def window(n_union: int, seed: int) -> dict[str, ExtractionPlan]:
    """Two overlapping plans whose union holds exactly ``n_union``
    sorted offsets, spread over the payload."""
    rng = np.random.default_rng(seed)
    union = np.sort(rng.choice(N_ELEMENTS, n_union, replace=False))
    half = (n_union + 1) // 2
    return {"a": plan_of(union[:half]),
            "b": plan_of(union[max(half - 2, 0):])}


def serve(cube, plans: dict[str, ExtractionPlan], flat_data):
    results = [ServiceResult(request=None, key=k, plan=p, cached=True)
               for k, p in plans.items()]
    delta = shared_union_gather(cube, results, plans, flat_data)
    return results, delta


@contextlib.contextmanager
def backend_compiles():
    """Backend compiles inside the block, less persistent-cache loads
    (as ``bench/harness/cell.py`` ``CompileCounter`` counts them)."""
    count = [0]

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            count[0] += 1

    def on_event(event, **_):
        if event == CACHE_HIT_EVENT:
            count[0] -= 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield count
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


@pytest.mark.parametrize("n", [1, 4, 1023, 1024, 1025, 4096, 4097])
def test_bucket_is_the_next_power_of_two_above_the_floor(n):
    b = bucket(n)
    floor = max(n, UNION_BUCKET_FLOOR)
    assert b & (b - 1) == 0
    assert floor <= b < 2 * floor


@pytest.mark.parametrize("n", [1, 2, 3, 512, 513, 1024, 1025])
def test_padded_plan_holds_the_union_in_its_middle(n):
    union = plan_of(np.arange(5, 5 + 3 * n, 3))
    padded, lead = padded_read_plan(union)
    offs = padded.offsets
    assert padded.n_points == bucket(n)
    np.testing.assert_array_equal(offs[lead:lead + n], union.offsets)
    assert (offs[:lead] == union.offsets[0]).all()
    assert (offs[lead + n:] == union.offsets[-1]).all()
    assert lead <= padded.n_points // 2 < lead + n
    assert padded.run_starts is union.run_starts


@pytest.mark.parametrize("k", [4, 10, 12])
@pytest.mark.parametrize("step", [-1, 0, 1])
def test_padded_read_answers_bit_equal(cube, payload, host_payload, k, step):
    n = 2 ** k + step
    plans = window(n, seed=k * 3 + step)
    results, _ = serve(cube, plans, payload)
    for res in results:
        assert res.values.dtype == np.float32
        np.testing.assert_array_equal(
            res.values.view(np.uint32),
            host_payload[res.plan.offsets].view(np.uint32))


def test_one_compile_for_two_windows_in_one_bucket(cube):
    # a payload of its own, so no earlier test compiled this read
    data = jnp.arange(N_ELEMENTS + 7, dtype=jnp.float32)
    first, second = window(2000, seed=1), window(1900, seed=2)
    with backend_compiles() as compiles:
        serve(cube, first, data)
        serve(cube, second, data)
    assert bucket(2000) == bucket(1900) == 2048
    assert compiles[0] == 1


@pytest.mark.parametrize("n", [3, 1024, 1500])
def test_bytes_read_is_the_unpadded_union(cube, payload, n):
    _, delta = serve(cube, window(n, seed=n), payload)
    assert delta.bytes_read == cube.dtype.itemsize * n
    assert delta.gather_padded_elems == bucket(n) - n


def test_host_payload_is_read_exactly(cube, host_payload):
    results, delta = serve(cube, window(1500, seed=5), host_payload)
    assert delta.gather_padded_elems == 0
    assert delta.bytes_read == cube.dtype.itemsize * 1500
    for res in results:
        np.testing.assert_array_equal(res.values,
                                      host_payload[res.plan.offsets])


def test_served_windows_count_their_padding():
    """Through the queue and the sharded service, every window's padding
    folds into the service's stats and the answers stay exact."""
    from repro.core import Box, Request, Select

    icw = IrregularWeatherCube(n_dates=2, times_per_day=3, n_levels=4,
                               n_lat=24, n_lon=36)
    host = np.asarray(icw.field_data(seed=3), np.float32)
    svc = ShardedExtractionService(icw.cube, shards=3)
    reqs = [Request([Select("datetime", [0.0]), Select("level", [lvl]),
                     Box(("lat", "lon"), [20.0, 34.0], [70.0, 76.0])])
            for lvl in (1.0, 2.0)]
    with AdmissionQueue(svc, flat_data=jnp.asarray(host),
                        window_s=0.005) as q:
        answers = [q.submit(r).result(timeout=120) for r in reqs]
    for res in answers:
        np.testing.assert_array_equal(res.values, host[res.plan.offsets])
    s = svc.stats
    unions = s.bytes_read // icw.cube.dtype.itemsize
    assert 0 < unions < UNION_BUCKET_FLOOR
    assert s.gather_padded_elems == 2 * UNION_BUCKET_FLOOR - unions
