"""A window's union built, and its answers sliced, from the plans' runs
(``serve/extraction.py`` ``union_of_runs`` and ``slice_by_runs``): the
union plan is byte-identical to ``coalesce_runs(np.unique(...))`` of
every plan's offsets, every answer is bit-equal to ``flat[offsets]``
for a host and a device payload, duplicate requests share one answer,
and an answer cut as one stretch of the read owns its data.
"""

from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index_tree import ExtractionPlan, coalesce_runs
from repro.serve.extraction import (ServiceResult, shared_union_gather,
                                    union_of_runs)

N_ELEMENTS = 40_009          # a payload length no other test file uses
ROW = 97                     # row width of the seam-like plans

CUBE = SimpleNamespace(dtype=np.dtype(np.float32), n_elements=N_ELEMENTS)


def plan_of(runs) -> ExtractionPlan:
    """The strictly ascending plan of the elements ``(start, length)``
    runs cover, its runs coalesced as every planner emits them."""
    parts = [np.arange(s, min(s + n, N_ELEMENTS), dtype=np.int64)
             for s, n in runs if 0 <= s < N_ELEMENTS]
    offsets = np.unique(np.concatenate(parts)) if parts \
        else np.empty(0, np.int64)
    starts, lengths = coalesce_runs(offsets)
    return ExtractionPlan(offsets=offsets, run_starts=starts,
                          run_lengths=lengths, coords={}, itemsize=4)


def seam_rows(rows, width: int):
    """A box across a row's seam: each row's first and last ``width``
    elements, so one row's end touches the next row's start."""
    return [r for row in rows
            for r in ((row * ROW, width), ((row + 1) * ROW - width, width))]


def random_window(seed: int) -> list[list[tuple[int, int]]]:
    """1–6 plans drawn from a shared pool of runs, each run taken as it
    is, nested in one, touching one's end, overlapping one, or as a
    single element; a plan after the first may draw no run and be
    empty."""
    rng = np.random.default_rng(seed)
    pool = [(int(rng.integers(0, N_ELEMENTS - 400)),
             int(rng.integers(1, 200))) for _ in range(10)]
    window = []
    for _ in range(int(rng.integers(1, 7))):
        runs = []
        for _ in range(int(rng.integers(1 if not window else 0, 9))):
            s, n = pool[int(rng.integers(len(pool)))]
            kind = int(rng.integers(5))
            if kind == 0:
                runs.append((s, n))
            elif kind == 1:
                a = int(rng.integers(0, n))
                runs.append((s + a, int(rng.integers(1, n - a + 1))))
            elif kind == 2:
                runs.append((s + n, int(rng.integers(1, 60))))
            elif kind == 3:
                runs.append((max(s + int(rng.integers(-n, n)), 0),
                             int(rng.integers(1, 2 * n + 1))))
            else:
                runs.append((int(rng.integers(0, N_ELEMENTS)), 1))
        window.append(runs)
    return window


WINDOWS = {
    "one_plan": [[(10, 50), (100, 3), (200, 1)]],
    "one_run": [[(3000, 2500)]],
    "overlapping": [[(10, 50), (500, 40)], [(40, 30), (520, 60)]],
    "touching": [[(10, 50)], [(60, 20)], [(80, 1), (200, 5)], [(195, 5)]],
    "nested": [[(10, 100)], [(20, 5), (50, 5)], [(60, 1)]],
    "identical": [[(10, 50), (300, 7)], [(10, 50), (300, 7)]],
    "single_elements": [[(5, 1), (7, 1), (9, 1)], [(6, 1), (8, 1)],
                        [(9, 1)], [(30_000, 1)]],
    "empty_plans": [[], [(10, 5), (40, 5)], [], [(12, 30)]],
    "apart_in_order": [[(10, 5), (20, 5)], [(100, 5)], [(4000, 900)]],
    "apart_in_reverse": [[(4000, 900)], [(100, 5)], [(10, 5), (20, 5)]],
    "seam": [seam_rows(range(5, 40), 12), seam_rows(range(8, 44), 9),
             [(r * ROW + 30, 40) for r in range(5, 40)]],
    "six_plans": [[(1000 * k + 7 * k, 700)] for k in range(6)],
    **{f"random_{seed}": random_window(seed) for seed in range(12)},
}


def plans_of(name: str) -> dict[str, ExtractionPlan]:
    return {f"p{i}": plan_of(runs) for i, runs in enumerate(WINDOWS[name])}


def expected_union(plans) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    union = np.unique(np.concatenate([p.offsets for p in plans]))
    return (union, *coalesce_runs(union))


@pytest.fixture(scope="module")
def host_payload():
    rng = np.random.default_rng(23)
    return rng.standard_normal(N_ELEMENTS).astype(np.float32)


@pytest.fixture(scope="module")
def payloads(host_payload):
    return {"numpy": host_payload, "jax": jnp.asarray(host_payload)}


def asked_twice(plans: dict[str, ExtractionPlan]) -> list[str]:
    """The first and the last non-empty plan's keys."""
    keys = [k for k, p in plans.items() if p.n_points]
    return [keys[0], keys[-1]]


def serve(plans: dict[str, ExtractionPlan], flat_data):
    """Every plan requested once, then :func:`asked_twice`'s again."""
    keys = list(plans) + asked_twice(plans)
    results = [ServiceResult(request=None, key=k, plan=plans[k],
                             cached=True) for k in keys]
    delta = shared_union_gather(CUBE, results, plans, flat_data,
                                verify=True)
    return results, delta


@pytest.mark.parametrize("name", sorted(WINDOWS))
def test_union_is_the_coalesced_unique_offsets(name):
    nonempty = [p for p in plans_of(name).values() if p.n_points]
    got = union_of_runs(nonempty, itemsize=4)
    for have, want in zip((got.offsets, got.run_starts, got.run_lengths),
                          expected_union(nonempty)):
        assert have.dtype == want.dtype
        np.testing.assert_array_equal(have, want)


@pytest.mark.parametrize("payload", ["numpy", "jax"])
@pytest.mark.parametrize("name", sorted(WINDOWS))
def test_answers_bit_equal(name, payload, payloads, host_payload):
    plans = plans_of(name)
    results, delta = serve(plans, payloads[payload])
    nonempty = [p for p in plans.values() if p.n_points]
    union, starts, _ = expected_union(nonempty)
    assert delta.bytes_read == 4 * len(union)
    assert delta.union_runs == len(starts)
    for res in results:
        assert res.values.dtype == np.float32
        np.testing.assert_array_equal(
            res.values.view(np.uint32),
            host_payload[res.plan.offsets].view(np.uint32))


@pytest.mark.parametrize("name", sorted(WINDOWS))
def test_duplicate_requests_share_one_answer(name, payloads):
    plans = plans_of(name)
    results, _ = serve(plans, payloads["jax"])
    first = {res.key: res.values for res in results[:len(plans)]}
    for res, key in zip(results[len(plans):], asked_twice(plans)):
        assert res.values is first[key]


@pytest.mark.parametrize("payload", ["numpy", "jax"])
@pytest.mark.parametrize("name", ["one_plan", "one_run", "seam",
                                  "apart_in_order"])
def test_an_answer_cut_as_one_stretch_owns_its_data(name, payload,
                                                    payloads):
    """A window's only plan, and plans that lie apart, are each one
    stretch of the read buffer: the answer is a copy that owns its
    data, so it does not keep the bucket-sized buffer alive."""
    plans = plans_of(name)
    if name == "seam":
        plans = {"p0": plans["p0"]}
    results, delta = serve(plans, payloads[payload])
    for res in results:
        assert res.values.flags.owndata
    if len(plans) == 1:
        assert delta.union_runs == plans["p0"].n_runs
