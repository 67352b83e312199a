"""Whether what the timed path produced is correct.

After the window closes, every answer due in it is held against the
plain reference (``reference.py``, ``payload.py``), which shares
nothing with the service: not its plans, its caches or its gather.

* ``unanswered``: requests of the window that never got an answer, or
  got an error.
* ``value_mismatch``: answered elements whose float32 bits differ from
  the payload value at the plan's offset (an element missing from, or
  extra in, the values counts once).
* ``plan_mismatch``: elements in which a plan and the reference's
  offsets for the same request differ, over every distinct plan that
  answered the request: a later answer from another plan (a cache key
  collision, an eviction, a splice) is compared as well.

The service promises exact bytes, so every limit is 0.  The control
puts the reference in the service's place at the next precision down:
bfloat16 values over the reference's own plans.
"""

from __future__ import annotations

import json

import numpy as np

from .payload import reference_bits

LIMITS = {"unanswered": 0, "value_mismatch": 0, "plan_mismatch": 0}


def _desc_key(desc: dict) -> str:
    return json.dumps(desc, sort_keys=True)


def compare(records, reference, seed: int) -> dict:
    """The numbers compared, over every record of the window."""
    unanswered = sum(1 for r in records if not r.answered)
    value_bad = 0
    seen_values: set[int] = set()
    plans: dict[tuple[str, int], np.ndarray] = {}
    for r in records:
        if not r.answered:
            continue
        offsets = np.asarray(r.plan.offsets)
        plans.setdefault((_desc_key(r.desc), id(r.plan)), offsets)
        if id(r.values) in seen_values:
            continue
        seen_values.add(id(r.values))
        value_bad += value_mismatch(np.asarray(r.values), offsets, seed)
    plan_bad = 0
    want: dict[str, np.ndarray] = {}
    for (key, _), offsets in plans.items():
        if key not in want:
            want[key] = reference.offsets(json.loads(key))
        plan_bad += plan_mismatch(offsets, want[key])
    return {"unanswered": unanswered, "value_mismatch": value_bad,
            "plan_mismatch": plan_bad}


def value_mismatch(values: np.ndarray, offsets: np.ndarray,
                   seed: int) -> int:
    n = min(len(values), len(offsets))
    extra = abs(len(values) - len(offsets))
    if values.dtype != np.float32:
        return max(len(values), len(offsets))
    got = values[:n].view(np.uint32)
    return int(np.count_nonzero(got != reference_bits(seed, offsets[:n]))
               + extra)


def plan_mismatch(offsets: np.ndarray, want: np.ndarray) -> int:
    return int(len(np.setxor1d(offsets, want, assume_unique=False)))


def to_bfloat16(values: np.ndarray) -> np.ndarray:
    """float32 → nearest bfloat16 (ties to even) → float32."""
    bits = values.astype(np.float32).view(np.uint32).astype(np.uint64)
    rounded = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return rounded.astype(np.uint32).view(np.float32)


def control(records, reference, seed: int) -> dict:
    """The same numbers for the control: each request answered with the
    reference's plan and its values rounded to bfloat16."""
    value_bad = 0
    plans: dict[str, np.ndarray] = {}
    for r in records:
        key = _desc_key(r.desc)
        if key not in plans:
            plans[key] = reference.offsets(r.desc)
        offsets = plans[key]
        ctrl = to_bfloat16(reference_bits(seed, offsets).view(np.float32))
        value_bad += value_mismatch(ctrl, offsets, seed)
    return {"unanswered": 0, "value_mismatch": value_bad,
            "plan_mismatch": 0}


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())
