"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: how long the device was busy, how long named kernels ran
on it, and what the host was doing while it sat idle.

Device activity is the union of the op intervals on the ``XLA Ops``
line of each ``/device:TPU:<n>`` plane.  Each op is credited to the XLA
module (``XLA Modules`` line) whose interval holds its start.  Host
activity is every event on the ``/host:CPU`` plane's thread lines: JAX's
own and the program's spans (``Stage`` in ``src/repro/serve``).
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


@dataclass
class Reduced:
    window_s: float
    busy_s: float                                   # mean over devices
    device_ops: list = field(default_factory=list)  # [op_key, s], top 10
    idle_gaps: list = field(default_factory=list)   # [host activity, s]
    module_s: dict = field(default_factory=dict)    # module name → s

    def module_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for name, s in self.module_s.items() if rx.search(name))


def op_key(module: str, op: str) -> str:
    """``module:op`` without the module's fingerprint, the op's shapes or
    its numeric suffix: ``jit__take(123…)`` and ``%fusion.3 = f32[4061]
    …`` give ``jit__take:%fusion``."""
    base = module.split("(", 1)[0]
    name = re.sub(r"\.\d+$", "", op.split(" = ", 1)[0].strip())
    return f"{base}:{name}"


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def union_intervals(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Disjoint (start, end) rows covering the given intervals."""
    if len(starts) == 0:
        return np.empty((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(s) - 1)
    return np.stack([s[idx], run_end[last]], axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if not len(iv):
        return iv
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _module_of(ops, modules) -> list[str]:
    if not modules:
        return ["?"] * len(ops)
    modules = sorted(modules, key=lambda m: m[1])
    starts = np.array([m[1] for m in modules])
    out = []
    for _, s, _ in ops:
        i = int(np.searchsorted(starts, s, side="right")) - 1
        if i >= 0 and s <= modules[i][1] + modules[i][2]:
            out.append(modules[i][0])
        else:
            out.append("?")
    return out


# What the host was doing, by the names of its trace events; a gap's
# time goes to the first category, in this order, whose events cover it.
HOST_ACTIVITY = (
    ("compile", re.compile(r"(?i)compil|hlo pass|lower_sharding|LSRA|"
                           r"codegen|LoadProgram")),
    ("transfer", re.compile(r"Transfer|ToLiteral|D2H|H2D|np\.asarray|"
                            r"DevicePut|CopyToDevice")),
    ("dispatch", re.compile(r"PjitFunction|Execute|EnqueueProgram")),
)
# What is left goes to the program's leaf spans, named exactly, innermost
# first: a planner inside a lookup counts as the planner.  The root span,
# ``polytope.window``, holds every stage of a window and would take all
# that none of them covers, so it is no label.
PROGRAM_SPANS = (
    "polytope.planner.cold", "polytope.planner.delta",
    "polytope.plan_cache.lookup", "polytope.gather.union",
    "polytope.gather.launch", "polytope.gather.copy",
    "polytope.gather.slice", "polytope.admission.collect",
)
UNTRACED = "host work outside every span"


def _measure(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def _intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two sets of disjoint sorted intervals."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.array(out).reshape(-1, 2)


def _subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``a`` less ``b``, both disjoint sorted intervals."""
    if not len(b) or not len(a):
        return a
    edges = np.concatenate([[-np.inf], b.ravel(), [np.inf]]).reshape(-1, 2)
    return _intersect(a, edges[edges[:, 1] > edges[:, 0]])


def _attribute_gaps(gaps: np.ndarray, host) -> list:
    """Idle seconds by what the host was doing: ``HOST_ACTIVITY``, then
    ``PROGRAM_SPANS``, then ``UNTRACED``, largest first.  Labels that
    cover no idle time are left out; past ``TOP`` labels, the smallest
    are joined into one, so the seconds still sum to the idle time."""
    by_name = defaultdict(list)
    for name, start, dur in host:
        by_name[name].append((start, start + dur))
    rules = [(label, rx.search) for label, rx in HOST_ACTIVITY]
    rules += [(span, span.__eq__) for span in PROGRAM_SPANS]
    out = []
    left = gaps
    for label, match in rules:
        ev = np.array([se for name, ses in by_name.items() if match(name)
                       for se in ses]).reshape(-1, 2)
        cover = union_intervals(ev[:, 0], ev[:, 1])
        out.append([label, _measure(_intersect(left, cover)) / 1e9])
        left = _subtract(left, cover)
    out.append([UNTRACED, _measure(left) / 1e9])
    out = sorted((kv for kv in out if kv[1] > 0), key=lambda kv: -kv[1])
    if len(out) > TOP:
        rest = out[TOP - 1:]
        out = out[:TOP - 1] + [[" + ".join(k for k, _ in rest),
                                sum(s for _, s in rest)]]
    return out


def reduce_profile(profile, window_ns: tuple[float, float]) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` over ``window_ns`` (trace
    clock).  Raises when the trace holds no device plane."""
    lo, hi = window_ns
    busy, module_s = [], defaultdict(float)
    op_s: dict[str, float] = defaultdict(float)
    all_busy = []
    host = []
    n_dev = 0
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [ev for ev in _events(line) if ev[2] > 0]
            continue
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        n_dev += 1
        ops = [ev for ev in _events(lines[OPS_LINE])
               if ev[1] < hi and ev[1] + ev[2] > lo]
        modules = _events(lines[MODULES_LINE]) \
            if MODULES_LINE in lines else []
        for (name, _, d), mod in zip(ops, _module_of(ops, modules)):
            op_s[op_key(mod, name)] += d / 1e9
            module_s[mod] += d / 1e9
        starts = np.array([o[1] for o in ops])
        iv = _clip(union_intervals(starts, starts + np.array(
            [o[2] for o in ops])), lo, hi)
        busy.append(float((iv[:, 1] - iv[:, 0]).sum()) / 1e9 if len(iv)
                    else 0.0)
        all_busy.append(iv)
    if n_dev == 0:
        raise ValueError("the trace holds no TPU device plane with ops")
    iv = union_intervals(*(np.concatenate([b[:, k] for b in all_busy])
                           for k in (0, 1)))
    edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    top_ops = sorted(([k, v] for k, v in op_s.items()),
                     key=lambda kv: -kv[1])[:TOP]
    return Reduced(window_s=(hi - lo) / 1e9,
                   busy_s=float(np.mean(busy)),
                   device_ops=top_ops,
                   idle_gaps=_attribute_gaps(gaps, host),
                   module_s=dict(module_s))


def window_ns(profile, seconds: float) -> tuple[float, float]:
    """The traced window on the trace's clock: from the first event of
    any plane, ``seconds`` long (the trace starts as the window opens)."""
    first = min((float(e.start_ns) for plane in profile.planes
                 for line in plane.lines for e in line.events),
                default=0.0)
    return first, first + seconds * 1e9


def load(path):
    import jax

    return jax.profiler.ProfileData.from_file(str(path))


def find_xplane(log_dir) -> str:
    from pathlib import Path

    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return str(files[-1])
