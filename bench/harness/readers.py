"""Arithmetic shared by the metric readers in ``bench/metrics/``.  Each
reader is ``read(window) -> float | None``: ``None`` when the window
holds nothing to read, and the metric is then left out of the line."""

from __future__ import annotations


def ratio(num: float, den: float, scale: float = 1.0) -> float | None:
    return scale * num / den if den > 0 else None


def roofline_pct(window, module_pattern: str) -> float | None:
    """Share of the HBM roofline reached by the device ops of the
    modules matching ``module_pattern``: the bytes the windows' unions
    need (each element read once and written once) over the ops' device
    time, against the chip's peak HBM bandwidth."""
    if window.trace is None:
        return None
    busy = window.trace.module_seconds(module_pattern)
    need = 2 * window.counters["cache.bytes_read"]
    if busy <= 0 or need <= 0:
        return None
    return 100.0 * need / busy / window.peaks["hbm_bytes_per_s"]


def idle_pct(window) -> float | None:
    t = window.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
