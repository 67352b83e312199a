"""One run of one cell: set-up, the measured window, the check, and the
result line.

Set-up runs from process start to window start (``setup_s``): the
payload made on the device from the seed, the mix's warm set planned
and gathered through the queue one request at a time (so the plan cache
holds it and every single-request gather shape is compiled), every
request of the window built, and every counter read.  Per-layer metrics
are deltas of the program's counters over the window.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

from . import check, spec, window
from .payload import make_device_payload
from .reference import Reference
from .system import Served, to_request
from .traffic import Traffic

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_IMPORTED = time.perf_counter()


class NoAccelerator(SystemExit):
    pass


def process_age_s() -> float:
    """Seconds since this process started (``/proc``), so set-up counts
    the interpreter's start and imports too."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


def require_accelerator(chips: int):
    """JAX's devices, when the first is a TPU and there are ``chips`` of
    them; otherwise exit non-zero before anything is measured."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"bench: JAX's first device is "
                            f"{devs[0].platform!r}, not a TPU; no result")
    if len(devs) < chips:
        raise NoAccelerator(f"bench: the cell needs {chips} chips, JAX "
                            f"finds {len(devs)}; no result")
    return devs[:chips]


class CompileCounter:
    """Backend compiles, less loads from the persistent cache, counted
    through ``jax.monitoring``, with each compile's span on the host
    clock (``time.time``) and the persistent cache's misses."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.spans: list[tuple[float, float]] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.compiles += 1
                self.seconds += secs

    def _span(self, event: str, start: float, end: float, **_) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.spans.append((start, end))

    def _event(self, event: str, **_) -> None:
        with self._lock:
            if event == CACHE_HIT_EVENT:
                self.compiles -= 1
                self.cache_hits += 1
            elif event == CACHE_MISS_EVENT:
                self.cache_misses += 1

    def read(self) -> tuple[int, float]:
        with self._lock:
            return self.compiles, self.seconds

    def totals(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}


class GcPauses:
    """Python's garbage collections while installed: (start, seconds,
    generation) on ``time.perf_counter``'s clock."""

    def __init__(self):
        self.pauses: list[tuple[float, float, int]] = []
        self._start = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.pauses.append((self._start,
                                time.perf_counter() - self._start,
                                int(info.get("generation", -1))))

    def __enter__(self) -> "GcPauses":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)


def use_checkout_cache(root: Path) -> None:
    """JAX's persistent cache at a fixed path inside the checkout; every
    program compiled in set-up is written there."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def stop_cache_writes() -> None:
    """Programs compiled inside the window are not written: each run then
    pays the same compiles, however many runs came before it."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)


@dataclass
class Window:
    """What a metric reader reads: counter deltas over the window, the
    window's latencies, compiles and, in a traced run, the trace."""

    seconds: float
    counters: dict
    latency: dict
    throughput_req_s: float | None
    compiles: int
    compile_s: float
    setup_s: float
    peaks: dict
    trace: object = None
    records: list = field(default_factory=list)
    stalls: list = field(default_factory=list)
    gc: dict = field(default_factory=dict)


@dataclass
class Cell:
    """A cell set up on its device: payload, service and traffic."""

    config: dict
    traffic: Traffic
    devices: list
    peaks: dict
    counter: CompileCounter
    payload: object = None
    served: Served = None
    phases: dict = field(default_factory=dict)
    setup_programs: dict = field(default_factory=dict)

    def mark(self, phase: str) -> None:
        """Process age (s) as set-up's ``phase`` ends."""
        self.phases[phase] = round(process_age_s(), 3)

    @property
    def device(self):
        return self.devices[0]

    def start_service(self) -> None:
        """A fresh service over the payload, its warm set served through
        the queue one request at a time."""
        self.served = Served(self.config, self.payload)
        for desc in self.traffic.warm_set():
            self.served.queue.extract(to_request(desc), timeout=600)

    def close(self) -> None:
        if self.served is not None:
            self.served.close()
            self.served = None


def set_up(workload: str, seed: int, root: Path | None = None,
           require=None) -> Cell:
    """The cell ready for its window.  ``phases`` holds the process age
    as each part of set-up ends: start-up and imports, the device, the
    payload, the service with its warm set."""
    root = root or spec.ROOT
    started = round(process_age_s(), 3)
    bm = spec.load_benchmark(root)
    cell, config, mix = spec.resolve(bm, workload, root)
    devices = (require or require_accelerator)(int(cell["chips"]))
    use_checkout_cache(root)
    c = Cell(config=config, traffic=Traffic.load(mix, config),
             devices=devices,
             peaks=spec.peaks(devices[0].device_kind),
             counter=CompileCounter(), phases={"imports": started})
    c.mark("device")
    c.payload = make_device_payload(seed, int(config["elements"]))
    c.mark("payload")
    c.start_service()
    c.mark("warm")
    return c


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def measure(c: Cell, seed: int, seconds: float, trace: bool = False,
            setup_clock=process_age_s, rate_per_s: float | None = None,
            ) -> Window:
    """Draw the window's requests, then run it; ``setup_s`` is read from
    ``setup_clock`` as the window opens."""
    import jax

    loop = dict(c.traffic.loop)
    if rate_per_s is not None:
        loop["rate_per_s"] = rate_per_s
    c.traffic.spec = {**c.traffic.spec, "loop": loop}
    if loop["kind"] == "open":
        due, descs = c.traffic.open_loop(seed, seconds)
    else:
        cycles = [c.traffic.client_cycle(seed, i)
                  for i in range(int(loop["clients"]))]
    stop_cache_writes()
    closed = {}
    trace_dir = None
    queue = c.served.queue

    def close_window():
        closed["counters"] = c.served.counters()
        closed["compiles"] = c.counter.read()
        if trace_dir is not None:
            jax.profiler.stop_trace()

    c.setup_programs = c.counter.totals()
    before = c.served.counters()
    comp0 = c.counter.read()
    spans0 = len(c.counter.spans)
    setup_s = setup_clock()
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    wall = time.time() - time.perf_counter()
    with GcPauses() as gcp:
        if loop["kind"] == "open":
            records, t0, end = window.run_open(queue, to_request, descs,
                                               due, seconds, close_window)
        else:
            records, t0, end = window.run_closed(queue, to_request, cycles,
                                                 seconds, close_window)
    compile_spans = [(a - wall, b - wall)
                     for a, b in c.counter.spans[spans0:]]
    gc_pauses = [(a, d) for a, d, _ in gcp.pauses]
    reduced = None
    if trace_dir is not None:
        from . import trace as tr

        try:
            profile = tr.load(tr.find_xplane(trace_dir))
            reduced = tr.reduce_profile(profile,
                                        tr.window_ns(profile, end - t0))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return Window(seconds=end - t0,
                  counters=_delta(closed["counters"], before),
                  latency=window.latency_stats(records),
                  throughput_req_s=(window.throughput(records, t0, end)
                                    if loop["kind"] == "closed" else None),
                  compiles=closed["compiles"][0] - comp0[0],
                  compile_s=closed["compiles"][1] - comp0[1],
                  setup_s=setup_s, peaks=c.peaks, trace=reduced,
                  records=records,
                  stalls=window.stalls(records, t0, compile_spans,
                                       gc_pauses),
                  gc={"collections": len(gcp.pauses),
                      "gen2": sum(1 for *_, g in gcp.pauses if g == 2),
                      "total_ms": 1e3 * sum(d for _, d in gc_pauses),
                      "max_ms": 1e3 * max((d for _, d in gc_pauses),
                                          default=0.0)})


def run(workload: str, seed: int, seconds: float, trace: bool,
        require=None, root: Path | None = None, out=None, err=None) -> int:
    out = out or sys.stdout
    err = err or sys.stderr
    root = root or spec.ROOT
    bm = spec.load_benchmark(root)
    metrics = spec.metrics_for(bm, workload, trace)
    readers = {m["name"]: spec.reader(m["name"]) for m in metrics}
    c = set_up(workload, seed, root, require)
    try:
        w = measure(c, seed, seconds, trace)
        mem = (c.device.memory_stats() or {}).get("peak_bytes_in_use")
    finally:
        c.close()
    dev = c.device
    c.payload = None

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(c.devices), "memory_peak_bytes": mem}
    if w.trace is not None:
        device["busy_s"] = w.trace.busy_s
        device["window_s"] = w.trace.window_s
    values = {}
    for m in metrics:
        v = readers[m["name"]](w)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    numbers = check.compare(w.records, Reference(c.config), seed)
    checks = {k: {"value": v, "limit": check.LIMITS[k]}
              for k, v in numbers.items()}
    print(f"generator: {json.dumps(w.latency)}", file=err)
    print(f"setup phases (process age, s): {json.dumps(c.phases)}; "
          f"set-up programs: {json.dumps(c.setup_programs)}", file=err)
    print(f"compiles in window: {w.compiles} ({w.compile_s:.3f} s)",
          file=err)
    print(f"gc in window: {json.dumps(w.gc)}", file=err)
    print(f"longest stalls: {json.dumps(w.stalls)}", file=err)
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=err)
    line = {"correct": check.verdict(numbers) and len(w.records) > 0,
            "attempted": len(w.records), "failed": numbers["unanswered"],
            "metrics": values, "device": device}
    if w.trace is not None:
        line["breakdown"] = {"device_ops": w.trace.device_ops,
                             "idle_gaps": w.trace.idle_gaps}
    line["checks"] = checks
    print(json.dumps(line), file=out, flush=True)
    return 0
