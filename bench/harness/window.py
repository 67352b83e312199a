"""The measured window: an open-loop scheduler or closed-loop clients in
front of the admission queue, and the statistics taken over all of the
window's requests."""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

# A request that is due in the window and has no answer this long after
# the window closes never comes.
ANSWER_WAIT_S = 60.0


@dataclass
class Record:
    """One request: when it was due (open loop) or sent (closed loop),
    when it was handed to the queue, and how it ended.  Of an answer it
    keeps the plan and the values the check compares, not the program's
    result object: the request and what the program memoized on it are
    freed once answered, as a server's would be."""

    desc: dict
    due: float
    sent: float = float("nan")
    done: float = float("nan")
    plan: Any = None
    values: Any = None
    error: BaseException | None = None

    @property
    def answered(self) -> bool:
        return self.values is not None and self.error is None

    def keep(self, result) -> None:
        self.plan, self.values = result.plan, result.values

    @property
    def latency_s(self) -> float:
        return self.done - self.due


def run_open(queue, make_request: Callable[[dict], Any], descs: list[dict],
             due: np.ndarray, seconds: float,
             on_close: Callable[[], None] = lambda: None,
             ) -> tuple[list[Record], float, float]:
    """Submit ``make_request(descs[i])`` at ``t0 + due[i]`` from this
    thread, whatever the server is doing; call ``on_close`` when the
    window closes, then wait for the answers still due.  Returns the
    records and the window's start and end on ``time.perf_counter``'s
    clock.  ``submit`` returns a future at once, so the schedule never
    waits on the server.  Each request is made as it is sent, so the
    process holds only those in flight, as a server does."""
    records = [Record(desc=d, due=float(t)) for d, t in zip(descs, due)]
    done = threading.Event()
    left = [len(records)]
    lock = threading.Lock()

    def finish(rec: Record, fut) -> None:
        rec.done = time.perf_counter()
        try:
            rec.keep(fut.result())
        except Exception as e:          # counted as failed, never lost
            rec.error = e
        with lock:
            left[0] -= 1
            if left[0] == 0:
                done.set()

    t0 = time.perf_counter() + 0.01
    for rec in records:
        rec.due += t0
        pause = rec.due - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
        rec.sent = time.perf_counter()
        try:
            fut = queue.submit(make_request(rec.desc))
        except Exception as e:
            rec.error = e
            finish(rec, _failed(e))
            continue
        fut.add_done_callback(lambda f, r=rec: finish(r, f))
    end = t0 + seconds
    pause = end - time.perf_counter()
    if pause > 0:
        time.sleep(pause)
    on_close()
    if not records:
        done.set()
    done.wait(max(0.0, end + ANSWER_WAIT_S - time.perf_counter()))
    return records, t0, end


def _failed(e: BaseException):
    from concurrent.futures import Future

    f: Future = Future()
    f.set_exception(e)
    return f


def run_closed(queue, make_request: Callable[[dict], Any],
               cycles: list, seconds: float,
               on_close: Callable[[], None] = lambda: None,
               ) -> tuple[list[Record], float, float]:
    """``len(cycles)`` clients, each sending its next request as soon as
    the previous one is answered, until the window closes; then
    ``on_close``, and the requests still out are waited for."""
    per_client: list[list[Record]] = [[] for _ in cycles]
    barrier = threading.Barrier(len(cycles) + 1)
    clock = {}

    def client(i: int) -> None:
        mine = per_client[i]
        barrier.wait()
        end = clock["end"]
        while time.perf_counter() < end:
            desc = next(cycles[i])
            rec = Record(desc=desc, due=time.perf_counter())
            rec.sent = rec.due
            try:
                rec.keep(queue.extract(make_request(desc),
                                       timeout=ANSWER_WAIT_S))
            except Exception as e:
                rec.error = e
            rec.done = time.perf_counter()
            mine.append(rec)

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"bench-client-{i}")
               for i in range(len(cycles))]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    clock["end"] = t0 + seconds
    barrier.wait()
    pause = clock["end"] - time.perf_counter()
    if pause > 0:
        time.sleep(pause)
    on_close()
    for t in threads:
        t.join(2 * ANSWER_WAIT_S)
    return [r for recs in per_client for r in recs], t0, clock["end"]


def latency_stats(records: list[Record]) -> dict:
    """Median and 95th percentile (ms) over every answered request of
    the window, and how late the scheduler sent."""
    lat = np.array([r.latency_s for r in records if r.answered]) * 1e3
    late = np.array([r.sent - r.due for r in records
                     if np.isfinite(r.sent)]) * 1e3
    out = {"answered": int(len(lat))}
    if len(lat):
        out["latency_p50_ms"] = float(np.percentile(lat, 50))
        out["latency_p95_ms"] = float(np.percentile(lat, 95))
    if len(late):
        out["lateness_p50_ms"] = float(np.percentile(late, 50))
        out["lateness_max_ms"] = float(late.max())
    return out


def throughput(records: list[Record], t0: float, end: float) -> float:
    """Requests answered inside the window, over its length."""
    n = sum(1 for r in records if r.answered and r.done <= end)
    return n / (end - t0)


def stalls(records: list[Record], t0: float, compile_spans: list,
           gc_pauses: list, top: int = 3) -> list[dict]:
    """The ``top`` longest gaps between successive answers of the window
    (s after its start, ms long), with the compile and garbage-collection
    time that overlaps each: what held the server while nothing came
    back."""
    done = np.sort([r.done for r in records if np.isfinite(r.done)])
    if len(done) < 2:
        return []
    gaps = np.diff(done)
    out = []
    for i in np.argsort(-gaps)[:top]:
        a, b = done[i], done[i + 1]
        out.append({"at_s": round(float(a - t0), 3),
                    "gap_ms": round(float(1e3 * (b - a)), 1),
                    "compile_ms": round(1e3 * _overlap(compile_spans, a, b),
                                        1),
                    "gc_ms": round(1e3 * _overlap(
                        [(s, s + d) for s, d in gc_pauses], a, b), 1)})
    return out


def _overlap(spans: list, a: float, b: float) -> float:
    return float(sum(max(0.0, min(e, b) - max(s, a)) for s, e in spans))
