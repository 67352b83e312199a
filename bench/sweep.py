"""Find an open-loop cell's knee: the highest offered rate whose backlog
does not grow over the window.

    python bench/sweep.py --workload <name> --rates 20,40,80 --seconds 10 --seed <n>

One process sets the cell up once; before each rate it drops every
compiled program from memory and starts a fresh service, so each rate
pays the compiles a fresh run would.  For each rate it prints one JSON
line: the rate offered, the rate answered inside the window, the
median and 95th-percentile latency, the median latency of the second
and of the last fifth of the arrivals, the compiles in the window, and
whether the rate was sustained (``sustained``, the rule below).  The last line gives the knee, the
highest rate sustained with every lower rate of the sweep sustained
too, and the rate a cell offers, four fifths of it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# A rate is sustained when the answers inside the window are at least
# this share of the requests due a median latency before it closes, ...
KEEP_UP = 0.97
# ... the last fifth of the arrivals waits (median) at most this many
# times what the second fifth waits, so the backlog does not grow, ...
TAIL_GROWTH = 1.25
# ... and the scheduler sends on time (median lateness in ms, the
# admission window's length).
LATE_MS = 2.0
CELL_SHARE = 0.8


def sustained(row: dict) -> bool:
    early, tail = row["second_fifth_p50_ms"], row["last_fifth_p50_ms"]
    return (row["keep_up"] >= KEEP_UP
            and early is not None and tail is not None
            and tail <= TAIL_GROWTH * early
            and row["lateness_p50_ms"] <= LATE_MS)


def knee(rows: list[dict]) -> float | None:
    """The highest rate sustained with every lower rate sustained."""
    best = None
    for row in sorted(rows, key=lambda r: r["rate_per_s"]):
        if not row["sustained"]:
            break
        best = row["rate_per_s"]
    return best


def _median_ms(records) -> float | None:
    import numpy as np

    ms = [r.latency_s * 1e3 for r in records if r.answered]
    return float(np.median(ms)) if ms else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from harness import cell

    c = cell.set_up(args.workload, args.seed)
    rows = []
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            c.close()
            jax.clear_caches()
            c.start_service()
            w = cell.measure(c, args.seed + 1 + i, args.seconds,
                             rate_per_s=rate)
            recs = sorted(w.records, key=lambda r: r.due)
            fifth = len(recs) // 5
            end = min(r.due for r in recs) + w.seconds
            answered = sum(1 for r in recs if r.answered and r.done <= end)
            cut = end - w.latency.get("latency_p50_ms", 0.0) / 1e3
            row = {"rate_per_s": rate, "requests": len(recs),
                   "answered_per_s": answered / w.seconds,
                   "keep_up": answered / max(1, sum(1 for r in recs
                                                    if r.due <= cut)),
                   **w.latency,
                   "second_fifth_p50_ms": _median_ms(recs[fifth:2 * fifth]),
                   "last_fifth_p50_ms": _median_ms(recs[4 * fifth:]),
                   "compiles": w.compiles, "compile_s": w.compile_s,
                   "plan_time_s": w.counters["cache.plan_time_s"],
                   "gather_time_s": w.counters["cache.gather_time_s"],
                   "windows": w.counters["admission.windows"]}
            row["sustained"] = sustained(row)
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        c.close()
    k = knee(rows)
    print(json.dumps({"knee_per_s": k, "cell_rate_per_s":
                      None if k is None else CELL_SHARE * k}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
