"""Readings of ``correct``'s numbers for the program and for the control,
over many seeds, in one process.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds 5 [--control-seeds 1,2,3]

For each seed the payload is made anew, a fresh service is warmed, and
a short window runs at the cell's own load; then the program's answers
are compared with the reference (the lower readings), and, for the
control seeds, the control is compared in the same way: the reference
put in the program's place with its values in bfloat16 (the upper
readings).  Prints one JSON line per seed.  The benchmark's own runs
never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}

    from harness import cell, check
    from harness.payload import make_device_payload
    from harness.reference import Reference

    c = cell.set_up(args.workload, seeds[0])
    ref = Reference(c.config)
    try:
        for i, seed in enumerate(seeds):
            if i:
                c.close()
                c.payload = None
                gc.collect()
                c.payload = make_device_payload(seed,
                                                int(c.config["elements"]))
                c.start_service()
            w = cell.measure(c, seed, args.seconds)
            row = {"seed": seed, "requests": len(w.records),
                   "elements": int(sum(len(r.values)
                                       for r in w.records if r.answered)),
                   "program": check.compare(w.records, ref, seed)}
            if seed in control_seeds:
                row["control"] = check.control(w.records, ref, seed)
            print(json.dumps(row), flush=True)
    finally:
        c.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
