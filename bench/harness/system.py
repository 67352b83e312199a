"""The system under test, as the benchmark drives it: the served path of
``launch/serve.py --mode extract`` — a ``ShardedExtractionService``
behind an ``AdmissionQueue`` reading a payload placed once on the
device.  This is the only module that imports the program.
"""

from __future__ import annotations

from dataclasses import asdict

import numpy as np


def to_request(desc: dict):
    """A request description → the program's ``Request``."""
    from repro.core import Box, Polygon, Request, Select, Span

    shapes = []
    for axis, sel in desc.get("lead", {}).items():
        if sel[0] == "select":
            shapes.append(Select(axis, [float(sel[1])]))
        else:
            shapes.append(Span(axis, float(sel[1]), float(sel[2])))
    h = desc["horiz"]
    if h[0] == "polygon":
        shapes.append(Polygon(("lat", "lon"), np.asarray(h[1], np.float64)))
    elif h[0] == "box":
        shapes.append(Box(("lat", "lon"), list(h[1]), list(h[2])))
    else:
        shapes += [Select("lat", [float(h[1])]), Select("lon", [float(h[2])])]
    return Request(shapes)


def build_cube(config: dict):
    """The program's datacube for a configuration (its ``system``
    section names the class in ``repro.dataplane.weather``)."""
    from repro.dataplane import weather

    sysc = config["system"]
    cls = getattr(weather, sysc["cube"])
    wc = cls(**sysc["kwargs"], dtype=np.dtype(config["dtype"]))
    if wc.cube.n_elements != config["elements"]:
        raise ValueError(f"{sysc['cube']} holds {wc.cube.n_elements} "
                         f"elements; the configuration states "
                         f"{config['elements']}")
    return wc.cube


class Served:
    """One service and its admission queue over one payload."""

    def __init__(self, config: dict, payload, cube=None):
        from repro.serve.sharded import (AdmissionQueue,
                                         ShardedExtractionService)

        svc_cfg = config["service"]
        self.cube = cube if cube is not None else build_cube(config)
        self.service = ShardedExtractionService(
            self.cube, shards=int(svc_cfg["shards"]),
            capacity_per_shard=int(svc_cfg["capacity_per_shard"]))
        self.queue = AdmissionQueue(
            self.service, flat_data=payload,
            window_s=float(svc_cfg["window_ms"]) / 1e3,
            max_batch=int(svc_cfg["max_batch"]))

    def counters(self) -> dict:
        """Every counter the program keeps at its layer boundaries."""
        out = {f"cache.{k}": v for k, v in asdict(self.service.stats).items()}
        out.update({f"admission.{k}": v
                    for k, v in asdict(self.queue.snapshot()).items()})
        return out

    def close(self) -> None:
        self.queue.close(timeout=120.0)
