"""The one traffic generator.  A traffic mix is a JSON file of
parameters (``bench/traffic/<name>.json``); this module turns it, a
configuration and a seed into requests and arrival times.

A mix is a list of *streams*.  Each draw of a stream yields one request
description (see ``reference.py`` for its form):

* a static stream yields the same request every time;
* a drifting stream moves by a random whole number of steps along one
  axis on every draw (a storm track, a rolling window);
* a random stream yields a fresh request every time (ad-hoc regions).

Open-loop arrivals: ``round(rate * seconds)`` requests, whose gaps are
the quantiles of an exponential distribution in an order drawn from the
seed, scaled to fill the window.  Which stream each arrival draws is
stratified the same way: every seed sends the same number of requests
of each stream, in another order, so seeds change the order of the work
and not its amount.  Closed-loop clients walk their own seeded
permutations of the population (an epoch shuffle).
"""

from __future__ import annotations

import copy
import itertools
import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from .reference import axis_values

DATA = Path(__file__).resolve().parents[1] / "data"


def load_polygons() -> dict[str, list]:
    return json.loads((DATA / "countries.json").read_text())["polygons"]


def draw_probabilities(draw: dict, n: int) -> np.ndarray:
    """Share of draws that go to each of ``n`` streams, in listed order."""
    kind = draw["kind"]
    if kind == "uniform":
        return np.full(n, 1.0 / n)
    if kind == "weights":
        w = np.asarray(draw["weights"], np.float64)
        if len(w) != n or (w < 0).any() or w.sum() <= 0:
            raise ValueError(f"weights {draw['weights']} for {n} streams")
        return w / w.sum()
    if kind == "zipf":
        # numpy's zipf(s) - 1, with ranks past the last folded onto it
        from scipy.special import zeta

        s = float(draw["s"])
        if s <= 1.0:
            raise ValueError("a Zipf exponent must be > 1")
        p = np.arange(1, n, dtype=np.float64) ** -s / zeta(s, 1)
        return np.append(p, max(0.0, 1.0 - p.sum()))
    raise ValueError(f"unknown draw {kind!r}")


def stratified_counts(p: np.ndarray, n: int) -> np.ndarray:
    """``n`` draws split by ``p`` (largest remainder)."""
    exact = p * n
    counts = np.floor(exact).astype(np.int64)
    rest = n - counts.sum()
    counts[np.argsort(-(exact - counts), kind="stable")[:rest]] += 1
    return counts


@dataclass
class Stream:
    desc: dict
    drift: dict | None = None
    random: dict | None = None
    offset: int = 0
    draws: int = 0

    @property
    def opener(self) -> dict:
        return copy.deepcopy(self.desc)

    def draw(self, rng: np.random.Generator, lead_values: dict) -> dict:
        self.draws += 1
        if self.random is not None:
            return _random_desc(self.desc, self.random, rng, lead_values)
        if self.drift is None:
            return copy.deepcopy(self.desc)
        if self.draws > 1:
            lo, hi = self.drift["steps"]
            self.offset += int(rng.integers(lo, hi + 1))
        steps = self.offset % int(self.drift["wrap"])
        return _shifted(self.desc, self.drift["axis"],
                        steps * float(self.drift["step"]))


def _shifted(desc: dict, axis: str, delta: float) -> dict:
    out = copy.deepcopy(desc)
    if axis in ("lat", "lon"):
        col = 0 if axis == "lat" else 1
        h = out["horiz"]
        if h[0] == "polygon":
            for v in h[1]:
                v[col] += delta
        elif h[0] == "box":
            h[1][col] += delta
            h[2][col] += delta
        else:
            h[1 + col] += delta
        return out
    sel = out["lead"][axis]
    out["lead"][axis] = [sel[0]] + [v + delta for v in sel[1:]]
    return out


def _random_desc(desc: dict, spec: dict, rng: np.random.Generator,
                 lead_values: dict) -> dict:
    out = copy.deepcopy(desc)
    h = out["horiz"]
    if "shift_deg" in spec:
        dlat, dlon = spec["shift_deg"]
        sl, so = rng.uniform(-dlat, dlat), rng.uniform(-dlon, dlon)
        if h[0] == "polygon":
            for v in h[1]:
                v[0] += sl
                v[1] += so
        elif h[0] == "box":
            for corner in h[1:]:
                corner[0] += sl
                corner[1] += so
    if "point_lat" in spec:
        lo, hi = spec["point_lat"]
        out["horiz"] = ["point", float(rng.uniform(lo, hi)),
                        float(rng.uniform(0.0, 360.0))]
    for axis, how in spec.get("lead", {}).items():
        vals = lead_values[axis]
        if how == "select":
            pick = float(vals[rng.integers(len(vals))])
            out["lead"][axis] = ["select", pick]
        else:                                  # ["span", n indices]
            n = int(how[1])
            i = int(rng.integers(len(vals) - n + 1))
            out["lead"][axis] = ["span", float(vals[i]),
                                 float(vals[i + n - 1])]
    return out


def _expand(spec: dict, polygons: dict, lead: dict) -> list[dict]:
    """One stream spec → its streams (``expand`` takes the product of
    the listed select values, first key outermost; ``"all"`` lists every
    value of the axis)."""
    horiz = copy.deepcopy(spec["horiz"])
    if horiz[0] == "polygon" and isinstance(horiz[1], str):
        horiz[1] = copy.deepcopy(polygons[horiz[1]])
    base = {"lead": copy.deepcopy(spec.get("lead", {})), "horiz": horiz}
    expand = {axis: lead[axis] if vals == "all" else vals
              for axis, vals in spec.get("expand", {}).items()}
    out = []
    for combo in itertools.product(*expand.values()):
        desc = copy.deepcopy(base)
        for axis, v in zip(expand, combo):
            desc["lead"][axis] = ["select", float(v)]
        out.append({"desc": desc, "drift": spec.get("drift"),
                    "random": spec.get("random")})
    return out


@dataclass
class Traffic:
    """A mix bound to a configuration: its streams and how they arrive."""

    spec: dict
    streams: list[Stream]
    lead_values: dict

    @classmethod
    def load(cls, spec: dict, config: dict) -> "Traffic":
        polygons = load_polygons()
        lead = {a["name"]: axis_values(a) for a in config["lead_axes"]}
        streams = [Stream(**s) for item in spec["streams"]
                   for s in _expand(item, polygons, lead)]
        return cls(spec=spec, streams=streams, lead_values=lead)

    @property
    def loop(self) -> dict:
        return self.spec["loop"]

    def warm_set(self) -> list[dict]:
        """Requests planned in set-up: every static stream's request and
        every drifting stream's opener (``warm``: all), the openers
        alone (``openers``) or nothing (``none``)."""
        how = self.spec.get("warm", "none")
        if how == "none":
            return []
        return [s.opener for s in self.streams
                if s.random is None and (how == "all" or s.drift)]

    def open_loop(self, seed: int, seconds: float,
                  ) -> tuple[np.ndarray, list[dict]]:
        """Due times (s after window start) and request descriptions."""
        rng = np.random.default_rng([seed, 1])
        n = max(1, round(float(self.loop["rate_per_s"]) * seconds))
        q = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-q))
        gaps *= seconds / gaps.sum()
        due = np.cumsum(gaps) - gaps           # the last gap ends the window
        p = draw_probabilities(self.spec["draw"], len(self.streams))
        order = rng.permutation(np.repeat(np.arange(len(self.streams)),
                                          stratified_counts(p, n)))
        descs = [self.streams[i].draw(rng, self.lead_values) for i in order]
        return due, descs

    def client_cycle(self, seed: int, client: int):
        """Endless request descriptions for one closed-loop client:
        seeded permutations of the population, one after another."""
        rng = np.random.default_rng([seed, 2, client])
        while True:
            for i in rng.permutation(len(self.streams)):
                yield self.streams[i].draw(rng, self.lead_values)
