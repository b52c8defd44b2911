"""The one general traffic generator. A traffic mix is a data file under
``traffic/``; this module turns it, ``--seed`` and ``--seconds`` into a
schedule. It imports neither JAX nor the program (the load generator's
child process runs it).

Every seed gets the *same set* of sizes and arrival gaps in another
order: the sets are the stratified quantiles of the file's distributions
(sample i of n sits at quantile (i + 0.5) / n), and the seed only
permutes them and draws the token ids. A run's work therefore does not
swing with the seed, which is what lets a bound be tight.

Traffic file keys:

- ``loop``: ``"open"`` (arrivals on a schedule, whatever the server does)
  or ``"closed"`` (``clients`` callers, each sending its next request
  when its last one ended).
- ``rate_rps`` (open) or ``clients`` (closed).
- ``ramp_s``: load before the window opens, counted as set-up.
- ``drain_s``: how long after the window a due request may still finish.
- ``generators``: child processes that share the schedule (default 1).
- ``lengths``: for generation, ``{"prompt": dist, "output": dist}`` where
  a dist is ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
  ``{"dist": "uniform", "min", "max"}`` or ``{"dist": "fixed", "value"}``.
- ``images``: for classification, how many seeded inputs rotate.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile_set(dist: Dict[str, Any], n: int) -> List[int]:
    """The n stratified quantiles of ``dist``, as whole numbers."""
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        kind = dist["dist"]
        if kind == "lognormal":
            value = math.exp(math.log(dist["median"])
                             + dist["sigma"] * _NORMAL.inv_cdf(u))
        elif kind == "uniform":
            value = dist["min"] + u * (dist["max"] - dist["min"])
        elif kind == "fixed":
            value = dist["value"]
        else:
            raise ValueError(f"unknown dist {kind!r}")
        if "min" in dist:
            value = max(dist["min"], value)
        if "max" in dist:
            value = min(dist["max"], value)
        out.append(int(round(value)))
    return out


def _shuffled(rng, values: List) -> List:
    values = list(values)
    rng.shuffle(values)
    return values


def _lengths(rng, traffic: Dict[str, Any], n: int) -> List[Dict[str, int]]:
    """n requests' sizes: prompt and output sets permuted independently."""
    if "lengths" not in traffic:
        return [{} for _ in range(n)]
    prompts = _shuffled(rng, quantile_set(traffic["lengths"]["prompt"], n))
    outputs = _shuffled(rng, quantile_set(traffic["lengths"]["output"], n))
    return [{"prompt_len": p, "max_new_tokens": o}
            for p, o in zip(prompts, outputs)]


def _arrivals(rng, rate: float, span: float) -> List[float]:
    """Poisson arrivals over ``span`` seconds: round(rate * span)
    exponential gaps, stratified, scaled to fill the span exactly, in a
    seeded order. Offsets from the start of the span."""
    n = max(1, int(round(rate * span)))
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span / sum(gaps)
    gaps = _shuffled(rng, [g * scale for g in gaps])
    at, out = 0.0, []
    for gap in gaps:
        out.append(at + gap * 0.5)      # centre each arrival in its gap
        at += gap
    return out


def schedule(traffic: Dict[str, Any], seed: int,
             seconds: float) -> Dict[str, Any]:
    """The whole run's requests. Times are offsets from the window's
    start, so the ramp's are negative.

    open   -> {"loop", "requests": [{"id", "due", "measured", ...sizes}]}
    closed -> {"loop", "clients": [[{"id", ...sizes}, ...], ...]}: each
              client's sequence; round j of every client together is one
              stratified set, so what is in flight at any time is one."""
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    if traffic["loop"] == "open":
        rate, ramp = float(traffic["rate_rps"]), float(traffic["ramp_s"])
        requests = []
        for span, offset, measured in ((ramp, -ramp, False),
                                       (float(seconds), 0.0, True)):
            if span <= 0:
                continue
            times = _arrivals(rng, rate, span)
            for at, sizes in zip(times, _lengths(rng, traffic, len(times))):
                requests.append(dict(sizes, id=len(requests),
                                     due=offset + at, measured=measured))
        return {"loop": "open", "requests": requests}
    if traffic["loop"] == "closed":
        clients = int(traffic["clients"])
        rounds = int(traffic.get("rounds", 64))
        sequences: List[List[Dict[str, int]]] = [[] for _ in range(clients)]
        for j in range(rounds):
            for c, sizes in enumerate(_lengths(rng, traffic, clients)):
                sequences[c].append(dict(sizes, id=j * clients + c))
        return {"loop": "closed", "clients": sequences}
    raise ValueError(f"unknown loop {traffic['loop']!r}")


def prompt_ids(seed: int, request_id: int, length: int,
               vocab: int) -> List[int]:
    """One request's prompt: ids drawn over the whole vocabulary."""
    rng = np.random.default_rng([int(seed), 0x9807, int(request_id)])
    return rng.integers(0, vocab, length).tolist()


def images(seed: int, count: int, shape) -> np.ndarray:
    """``count`` seeded uint8 inputs of ``shape``."""
    rng = np.random.default_rng([int(seed), 0x1A6E])
    return rng.integers(0, 256, (count, *shape), dtype=np.uint8)
