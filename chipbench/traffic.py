"""Open-loop request schedules of a traffic mix, and prompts from a seed.

A mix (`chipbench/traffic/<mix>.json`) is data:

    {"arrivals": {"process": "poisson", "rate_rps": 4.0},
     "lengths": {"dist": "lognormal", "mean": 768, "sigma": 0.8,
                 "min": 128, "max": 2048},
     "out_len": 1}

`process` is "poisson" (exponential gaps).  `dist` is "lognormal" (the
lognormal arithmetic of `repro.core.trace`, copied here so that a change of
the program cannot move the yardstick).

The schedule (when each request is due and how long its prompt is) is the
mix's own and the same for every seed; the seed draws only the prompts'
token ids (and, elsewhere, the weights).  Lengths and Poisson gaps are the
distribution's quantiles at (i + 0.5) / n, put in one fixed order.  An
order drawn from the seed would move a queue's tail by itself: in a
simulated queue at four fifths of its capacity, six orders of the same
requests put the 95th-percentile wait's quartiles 35-40% of the median
apart, which no bound can hold.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, List

import numpy as np


_ORDER = 20240611  # the one fixed draw that orders every schedule


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for (seed, stream); any integer seed, negative or
    wider than 64 bits, maps to one generator."""
    return np.random.default_rng([seed % (1 << 64), stream])


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    due: float  # seconds after the window opens
    length: int  # real prompt tokens


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def prompt_lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """The mix's n prompt lengths, in ascending order."""
    dist = spec["dist"]
    if dist != "lognormal":
        raise ValueError(f"unknown length distribution {dist!r}")
    sigma = float(spec["sigma"])
    mu = math.log(float(spec["mean"])) - sigma ** 2 / 2.0
    z = np.array([statistics.NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.exp(mu + sigma * z)
    return np.clip(x, int(spec["min"]), int(spec["max"])).astype(np.int64)


def arrival_gaps(spec: Dict[str, Any], n: int) -> np.ndarray:
    """The mix's n gaps between arrivals (seconds), in ascending order."""
    rate = float(spec["rate_rps"])
    proc = spec["process"]
    if proc != "poisson":
        raise ValueError(f"unknown arrival process {proc!r}")
    return np.sort(-np.log1p(-_quantiles(n)) / rate)


def schedule(traffic: Dict[str, Any], seconds: float) -> List[Arrival]:
    """The requests due in a window of `seconds`: round(rate x seconds) of
    them, the first at 0, their gaps scaled so that the rate is exact."""
    arr = traffic["arrivals"]
    n = max(int(round(float(arr["rate_rps"]) * seconds)), 1)
    rng = np.random.default_rng(_ORDER)
    lengths = prompt_lengths(traffic["lengths"], n)[rng.permutation(n)]
    gaps = arrival_gaps(arr, n)[rng.permutation(n)]
    due = np.concatenate(([0.0], np.cumsum(gaps)[:-1])) * (seconds
                                                           / gaps.sum())
    return [Arrival(rid=i, due=float(due[i]), length=int(lengths[i]))
            for i in range(n)]


def prompt_tokens(arrivals: List[Arrival], vocab: int,
                  seed: int) -> Dict[int, np.ndarray]:
    """Token ids of every prompt, drawn from the seed."""
    rng = rng_for(seed, 2)
    return {a.rid: rng.integers(0, vocab, a.length, dtype=np.int32)
            for a in arrivals}
