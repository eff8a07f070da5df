"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and turns them into the requests a run
serves.

The schedule does not depend on the run's seed: every run of a cell
serves the same arrivals and sizes in the same order, drawn once from the
mix's own ``schedule_seed`` (0 where the file gives none). The run's seed
picks the weights and the inputs (token ids, images), which change no
amount of work. Sizes are stratified quantiles of the stated
distribution, so the multiset of sizes is the distribution's and not one
draw's.

Parameters (all in the JSON file):

* ``driver``: the driver that hands the requests to the served path,
  ``bench/drivers/<driver>.py``; it may read keys of its own from the mix
  (``clients``, ``think_s`` for ``closed_loop``).
* ``arrivals``: ``{"process": <name>, ...}``, the arrival process
  ``bench/arrivals/<name>.py`` and its parameters; its ``times(arrivals,
  seconds, rng)`` gives the arrival times over the window. A mix without
  arrivals (a closed loop) has ``pool``: how many distinct requests its
  driver cycles through.
* ``prompt``, ``output``: size distributions, each ``{"dist":
  "lognormal", "median", "sigma", "min", "max", "round_up"}`` or
  ``{"dist": "uniform", "min", "max"}``. Absent for requests without a
  length (images).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Any, Dict, List, Optional

import numpy as np


@dataclass
class Request:
    uid: int
    t: float                 # scheduled arrival, s from the window's start
    prompt_len: int = 0
    output_len: int = 0


@dataclass
class Schedule:
    driver: str              # bench/drivers/<driver>.py
    requests: List[Request]
    params: Dict[str, Any] = field(default_factory=dict)   # the mix

    @property
    def prompt_lengths(self) -> List[int]:
        return sorted({r.prompt_len for r in self.requests})


def _quantile(dist: Dict[str, Any], p: np.ndarray) -> np.ndarray:
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = int(dist["min"]), int(dist["max"])
        return lo + np.floor(p * (hi - lo + 1))
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(q)) for q in p])
        return float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    raise ValueError(f"unknown size distribution {kind!r}")


def sizes(dist: Optional[Dict[str, Any]], n: int,
          rng: np.random.Generator) -> np.ndarray:
    """``n`` sizes at the distribution's quantiles (i + 1/2) / n, rounded
    up to ``round_up``, clipped to [min, max], in a seeded order."""
    if dist is None:
        return np.zeros(n, np.int64)
    p = (np.arange(n) + 0.5) / max(n, 1)
    v = np.ceil(_quantile(dist, p))
    step = int(dist.get("round_up", 1))
    v = np.ceil(v / step) * step
    lo, hi = dist.get("min"), dist.get("max")
    if lo is not None or hi is not None:
        v = np.clip(v, lo, hi)
    return rng.permutation(v.astype(np.int64))


def piecewise_poisson(segments, rng: np.random.Generator) -> np.ndarray:
    """Poisson arrivals conditioned on their count: round(rate x length)
    arrivals in each (start, length, rate) stretch, placed uniformly at
    random in it."""
    times = []
    for start, length, rate in segments:
        n = int(round(rate * length))
        times.append(start + np.sort(rng.uniform(0.0, length, n)))
    return np.concatenate(times) if times else np.zeros(0)


def arrival_times(arrivals: Dict[str, Any], seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Arrival times over [0, seconds) from the named process."""
    from bench import spec

    process = spec.load_part("arrivals", arrivals["process"])
    return np.asarray(process.times(arrivals, seconds, rng), np.float64)


def generate(mix: Dict[str, Any], seconds: float) -> Schedule:
    """The schedule of a mix over a window of ``seconds``: the same for
    every run."""
    rng = np.random.default_rng(int(mix.get("schedule_seed", 0)))
    if "arrivals" in mix:
        times = arrival_times(mix["arrivals"], seconds, rng)
    else:
        times = np.zeros(int(mix["pool"]))
    n = len(times)
    prompt = sizes(mix.get("prompt"), n, rng)
    output = sizes(mix.get("output"), n, rng)
    reqs = [Request(i, float(times[i]), int(prompt[i]), int(output[i]))
            for i in range(n)]
    return Schedule(mix["driver"], reqs, mix)
