"""Poisson arrivals at a steady rate: ``{"process": "poisson", "per_s":
R}``, conditioned on their count (round(R x seconds) arrivals)."""
from bench.traffic import piecewise_poisson


def times(arrivals, seconds, rng):
    return piecewise_poisson([(0.0, seconds, float(arrivals["per_s"]))],
                             rng)
