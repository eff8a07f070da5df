"""Bursts on a steady base: ``{"process": "bursts", "period_s": P,
"burst_s": B, "burst_per_s": X, "base_per_s": Y}``: Poisson arrivals at
rate X for B seconds at the start of every period, at rate Y for the
rest, each stretch conditioned on its count."""
from bench.traffic import piecewise_poisson


def segments(arrivals, seconds):
    """(start, length, rate) stretches covering [0, seconds)."""
    period, burst = float(arrivals["period_s"]), float(arrivals["burst_s"])
    out, t = [], 0.0
    while t < seconds:
        b = min(burst, seconds - t)
        out.append((t, b, float(arrivals["burst_per_s"])))
        rest = min(period - burst, seconds - t - b)
        if rest > 0:
            out.append((t + b, rest, float(arrivals["base_per_s"])))
        t += period
    return out


def times(arrivals, seconds, rng):
    return piecewise_poisson(segments(arrivals, seconds), rng)
