"""The traffic generator: one schedule for every run of a mix, whatever
its seed, and the parameters of each mix file honoured."""
from __future__ import annotations

import numpy as np
import pytest

from bench import spec, traffic

MIXES = sorted(p.stem for p in (spec.BENCH_DIR / "traffic").glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    t = spec.load_json(spec.traffic_path(mix))
    a = traffic.generate(t, 30.0)
    b = traffic.generate(t, 30.0)
    assert a == b and a.driver == t["driver"]
    # The mix's own schedule seed, not the run's, orders the work.
    other = traffic.generate(dict(t, schedule_seed=7), 30.0)
    assert [r.t for r in a.requests] != [r.t for r in other.requests]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    """The run's seed reaches no part of the schedule: it is not an
    argument of the generator, and the schedule reads no global state."""
    t = spec.load_json(spec.traffic_path(mix))
    np.random.seed(1)
    a = traffic.generate(t, 30.0)
    np.random.seed(99)
    b = traffic.generate(t, 30.0)
    assert a == b
    assert len(a.requests) > 0


@pytest.mark.parametrize("mix", MIXES)
def test_sizes_within_the_stated_ranges(mix):
    t = spec.load_json(spec.traffic_path(mix))
    s = traffic.generate(t, 30.0)
    for key, attr in (("prompt", "prompt_len"), ("output", "output_len")):
        d = t.get(key)
        if d is None:
            continue
        v = np.array([getattr(r, attr) for r in s.requests])
        assert v.min() >= d["min"] and v.max() <= d["max"]
        assert np.all(v % d.get("round_up", 1) == 0)


def test_lognormal_median_and_rounding():
    d = {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32,
         "max": 1024, "round_up": 64}
    v = traffic.sizes(d, 1001, np.random.default_rng(0))
    assert np.median(v) == 256
    assert set(np.unique(v)) <= set(range(64, 1025, 64))


def test_poisson_count_and_placement():
    arr = {"process": "poisson", "per_s": 4.0}
    t = traffic.arrival_times(arr, 30.0, np.random.default_rng(5))
    assert len(t) == 120
    assert np.all(np.diff(t) >= 0) and t.min() >= 0 and t.max() < 30


def test_bursts_rate_in_each_stretch():
    arr = {"process": "bursts", "period_s": 5.0, "burst_s": 1.0,
           "burst_per_s": 30.0, "base_per_s": 5.0}
    t = traffic.arrival_times(arr, 10.0, np.random.default_rng(5))
    in_burst = np.sum((t % 5.0) < 1.0)
    assert in_burst == 60 and len(t) == 60 + 40


def test_closed_loop_pool():
    t = {"driver": "closed_loop", "clients": 16, "pool": 64,
         "output": {"dist": "uniform", "min": 16, "max": 64}}
    s = traffic.generate(t, 30.0)
    assert s.driver == "closed_loop" and s.params["clients"] == 16
    assert len(s.requests) == 64 and all(r.t == 0 for r in s.requests)
    out = sorted(r.output_len for r in s.requests)
    assert out[0] == 16 and out[-1] == 64


def test_unknown_arrival_process_is_refused():
    with pytest.raises(KeyError):
        traffic.generate({"driver": "open_loop",
                          "arrivals": {"process": "no_such_process"}}, 5.0)
