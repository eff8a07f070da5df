"""Offered-load sweep of one cell, in one process: Poisson arrivals at
each rate in turn, each over its own window, to find the highest rate
the served path sustains (the knee). Not one of the benchmark's runs.

    python -m bench.sweep --workload <cell> --seed <n> --seconds <s> --rates 2,4,8
"""
from __future__ import annotations

import argparse
import copy
import json

from bench import harness, spec, traffic
from bench.run import enable_cache, log, prepare


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    mixes = []
    for r in rates:
        mix = copy.deepcopy(cell.traffic)
        mix["driver"] = "open_loop"
        mix["arrivals"] = {"process": "poisson", "per_s": r}
        mixes.append(mix)
    enable_cache()
    system, rec, _, compiles, _ = prepare(cell, args.seed, args.seconds,
                                          False)
    schedules = [traffic.generate(m, args.seconds) for m in mixes]
    lengths = sorted({n for s in schedules for n in s.prompt_lengths})
    system.warm(traffic.Schedule("open_loop", [
        traffic.Request(0, 0.0, n, 1) for n in lengths]))
    for rate, sched in zip(rates, schedules):
        rec.reset()
        before = compiles.snapshot()
        system.start(False)
        win = harness.run_window(system, sched, args.seconds, rec)
        system.stop()
        left = system.queued()
        ttft = harness.first_token_ms(rec, win)
        lat = harness.request_latency_ms(rec, win)
        row = {
            "rate": rate, "due": len(harness.due_in(rec, win)),
            "queued_at_end": left,
            "compiles_in_window": compiles.snapshot()[0] - before[0],
            "ttft_p50_ms": harness.percentile(ttft, 50),
            "ttft_p95_ms": harness.percentile(ttft, 95),
            "itl_p50_ms": harness.percentile(
                harness.token_gaps_ms(rec, win), 50),
            "itl_p95_ms": harness.percentile(
                harness.token_gaps_ms(rec, win), 95),
            "tokens_per_s": harness.tokens_in(rec, win) / win.seconds,
            "req_latency_p50_ms": harness.percentile(lat, 50),
            "req_latency_p95_ms": harness.percentile(lat, 95),
            "lateness_p95_ms": harness.percentile(win.lateness_s, 95) * 1e3,
        }
        log("sweep " + json.dumps(row))
        system.reset()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
