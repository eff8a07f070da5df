"""Readings that the limits of ``correct`` are set from, in one process:
for each seed, the program's served results through a short window at
the cell's own load, compared as a run compares them, and the control's
reading on the same sample. Not one of the benchmark's runs.

    python -m bench.limits --workload <cell> --seeds 1,2,3 --seconds <s>

The control is the reference in the nearest precision below the one the
configuration states: ``high`` (three bfloat16 passes) for float32 at
``highest``, fp8 (e4m3, one scale per matrix) for bfloat16.
"""
from __future__ import annotations

import argparse
import json

from bench import harness, spec
from bench.run import enable_cache, log, prepare


def to_fp8(w):
    """Round a matrix to float8 e4m3 under one scale that maps its largest
    magnitude to the format's largest finite value (448)."""
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def control(system, uids) -> dict:
    """The control's readings on the sample ``uids``: fp8 for a bfloat16
    model; for a float32 one at ``highest``, the TPU's own ``high`` (three
    bfloat16 passes), written out as three passes where the backend
    computes every float32 precision alike (the CPU). (Written out on a
    TPU, the compiler may drop the float32 -> bfloat16 -> float32 round
    trips the split relies on, and the three passes read as one.)"""
    import jax

    cfg = system.cfg
    if cfg["param_dtype"] == "bfloat16":
        return system.compare(uids, cast=to_fp8)
    if cfg.get("matmul_precision") == "highest":
        if jax.default_backend() == "tpu":
            return system.compare(uids, precision="high")
        return system.compare(uids, passes=3)
    raise ValueError("no control for a float32 model at default precision: "
                     "state its precision")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--matmul-precision", default=None,
                    help="state this precision in the configuration")
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    if args.matmul_precision:
        cell.config["matmul_precision"] = args.matmul_precision
    seeds = [int(s) for s in args.seeds.split(",")]
    enable_cache()
    system, rec, schedule, _, _ = prepare(cell, seeds[0], args.seconds,
                                          False)
    for i, seed in enumerate(seeds):
        if i:
            system.reseed(seed)
        rec.reset()
        system.start(False)
        win = harness.run_window(system, schedule, args.seconds, rec)
        system.stop()
        uids = system.sample()
        if hasattr(system, "collect"):
            system.collect(uids)
        checks = {c.name: c.value for c in system.checks(uids)}
        row = {"seed": seed, "sampled": len(uids),
               "tokens": harness.tokens_in(rec, win), "program": checks,
               "control": control(system, uids)}
        if "logit_gap" in checks:
            # A token altered where it is produced: the next id served.
            row["fault_token"] = system.compare(uids, shift=1)["logit_gap"]
        log("limits " + json.dumps(row))
        system.reset()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
