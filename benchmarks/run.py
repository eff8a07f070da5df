"""Benchmark driver: one module per paper table/figure + the roofline.

  PYTHONPATH=src python -m benchmarks.run            # quick (CPU-minutes)
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale
  PYTHONPATH=src python -m benchmarks.run --only table2_speedup
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

from repro.utils.compile_cache import enable_compile_cache

MODULES = [
    "fig2_amplification",
    "fig3_compression",
    "fig4_accuracy_vs_c",
    "fig5_stability",
    "fig6_per_layer",
    "table2_speedup",
    "fig7_threshold",
    "fig8_bandwidth",
    "table3_edge_power",
    "ilp_solve_time",
    "calibration",
    "codec",
    "fleet",
    "pipeline_serving",
    "token_streaming",
    "meshed_tail",
    "roofline",
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    mods = [args.only] if args.only else MODULES
    quick = not args.full
    failures = []
    t00 = time.perf_counter()
    for name in mods:
        t0 = time.perf_counter()
        print(f"\n{'=' * 72}\n== benchmarks.{name}\n{'=' * 72}")
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            payload = mod.run(quick=quick)
            if isinstance(payload, dict):
                # Machine-readable trajectory: every scalar in the payload
                # appends to results/BENCH_<name>.json (see record_bench).
                from benchmarks.common import flatten_metrics, record_bench

                metrics = flatten_metrics(payload)
                if metrics:
                    record_bench(name, metrics, quick=quick)
            print(f"-- {name} OK ({time.perf_counter() - t0:.1f}s)")
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failures.append((name, repr(e)))
    print(f"\n{'=' * 72}")
    print(f"{len(mods) - len(failures)}/{len(mods)} benchmarks passed "
          f"in {time.perf_counter() - t00:.0f}s")
    for n, e in failures:
        print(f"  FAIL {n}: {e}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
