"""One run of one benchmark cell on the chip.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's served path (configuration from its file, weights and
inputs from the seed), warms up every shape its traffic uses, then
drives it for ``--seconds`` under the cell's traffic mix. With
``--trace 0`` it reports the cell's end-to-end metrics, with ``--trace
1`` its per-layer metrics from a profiler trace of the window. Then the
served results are compared with the plain reference (``correct``).

Earlier lines describe the run; the last lines on standard error are
each compared number beside its limit; the last line on standard output
is one JSON object. Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from bench import harness, spec, traffic  # noqa: E402

sys.path.insert(0, str(spec.ROOT / "src"))

TRACE_ROOT = spec.BENCH_DIR / "out" / "trace"
# A traced run traces the last this many seconds of its window: the
# steady part, and a trace that is read within the run's time.
TRACE_SECONDS = 8.0


def log(msg: str) -> None:
    print(msg, flush=True)


def peaks_for(kind: str) -> dict:
    table = spec.load_json(spec.BENCH_DIR / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def enable_cache() -> None:
    """The program's persistent compilation cache, at its fixed place in
    the checkout, holding every program however fast it compiled."""
    import jax

    from repro.utils.compile_cache import enable_compile_cache

    log(f"compilation cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "device_kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": peak}


class RunView:
    """What a per-layer metric reader reads: the recorder's spans,
    counters and request events, the measured window, the served system,
    the reduced trace and the peak table."""

    def __init__(self, rec, system, trace, peaks, win=None):
        self.rec, self.system = rec, system
        self.trace, self.peaks = trace, peaks
        self.win = win

    def span_s(self, name: str) -> float:
        return sum(self.rec.spans.get(name, []))

    def span_n(self, name: str) -> int:
        return len(self.rec.spans.get(name, []))


def prepare(cell: spec.Cell, seed: int, seconds: float, trace: bool,
            require_chip: bool = True, peaks: dict = None):
    """Set-up: the chip, the served path built from the seed, and every
    shape of the cell's traffic warmed up. Returns (system, recorder,
    schedule, compile counter, peak table)."""
    import jax

    dev = jax.devices()[0]
    if require_chip:
        if jax.default_backend() != "tpu":
            raise SystemExit(f"JAX found no TPU (backend "
                             f"{jax.default_backend()!r})")
        if len(jax.devices()) < cell.chips:
            raise SystemExit(f"the cell needs {cell.chips} chips, JAX "
                             f"found {len(jax.devices())}")
    peaks = peaks or peaks_for(dev.device_kind)
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}; cell {cell.name}, seed {seed}")
    compiles = harness.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    rec = harness.Recorder(annotate=trace)
    module = importlib.import_module(f"bench.systems.{cell.config['system']}")
    system = module.build(cell.config, seed, rec)
    schedule = traffic.generate(cell.traffic, seconds)
    system.warm(schedule)
    log(f"set-up: {compiles.compiles} compilations "
        f"({compiles.compile_s:.3f} s), {compiles.traces} traces")
    return system, rec, schedule, compiles, peaks


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        require_chip: bool = True, peaks: dict = None) -> dict:
    import jax

    system, rec, schedule, compiles, peaks = prepare(
        cell, seed, seconds, trace, require_chip, peaks)
    trace_dir = TRACE_ROOT / cell.name
    traced = []

    def start_trace():
        """From here on: the profiler, and spans and counters anew."""
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        rec.spans.clear()
        rec.counters.clear()
        traced.append(jax.profiler.TraceAnnotation("bench:window"))
        traced[0].__enter__()

    before = compiles.snapshot()
    rec.reset()
    system.start(trace)
    gc_watch = harness.GcWatch()
    gc.callbacks.append(gc_watch)
    setup_s = time.perf_counter() - T_START
    gc_watch.on = True
    try:
        win = harness.run_window(
            system, schedule, seconds, rec,
            at=max(0.0, seconds - TRACE_SECONDS) if trace else None,
            then=start_trace if trace else None)
    finally:
        gc_watch.on = False
        gc.callbacks.remove(gc_watch)
    system.stop()
    if traced:
        traced[0].__exit__(None, None, None)
        jax.profiler.stop_trace()
    c_in, t_in = (a - b for a, b in zip(compiles.snapshot(), before))
    log(f"window: {seconds} s, {win.submitted} requests submitted; "
        f"{c_in} compilations and {t_in} traces inside it")
    log(f"garbage collection inside it: {gc_watch.summary()}")
    if win.lateness_s:
        log(f"generator lateness: median "
            f"{harness.percentile(win.lateness_s, 50) * 1e3:.3f} ms, max "
            f"{max(win.lateness_s) * 1e3:.3f} ms")
    device = device_info(cell.chips)
    log(f"memory: peak {device['memory_peak_bytes']} B in use on the "
        f"fullest chip")
    if hasattr(system, "describe"):
        for line in system.describe():
            log(line)

    result = {}
    if trace:
        from bench import trace_reduce

        path = trace_reduce.find_xplane(str(trace_dir))
        summary = trace_reduce.reduce_trace(path)
        view = RunView(rec, system, summary, peaks, win)
        metrics = {}
        for m in cell.per_layer:
            value = spec.load_reader(m.name)(view)
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        for chip, busy in sorted(summary.busy_s.items()):
            log(f"trace: {chip} busy {busy:.6f} s of "
                f"{summary.window_s:.6f} s ({summary.events} op events)")
        ops = sorted(summary.program_s.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    else:
        values = harness.end_to_end([m.name for m in cell.end_to_end],
                                    rec, win, setup_s)
        metrics = {m.name: {"value": values[m.name], "unit": m.unit}
                   for m in cell.end_to_end}
        log("samples: " + json.dumps(system_counts(rec, win)))
        if rec.tokens:
            ttft = sorted(harness.first_token_ms(rec, win), reverse=True)
            log("longest first tokens (ms): "
                + " ".join(f"{t:.1f}" for t in ttft[:8]))

    uids = system.sample()
    system.release(uids)
    checks = system.checks(uids)
    due = harness.due_in(rec, win)
    out = {"correct": all(c.ok for c in checks), "attempted": len(due),
           "failed": 0, "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def system_counts(rec, win) -> dict:
    return {"requests_due": len(harness.due_in(rec, win)),
            "first_tokens": len(harness.first_token_ms(rec, win)),
            "token_gaps": len(harness.token_gaps_ms(rec, win)),
            "tokens": harness.tokens_in(rec, win),
            "finished": sum(1 for t in rec.done.values() if t < win.end)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    enable_cache()
    out = run(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
