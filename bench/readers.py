"""Arithmetic the per-layer metric readers share. Each reader in
``bench/metrics/`` takes a run (``bench.run.RunView``) and returns its
number, or None where the run holds nothing to read."""
from __future__ import annotations

from typing import Optional


def mean_ms(run, span: str) -> Optional[float]:
    n = run.span_n(span)
    return run.span_s(span) / n * 1e3 if n else None


def roofline(run, spans, bytes_counter: str) -> Optional[float]:
    """Least time the bytes counted for these spans take at the chip's
    memory bandwidth, over the device time of the programs the spans
    launched (found in the trace, whenever the device ran them), in
    percent."""
    if run.trace is None:
        return None
    device_s = sum(run.trace.span_device_s.get(s, 0.0) for s in spans)
    nbytes = run.rec.counters.get(bytes_counter, 0.0)
    if device_s <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / device_s


def mfu(run, span: str) -> Optional[float]:
    """Model FLOPs of the work done, over the host time inside ``span``,
    over the chip's bf16 peak, in percent."""
    t = run.span_s(span)
    flops = run.rec.counters.get("model_flops", 0.0)
    if t <= 0 or flops <= 0:
        return None
    return 100.0 * flops / t / run.peaks["bf16_flops"]


def idle(run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device, averaged over the chips, in percent."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.mean_busy_s / run.trace.window_s)
