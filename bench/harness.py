"""Shared machinery of a run: spans and counters, the compile counter,
the measured window, and the arithmetic the drivers and end-to-end
metrics share.

Spans are the benchmark's own: host intervals around the calls it makes
into each layer of the program (``Recorder.span``), written into the
profiler's trace as ``jax.profiler.TraceAnnotation``s when a trace is on.
"""
from __future__ import annotations

import contextlib
import math
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from bench.traffic import Schedule

clock = time.perf_counter

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class GcWatch:
    """Pauses of Python's garbage collector while ``on``: (generation,
    seconds) for each collection, from ``gc.callbacks``."""

    def __init__(self):
        self.on = False
        self.pauses: List[tuple] = []
        self._t0: Optional[float] = None

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if not self.on:
            return
        if phase == "start":
            self._t0 = clock()
        elif self._t0 is not None:
            self.pauses.append((info["generation"], clock() - self._t0))
            self._t0 = None

    def summary(self) -> str:
        by_gen = defaultdict(int)
        for g, _ in self.pauses:
            by_gen[g] += 1
        longest = max(self.pauses, key=lambda p: p[1], default=(None, 0.0))
        return (f"{len(self.pauses)} collections {dict(sorted(by_gen.items()))}"
                f", {sum(p[1] for p in self.pauses) * 1e3:.3f} ms in all, "
                f"the longest {longest[1] * 1e3:.3f} ms (generation "
                f"{longest[0]})")


class CompileCounter:
    """Counts XLA compilations and jaxpr traces (JAX reports each one)."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.traces = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration
        elif event == TRACE_EVENT:
            self.traces += 1

    def snapshot(self):
        return self.compiles, self.traces


@dataclass
class Recorder:
    """Spans, counters and per-request events of one run."""

    annotate: bool = False               # write spans into the trace
    spans: Dict[str, List[float]] = field(
        default_factory=lambda: defaultdict(list))
    counters: Dict[str, float] = field(
        default_factory=lambda: defaultdict(float))
    arrival: Dict[int, float] = field(default_factory=dict)
    tokens: Dict[int, List[float]] = field(
        default_factory=lambda: defaultdict(list))
    done: Dict[int, float] = field(default_factory=dict)

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench:{name}")
            ann.__enter__()
        t0 = clock()
        try:
            yield
        finally:
            t1 = clock()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans[name].append(t1 - t0)

    def reset(self) -> None:
        """Forget what set-up recorded."""
        for d in (self.spans, self.counters, self.arrival, self.tokens,
                  self.done):
            d.clear()

    def count(self, name: str, value: float = 1.0) -> None:
        self.counters[name] += value

    def token(self, uid: int, t: Optional[float] = None) -> None:
        self.tokens[uid].append(clock() if t is None else t)

    def finish(self, uid: int, t: Optional[float] = None) -> None:
        self.done[uid] = clock() if t is None else t


# ---------------------------------------------------------------------------
# Drivers: they hand requests to a system and let it work. Each is a file,
# bench/drivers/<name>.py, named by the traffic mix.
# ---------------------------------------------------------------------------


@dataclass
class Window:
    start: float
    seconds: float
    submitted: int = 0
    lateness_s: List[float] = field(default_factory=list)
    at: Optional[float] = None           # call ``then`` this far in
    then: Any = None

    @property
    def end(self) -> float:
        return self.start + self.seconds

    def tick(self, now: float) -> bool:
        """Call ``then`` once it is due; False once the window is over."""
        if self.then is not None and now >= self.start + self.at:
            then, self.then = self.then, None
            then()
        return now < self.end


def run_window(system, schedule: Schedule, seconds: float,
               rec: Recorder, at: Optional[float] = None,
               then=None) -> Window:
    """Drive ``system`` for ``seconds`` with the schedule's driver. The
    system offers ``submit(request, uid)``, ``busy()``, ``queued()`` and
    ``pump()`` (one unit of work), and records each request's tokens and
    finish in ``rec``. ``then()`` is called once, ``at`` seconds into the
    window."""
    from bench import spec

    driver = spec.load_part("drivers", schedule.driver)
    win = Window(clock(), seconds, at=at, then=then)
    driver.drive(system, schedule, win, rec)
    return win


def sleep_until(t: float) -> None:
    time.sleep(max(0.0, t - clock()))


# ---------------------------------------------------------------------------
# End-to-end arithmetic
# ---------------------------------------------------------------------------


def percentile(values, q: float) -> float:
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return math.nan
    return float(np.percentile(v, q))


def due_in(rec: Recorder, win: Window) -> List[int]:
    """Requests whose scheduled arrival lies in the window."""
    return [u for u, t in rec.arrival.items() if win.start <= t < win.end]


def first_token_ms(rec: Recorder, win: Window) -> List[float]:
    """Per request due in the window: its first token minus its scheduled
    arrival; one with no token by the window's end counts at the time it
    has waited."""
    out = []
    for u in due_in(rec, win):
        toks = [t for t in rec.tokens.get(u, []) if t < win.end]
        t = toks[0] if toks else win.end
        out.append((t - rec.arrival[u]) * 1e3)
    return out


def token_gaps_ms(rec: Recorder, win: Window) -> List[float]:
    """Every gap between consecutive output tokens of one request, both
    inside the window."""
    gaps = []
    for toks in rec.tokens.values():
        t = np.asarray([x for x in toks if win.start <= x < win.end])
        if t.size > 1:
            gaps.extend((np.diff(t) * 1e3).tolist())
    return gaps


def tokens_in(rec: Recorder, win: Window) -> int:
    return sum(1 for toks in rec.tokens.values() for t in toks
               if win.start <= t < win.end)


def request_latency_ms(rec: Recorder, win: Window) -> List[float]:
    """Per request due in the window: its result ready minus its scheduled
    arrival; one unfinished at the window's end counts at its elapsed
    time."""
    out = []
    for u in due_in(rec, win):
        t = rec.done.get(u)
        t = t if t is not None and t < win.end else win.end
        out.append((t - rec.arrival[u]) * 1e3)
    return out


def end_to_end(names: List[str], rec: Recorder, win: Window,
               setup_s: float) -> Dict[str, float]:
    """The cell's end-to-end metrics, by name: each computed by its file,
    ``bench/end_to_end/<name>.py``."""
    from bench import spec

    return {name: spec.load_part("end_to_end", name).compute(
        rec, win, setup_s) for name in names}
