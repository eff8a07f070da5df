"""From a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: each chip's busy time in the window, device time per program, the
device time of the programs each of the benchmark's host spans launched,
each chip's time in collectives (and the part of it with no other
operation running), and the longest idle gaps with the host span they
fall in.

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per operation run (a loop and the operations in its
body both appear; busy time is their union), their ``XLA Modules`` line
one event per program run, with its ``run_id``. Host spans are the
benchmark's ``TraceAnnotation``s (named ``bench:<span>``) on the host
plane. The window is the ``bench:window`` span. Busy time is the union of
the operation intervals inside it. All times are on the trace's one
clock.

A program run belongs to the span in which the host launched it, whenever
the device ran it: the host's ``tpu::System::Execute`` event starts in the
span and carries a flow id (``_p``); the ``=>IssueSequencedEvent`` event
with that flow id (``_c``) holds, on its thread, the ``DoEnqueueProgram``
event that names the ``run_id``; the device's program run carries the same
``run_id``. So a span's device time needs no wait for the device inside
the span.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: Dict[str, float]                 # per chip
    program_s: Dict[str, float]              # per program, all chips
    span_device_s: Dict[str, float]          # launched in span, per chip
    idle_gaps: List[Tuple[str, float]]       # (host span, seconds)
    events: int = 0
    collective_s: Dict[str, float] = field(default_factory=dict)
    collective_exposed_s: Dict[str, float] = field(default_factory=dict)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def merge(intervals: List[Interval]) -> List[Interval]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _label(spans: Dict[str, List[Interval]], t: float) -> str:
    """The innermost benchmark span (the shortest) holding time ``t``."""
    best, best_len = "host outside any span", float("inf")
    for name, ivs in spans.items():
        if name == WINDOW_SPAN:
            continue
        for s, e in ivs:
            if s <= t < e and e - s < best_len:
                best, best_len = name, e - s
    return best


def program_name(text: str) -> str:
    """A program's name without its fingerprint: ``jit_add(5131...)``
    reads ``jit_add``."""
    return text.split("(", 1)[0]


COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")
LAUNCH = "tpu::System::Execute"
ISSUE = LAUNCH + "=>IssueSequencedEvent"
ENQUEUE = "DoEnqueueProgram"


@dataclass
class Events:
    spans: Dict[str, List[Interval]]
    ops: Dict[str, List[Interval]]                   # per chip
    collectives: Dict[str, List[Interval]]           # per chip
    progs: Dict[str, List[Tuple[str, float, float, int]]]  # per chip
    launches: List[Tuple[float, int]]                # (host time, run_id)


def _stats(ev) -> Dict[str, object]:
    return {k: v for k, v in ev.stats}


def read_events(path: str) -> Events:
    """Host spans by name; per chip, operation intervals, collective
    intervals and program runs (name, start, end, run_id); the host's
    program launches with their run_id; all in seconds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ev_ = Events(defaultdict(list), defaultdict(list), defaultdict(list),
                 defaultdict(list), [])
    flows: List[Tuple[float, int]] = []             # (launch time, flow)
    run_of_flow: Dict[int, int] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if line.name == OPS_LINE:
                        ev_.ops[plane.name].append((s, e))
                        if COLLECTIVE.match(ev.name):
                            ev_.collectives[plane.name].append((s, e))
                    else:
                        run = int(_stats(ev).get("run_id", -1))
                        ev_.progs[plane.name].append(
                            (program_name(ev.name), s, e, run))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                issues, enqueues = [], []
                for ev in line.events:
                    name = ev.name
                    if name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        ev_.spans[name[len(SPAN_PREFIX):]].append(
                            (s, s + ev.duration_ns * 1e-9))
                    elif name == LAUNCH:
                        flow = _stats(ev).get("_p")
                        if flow is not None:
                            flows.append((ev.start_ns * 1e-9, int(flow)))
                    elif name == ISSUE:
                        flow = _stats(ev).get("_c")
                        if flow is not None:
                            issues.append((ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           int(flow)))
                    elif name == ENQUEUE:
                        run = _stats(ev).get("run_id")
                        if run is not None:
                            enqueues.append((ev.start_ns, int(run)))
                enqueues.sort()
                starts = [t for t, _ in enqueues]
                for s, e, flow in issues:
                    k = bisect.bisect_left(starts, s)
                    if k < len(starts) and starts[k] <= e:
                        run_of_flow[flow] = enqueues[k][1]
    ev_.launches = [(t, run_of_flow[f]) for t, f in flows
                    if f in run_of_flow]
    return ev_


def collective_time(coll: List[Interval], ops: List[Interval], lo: float,
                    hi: float) -> Tuple[float, float]:
    """Time in collectives inside [lo, hi), and the part of it in which no
    other operation runs."""
    c = merge(clip(coll, lo, hi))
    total = sum(e - s for s, e in c)
    if not c:
        return 0.0, 0.0
    cset = set(coll)
    other = merge(clip([iv for iv in ops if iv not in cset], lo, hi))
    return total, total - overlap(c, other)


def span_launched_s(spans: Dict[str, List[Interval]],
                    launches: List[Tuple[float, int]],
                    runs: Dict[int, float]) -> Dict[str, float]:
    """Per span, the device seconds (``runs``: run_id -> seconds) of the
    programs launched inside it."""
    out: Dict[str, float] = defaultdict(float)
    for name, ivs in spans.items():
        starts = [s for s, _ in ivs]
        for t, run in launches:
            k = bisect.bisect_right(starts, t) - 1
            if k >= 0 and t < ivs[k][1] and run in runs:
                out[name] += runs[run]
    return dict(out)


def reduce_trace(path: str, n_gaps: int = 10) -> TraceSummary:
    ev = read_events(path)
    win = ev.spans.get(WINDOW_SPAN)
    if not win:
        raise ValueError(f"no {SPAN_PREFIX}{WINDOW_SPAN} span in {path}")
    lo, hi = win[0]
    merged_spans = {k: merge(v) for k, v in ev.spans.items()}
    busy_s, program_s = {}, defaultdict(float)
    coll_s, exposed_s = {}, {}
    span_dev = defaultdict(float)
    all_gaps = []
    n_events = 0
    for chip in sorted(ev.ops):
        runs = {}
        for name, s, e, run in ev.progs.get(chip, []):
            if e > lo and s < hi:
                program_s[name] += min(e, hi) - max(s, lo)
            runs[run] = e - s
        launched = [(t, r) for t, r in ev.launches if lo <= t < hi]
        for name, v in span_launched_s(merged_spans, launched,
                                       runs).items():
            span_dev[name] += v
        ivs = clip(ev.ops[chip], lo, hi)
        n_events += len(ivs)
        busy = merge(ivs)
        busy_s[chip] = sum(e - s for s, e in busy)
        coll_s[chip], exposed_s[chip] = collective_time(
            ev.collectives.get(chip, []), ev.ops[chip], lo, hi)
        all_gaps.extend(gaps(busy, lo, hi))
    chips = max(len(ev.ops), 1)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:n_gaps]
    idle = [(_label(merged_spans, (s + e) / 2), e - s) for s, e in longest]
    return TraceSummary(
        window_s=hi - lo, busy_s=busy_s, program_s=dict(program_s),
        span_device_s={k: v / chips for k, v in span_dev.items()},
        idle_gaps=idle, events=n_events, collective_s=coll_s,
        collective_exposed_s=exposed_s)
