"""The program's own spans in a profiler trace, and what the per-layer
metrics read from them.

The served paths write spans named ``jalad.<name>`` with small integer
stats (``repro.utils.trace.span``) into the profiler's trace, on the
clock of the device timeline. Here they are read from the host plane,
nested by containment on their thread (the parent of a span is the
innermost span that holds it), and each is given the device time of the
programs launched inside it, linked as ``bench.trace_reduce`` links them
(launch flow id -> ``run_id`` -> the device's program run). Spans are
kept where their outermost span lies wholly in the benchmark's window
(``bench:window``), so a step cut by the window's edge counts neither in
part nor whole.

Idle time is owned the same way: each stretch with no operation on the
device belongs to the innermost program span that holds it, else to the
innermost benchmark span (``bench:<name>``), else to the host outside any
span.

A trace of a program without these spans gives an empty set, and every
reading of it None.

    python3 -m bench.program_spans <trace dir or .xplane.pb>

prints, per span name, its count, host seconds and device seconds; idle
seconds per owner over the whole window; and the longest idle gaps.
"""
from __future__ import annotations

import bisect
import functools
import os
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from bench import spec, trace_reduce as tr

PREFIX = "jalad."
# Where ``bench.run`` writes a traced run's profile, one directory a cell.
TRACE_ROOT = spec.BENCH_DIR / "out" / "trace"
OUTSIDE = "host outside any span"


@dataclass
class Span:
    name: str                     # without the ``jalad.`` prefix
    start: float                  # s, on the trace's clock
    end: float
    stats: Dict[str, int]
    up: Tuple[str, ...] = ()      # the spans holding it, outermost first
    parent: int = -1              # index of the innermost span holding it
    device_s: float = 0.0         # device time of the programs launched in it

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class ProgramSpans:
    window: tr.Interval
    spans: List[Span]
    gaps: Dict[str, List[tr.Interval]] = field(   # device idle, per chip
        default_factory=dict)
    bench_spans: Dict[str, List[tr.Interval]] = field(default_factory=dict)
    chips: int = 0                # device planes in the trace

    # ---------------------------------------------------------- summary
    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def under(self, name: str, holder: str,
              not_under: Optional[str] = None) -> List[Span]:
        """Spans ``name`` held by a ``holder`` span, and by no
        ``not_under`` span."""
        return [s for s in self.spans if s.name == name and holder in s.up
                and (not_under is None or not_under not in s.up)]

    @property
    def program_span_device_s(self) -> Dict[str, float]:
        """Per span name, the device seconds of the programs launched
        inside its spans."""
        out = defaultdict(float)
        for s in self.spans:
            out[s.name] += s.device_s
        return dict(out)

    # ---------------------------------------------------- the token stream
    def _steps(self) -> List[Span]:
        return self.named("stream.step")

    def _per_step_ms(self, seconds: float) -> Optional[float]:
        n = len(self._steps())
        return seconds / n * 1e3 if n else None

    def stream_sync_ms(self) -> Optional[float]:
        """Host ms per engine step waiting for the device, joins left
        out."""
        if not self._steps():
            return None
        waits = self.under("sync", "stream.step", "stream.join")
        return self._per_step_ms(sum(s.seconds for s in waits))

    def stream_host_self_ms(self) -> Optional[float]:
        """Host ms per engine step outside its waits and its joins."""
        steps = self._steps()
        if not steps:
            return None
        joins = self.under("stream.join", "stream.step")
        waits = self.under("sync", "stream.step", "stream.join")
        return self._per_step_ms(sum(s.seconds for s in steps)
                                 - sum(s.seconds for s in joins)
                                 - sum(s.seconds for s in waits))

    def stream_device_ms(self, phase: str) -> Optional[float]:
        """Device ms per engine step of the programs launched in
        ``stream.<phase>`` (``head``, ``tail``)."""
        spans = self.under(f"stream.{phase}", "stream.step")
        if not spans or not self._steps() or not self.chips:
            return None
        return self._per_step_ms(sum(s.device_s for s in spans))

    def live_slots(self) -> Optional[float]:
        heads = [s.stats["slots"] for s in self.named("stream.head")
                 if "slots" in s.stats]
        return sum(heads) / len(heads) if heads else None

    def queue_wait_ms(self) -> Optional[float]:
        """Mean of join start minus submit start of the same ``uid``, over
        the joins whose submit is in the trace."""
        submits = {s.stats["uid"]: s.start
                   for s in self.named("stream.submit") if "uid" in s.stats}
        waits = [s.start - submits[s.stats["uid"]]
                 for s in self.named("stream.join")
                 if s.stats.get("uid") in submits]
        return sum(waits) / len(waits) * 1e3 if waits else None

    # ---------------------------------------------------------- the fleet
    def _requests(self) -> int:
        return sum(s.stats.get("requests", 0)
                   for s in self.named("fleet.serve"))

    def fleet_sync_ms(self) -> Optional[float]:
        """Host ms per request waiting for the device."""
        n = self._requests()
        if not n:
            return None
        waits = self.under("sync", "fleet.serve")
        return sum(s.seconds for s in waits) / n * 1e3

    def fleet_host_self_ms(self) -> Optional[float]:
        """Host ms per request in serve calls outside their waits."""
        n = self._requests()
        if not n:
            return None
        serve = sum(s.seconds for s in self.named("fleet.serve"))
        waits = sum(s.seconds for s in self.under("sync", "fleet.serve"))
        return (serve - waits) / n * 1e3

    # --------------------------------------------------------------- idle
    def _self_pieces(self) -> List[Tuple[float, float, str]]:
        """Each span's time not held by a child: disjoint, sorted."""
        children = defaultdict(list)
        for i, s in enumerate(self.spans):
            if s.parent >= 0:
                children[s.parent].append((s.start, s.end))
        pieces = []
        for i, s in enumerate(self.spans):
            label = PREFIX + s.name
            for a, b in tr.gaps(tr.merge(children[i]), s.start, s.end):
                if b > a:
                    pieces.append((a, b, label))
        pieces.sort()
        return pieces

    def _bench_label(self, t: float) -> str:
        """The innermost benchmark span holding time ``t``."""
        best, best_len = OUTSIDE, float("inf")
        for name, ivs in self.bench_spans.items():
            k = bisect.bisect_right(ivs, (t, float("inf"))) - 1
            if k >= 0 and t < ivs[k][1] and ivs[k][1] - ivs[k][0] < best_len:
                best, best_len = "bench:" + name, ivs[k][1] - ivs[k][0]
        return best

    def _owned(self, lo: float, hi: float, pieces, starts):
        """(owner, seconds) of the stretch [lo, hi)."""
        out = []
        k = max(bisect.bisect_right(starts, lo) - 1, 0)
        t = lo
        while k < len(pieces) and pieces[k][0] < hi:
            a, b, label = pieces[k]
            if b > t:
                if a > t:
                    out.append((self._bench_label((t + a) / 2), a - t))
                    t = a
                e = min(b, hi)
                out.append((label, e - t))
                t = e
            k += 1
        if hi > t:
            out.append((self._bench_label((t + hi) / 2), hi - t))
        return out

    def idle_by_owner(self) -> Dict[str, float]:
        """Idle seconds of the window per owner, averaged over chips."""
        pieces = self._self_pieces()
        starts = [p[0] for p in pieces]
        out = defaultdict(float)
        for lo, hi in (g for gaps in self.gaps.values() for g in gaps):
            for label, s in self._owned(lo, hi, pieces, starts):
                out[label] += s / max(self.chips, 1)
        return dict(out)

    def longest_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` longest idle gaps, each with the owner of its middle."""
        pieces = self._self_pieces()
        starts = [p[0] for p in pieces]
        out = []
        every = [g for gaps in self.gaps.values() for g in gaps]
        for lo, hi in sorted(every, key=lambda g: g[0] - g[1])[:n]:
            mid = (lo + hi) / 2
            out.append((self._owned(mid, mid + 1e-12, pieces, starts)[0][0],
                        hi - lo))
        return out

    def idle_in(self, name: str) -> float:
        """Idle seconds inside ``name`` spans, averaged over chips."""
        ivs = tr.merge([(s.start, s.end) for s in self.named(name)])
        return sum(tr.overlap(ivs, gaps)
                   for gaps in self.gaps.values()) / max(self.chips, 1)


def _nest(events: List[Tuple[int, int, str, Dict[str, int]]],
          base: int) -> List[Span]:
    """Spans of one thread from (start ns, end ns, name, stats), in start
    order, each with its parent (indices from ``base``)."""
    events.sort(key=lambda e: (e[0], -e[1]))
    out: List[Span] = []
    stack: List[Tuple[int, int]] = []         # open spans: (end ns, index)
    for s, e, name, stats in events:
        while stack and e > stack[-1][0]:
            stack.pop()
        span = Span(name, s * 1e-9, e * 1e-9, stats)
        if stack:
            parent = out[stack[-1][1] - base]
            span.parent = stack[-1][1]
            span.up = parent.up + (parent.name,)
        stack.append((e, base + len(out)))
        out.append(span)
    return out


def read(path: str) -> ProgramSpans:
    """The program spans of a trace, kept where their outermost span lies
    in the benchmark's window, with their device time, and the device's
    idle gaps in the window."""
    from jax.profiler import ProfileData

    ev = tr.read_events(path)
    win = ev.spans.get(tr.WINDOW_SPAN)
    if not win:
        raise ValueError(
            f"no {tr.SPAN_PREFIX}{tr.WINDOW_SPAN} span in {path}")
    lo, hi = win[0]
    spans: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns,
                    e.name[len(PREFIX):], {k: v for k, v in e.stats})
                   for e in line.events if e.name.startswith(PREFIX)]
            spans.extend(_nest(evs, len(spans)))
    # Keep whole trees whose root lies in the window, parents first.
    root: List[int] = []
    new: Dict[int, int] = {}
    kept: List[Span] = []
    for i, s in enumerate(spans):
        root.append(i if s.parent < 0 else root[s.parent])
        r = spans[root[i]]
        # Within a nanosecond: the window's ends are rounded seconds.
        if lo - 1e-9 <= r.start and r.end <= hi + 1e-9:
            new[i] = len(kept)
            s.parent = new.get(s.parent, -1)
            kept.append(s)
    chips = len(ev.ops)
    runs: Dict[int, float] = defaultdict(float)
    for progs in ev.progs.values():
        for _, s, e, run in progs:
            runs[run] += (e - s) / max(chips, 1)
    launches = sorted((t, runs[r]) for t, r in ev.launches if r in runs)
    times = [t for t, _ in launches]
    acc = [0.0]
    for _, d in launches:
        acc.append(acc[-1] + d)
    for s in kept:
        s.device_s = (acc[bisect.bisect_left(times, s.end)]
                      - acc[bisect.bisect_left(times, s.start)])
    gaps = {chip: tr.gaps(tr.merge(tr.clip(ops, lo, hi)), lo, hi)
            for chip, ops in ev.ops.items()}
    bench = {k: tr.merge(v) for k, v in ev.spans.items()
             if k != tr.WINDOW_SPAN}
    return ProgramSpans((lo, hi), kept, gaps, bench, chips)


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int) -> ProgramSpans:
    return read(path)


def of_run(run) -> Optional[ProgramSpans]:
    """The program spans of a traced run (``bench.run.RunView``): its
    profile is the newest under ``TRACE_ROOT``, and must span the window
    of the run's reduced trace. None for an untraced run."""
    if run.trace is None:
        return None
    paths = sorted(TRACE_ROOT.glob("**/*.xplane.pb"), key=os.path.getmtime)
    if not paths:
        return None
    path = str(paths[-1])
    spans = _load(path, os.stat(path).st_mtime_ns)
    lo, hi = spans.window
    if abs((hi - lo) - run.trace.window_s) > 1e-6:
        return None
    return spans


def main(argv=None) -> int:
    arg = (argv if argv is not None else sys.argv[1:])[0]
    path = arg if arg.endswith(".xplane.pb") else tr.find_xplane(arg)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {arg}")
    ps = read(path)
    lo, hi = ps.window
    print(f"window {hi - lo:.6f} s, {len(ps.spans)} program spans")
    dev = ps.program_span_device_s

    def key(s: Span) -> str:       # a wait reads per holder
        return f"sync in {s.up[-1]}" if s.name == "sync" and s.up else s.name

    host, own, n = defaultdict(float), defaultdict(float), defaultdict(int)
    for s in ps.spans:
        host[s.name] += s.seconds
        n[s.name] += 1
        own[key(s)] += s.seconds
        if s.parent >= 0:
            own[key(ps.spans[s.parent])] -= s.seconds
    for name in sorted(host, key=lambda k: -host[k]):
        print(f"span {PREFIX}{name}: {n[name]}, host {host[name]:.6f} s, "
              f"device {dev.get(name, 0.0):.6f} s")
    print("host self time: " + ", ".join(
        f"{k} {v:.6f}" for k, v in sorted(own.items(), key=lambda kv: -kv[1])))
    idle = ps.idle_by_owner()
    print(f"idle {sum(idle.values()):.6f} s of {hi - lo:.6f} s, by owner:")
    for label, s in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"  {label}: {s:.6f} s")
    for name in ("stream.step", "fleet.serve"):
        inside = ps.idle_in(name)
        if inside > 0:
            own = idle.get(PREFIX + name, 0.0)
            print(f"idle inside {PREFIX}{name}: {inside:.6f} s, its own "
                  f"self time {own:.6f} s ({100 * own / inside:.2f} %)")
    print("longest idle gaps: " + ", ".join(
        f"{label} {s:.6f}" for label, s in ps.longest_gaps()))
    readings = {
        "sync_ms.stream": ps.stream_sync_ms(),
        "host_self_ms.stream": ps.stream_host_self_ms(),
        "head_device_ms.stream": ps.stream_device_ms("head"),
        "tail_device_ms.stream": ps.stream_device_ms("tail"),
        "live_slots.stream": ps.live_slots(),
        "queue_wait_ms.stream": ps.queue_wait_ms(),
        "sync_ms.fleet": ps.fleet_sync_ms(),
        "host_self_ms.fleet": ps.fleet_host_self_ms()}
    for name, v in readings.items():
        if v is not None:
            print(f"{name}: {v:.6f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
