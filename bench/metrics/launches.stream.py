"""Programs the host launches per engine step, joins left out: the launches
the trace links to a program run on the device (``tpu::System::Execute``,
``bench.trace_reduce``) that start inside a ``jalad.stream.step`` and
outside its ``jalad.stream.join``, over the steps. The profile is found as
``bench.program_spans.of_run`` finds it."""
import bisect
import os
from collections import defaultdict

from bench import program_spans, trace_reduce


def _inside(ivs, t):
    k = bisect.bisect_right(ivs, (t, float("inf"))) - 1
    return k >= 0 and t < ivs[k][1]


def per_step(spans, launches):
    """Launches per engine step by the innermost program span that holds
    them (``jalad.<name>``), joins left out; None without steps."""
    steps = spans.named("stream.step")
    if not steps:
        return None
    held = trace_reduce.merge([(s.start, s.end) for s in steps])
    joins = trace_reduce.merge(
        [(s.start, s.end) for s in spans.under("stream.join",
                                               "stream.step")])
    pieces = spans._self_pieces()
    starts = [p[0] for p in pieces]
    out = defaultdict(float)
    for t, _ in launches:
        if not _inside(held, t) or _inside(joins, t):
            continue
        k = bisect.bisect_right(starts, t) - 1
        out[pieces[k][2]] += 1 / len(steps)
    return dict(out)


def read(run):
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    path = sorted(program_spans.TRACE_ROOT.glob("**/*.xplane.pb"),
                  key=os.path.getmtime)[-1]
    launches = trace_reduce.read_events(str(path)).launches
    by_span = per_step(spans, launches) if launches else None
    return None if by_span is None else sum(by_span.values())
