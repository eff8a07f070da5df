"""The reduction from a profiler trace to busy time, device time per
span, collectives and idle gaps: its interval arithmetic, and a small
trace recorded on a TPU v5e (``data/``), cut to a few engine steps."""
from __future__ import annotations

import pytest

from bench import trace_reduce as tr
from bench.spec import BENCH_DIR

DATA = BENCH_DIR / "tests" / "data"


def test_merge_overlap_and_gaps():
    busy = tr.merge([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.overlap(busy, [(1.5, 3.5)]) == pytest.approx(1.0)
    assert tr.gaps(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert tr.clip(busy, 0.5, 3.5) == [(0.5, 2.0), (3.0, 3.5)]


def test_gap_label_is_the_innermost_span():
    spans = {"window": [(0.0, 10.0)], "step": [(1.0, 5.0)],
             "encode": [(2.0, 3.0)]}
    assert tr._label(spans, 2.5) == "encode"
    assert tr._label(spans, 4.0) == "step"
    assert tr._label(spans, 7.0) == "host outside any span"


def test_collective_time_and_its_exposed_part():
    coll = [(1.0, 2.0), (5.0, 6.0)]
    ops = coll + [(1.5, 3.0), (0.0, 0.5)]
    total, exposed = tr.collective_time(coll, ops, 0.0, 5.5)
    assert total == pytest.approx(1.5)       # (1, 2) and (5, 5.5)
    assert exposed == pytest.approx(1.0)     # (1, 1.5) and (5, 5.5)
    assert tr.collective_time([], ops, 0.0, 10.0) == (0.0, 0.0)


def test_device_time_goes_to_the_span_that_launched_it():
    spans = {"encode": [(1.0, 1.1), (2.0, 2.1)], "step": [(0.5, 2.5)]}
    launches = [(1.05, 7), (2.05, 8), (1.5, 9), (3.0, 10)]
    runs = {7: 0.01, 8: 0.02, 9: 0.5, 10: 1.0}
    out = tr.span_launched_s(spans, launches, runs)
    assert out["encode"] == pytest.approx(0.03)
    assert out["step"] == pytest.approx(0.53)


def test_recorded_v5e_trace():
    """0.15 s of the token-streaming session traced on a TPU v5 lite
    (16 queued prompts of 512-1920 tokens: joins), its device programs and
    operations, the benchmark's spans and the host's program launches kept
    (the rest of the host plane dropped)."""
    s = tr.reduce_trace(str(DATA / "stream_v5e.xplane.pb"))
    assert s.window_s == pytest.approx(0.15)
    assert list(s.busy_s) == ["/device:TPU:0"]
    assert s.busy_s["/device:TPU:0"] == pytest.approx(0.099990844)
    assert s.events == 2184
    # Each codec span launched exactly the codec's own programs.
    assert s.span_device_s["encode"] == pytest.approx(
        s.program_s["jit_quantize_pack"])
    assert s.span_device_s["decode"] == pytest.approx(
        s.program_s["jit_dequantize_wire"])
    # Every program launched in the window was launched in a join.
    assert s.span_device_s["join"] == pytest.approx(
        s.span_device_s["window"])
    # The longest idle gaps fall in the joins' whole-sequence encodes.
    assert [g[0] for g in s.idle_gaps[:2]] == ["encode"] * 2
    assert s.idle_gaps[0][1] == pytest.approx(0.008784851)
    top = max(s.program_s, key=s.program_s.get)
    assert top == "jit__lambda"
    assert s.collective_s == {"/device:TPU:0": 0.0}
