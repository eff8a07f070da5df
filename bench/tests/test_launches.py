"""``launches.stream``: the programs the host launches per engine step,
joins left out, read from the recorded v5e trace of two chat steps at two
live slots (``data/stream_spans_v5e.xplane.pb``, recorded before the step's
programs took the whole slot state), and nothing read where there is
nothing to read."""
from __future__ import annotations

import shutil
from types import SimpleNamespace

import pytest

from bench import program_spans as ps
from bench import trace_reduce as tr
from bench.spec import BENCH_DIR, load_part, load_reader

DATA = BENCH_DIR / "tests" / "data"
NAME = "launches.stream"


def _run_of(src, root):
    dst = root / src.stem / "run.xplane.pb"
    dst.parent.mkdir()
    shutil.copy(src, dst)
    return SimpleNamespace(trace=tr.reduce_trace(str(dst)))


def test_launches_per_step_by_span():
    path = str(DATA / "stream_spans_v5e.xplane.pb")
    spans = ps.read(path)
    by_span = load_part("metrics", NAME).per_step(
        spans, tr.read_events(path).launches)
    assert by_span == {
        "jalad.stream.head": 9, "jalad.codec.encode": 1,
        "jalad.codec.decode": 5, "jalad.stream.tail": 38,
        "jalad.stream.select": 4, "jalad.stream.record": 15}
    assert load_part("metrics", NAME).per_step(ps.read(str(
        DATA / "fleet_spans_v5e.xplane.pb")), []) is None


def test_read_from_a_traced_run(tmp_path, monkeypatch):
    monkeypatch.setattr(ps, "TRACE_ROOT", tmp_path)
    read = load_reader(NAME)
    assert read(SimpleNamespace(trace=None)) is None
    run = _run_of(DATA / "stream_spans_v5e.xplane.pb", tmp_path)
    assert read(run) == pytest.approx(72.0)
    # A profile of another window is not this run's.
    assert read(SimpleNamespace(
        trace=SimpleNamespace(window_s=run.trace.window_s + 1.0))) is None


@pytest.mark.parametrize("trace", ["fleet_spans_v5e", "stream_v5e"])
def test_nothing_to_read_without_stream_steps(trace, tmp_path, monkeypatch):
    """The fleet has no engine steps; the trace recorded before the
    program had spans has none to count in."""
    monkeypatch.setattr(ps, "TRACE_ROOT", tmp_path)
    run = _run_of(DATA / f"{trace}.xplane.pb", tmp_path)
    assert load_reader(NAME)(run) is None
