"""The reading of the program's own spans (``bench.program_spans``): their
nesting, the per-step and per-request arithmetic the new per-layer
metrics read, the owners of idle time, and the lookup of a traced run's
profile."""
from __future__ import annotations

import os
import shutil
from types import SimpleNamespace

import pytest

from bench import program_spans as ps
from bench import trace_reduce as tr
from bench.spec import BENCH_DIR, load_reader

DATA = BENCH_DIR / "tests" / "data"
MS = 1_000_000          # ns


def spans(*events, gaps=(), bench=None, chips=1):
    """A ProgramSpans of one thread from (start ms, end ms, name, stats)."""
    evs = [(int(s * MS), int(e * MS), n, st) for s, e, n, st in events]
    out = ps._nest(evs, 0)
    return ps.ProgramSpans((0.0, 1.0), out, {"/device:TPU:0": list(gaps)},
                           bench or {}, chips)


# Two engine steps of 10 and 20 ms; the second admits a join of 8 ms that
# waits 2 ms for the device; each step's select waits for the tail.
STEPS = (
    (0, 0, "stream.submit", {"uid": 5}),
    (1, 11, "stream.step", {}),
    (1, 3, "stream.head", {"slots": 3}),
    (3, 5, "stream.tail", {"slots": 3}),
    (5, 9, "stream.select", {}),
    (6, 9, "sync", {}),
    (20, 40, "stream.step", {}),
    (20, 29, "stream.admit", {}),
    (21, 29, "stream.join", {"uid": 5, "prompt_len": 64}),
    (22, 24, "sync", {}),
    (30, 32, "stream.head", {"slots": 4}),
    (32, 35, "stream.tail", {"slots": 4}),
    (35, 39, "sync", {}),
)


def test_spans_nest_by_containment():
    s = spans(*STEPS)
    by = {(x.name, round(x.start * 1e3)): x for x in s.spans}
    join = by[("stream.join", 21)]
    assert join.up == ("stream.step", "stream.admit")
    assert s.spans[join.parent].name == "stream.admit"
    assert by[("sync", 22)].up == ("stream.step", "stream.admit",
                                   "stream.join")
    assert by[("sync", 6)].up == ("stream.step", "stream.select")
    assert by[("stream.submit", 0)].up == ()
    assert by[("stream.submit", 0)].parent == -1


def test_stream_readings_per_step():
    s = spans(*STEPS)
    # Waits outside joins: 3 + 4 ms over two steps.
    assert s.stream_sync_ms() == pytest.approx(3.5)
    # Steps 10 + 20 ms, less the 8 ms join and the 7 ms of waits.
    assert s.stream_host_self_ms() == pytest.approx(7.5)
    # Together they are the steps' time with their joins left out.
    assert s.stream_sync_ms() + s.stream_host_self_ms() == pytest.approx(
        (10 + 20 - 8) / 2)
    assert s.live_slots() == pytest.approx(3.5)
    assert s.queue_wait_ms() == pytest.approx(21.0)


def test_device_time_per_step_and_its_absence():
    s = spans(*STEPS)
    heads = [x for x in s.spans if x.name == "stream.head"]
    heads[0].device_s, heads[1].device_s = 0.004, 0.006
    assert s.stream_device_ms("head") == pytest.approx(5.0)
    assert s.stream_device_ms("tail") == pytest.approx(0.0)
    assert spans(*STEPS, chips=0).stream_device_ms("head") is None


def test_fleet_readings_per_request():
    s = spans((0, 10, "fleet.serve", {"requests": 2}),
              (1, 4, "edge", {"uid": 1}), (2, 3, "sync", {}),
              (4, 7, "edge", {"uid": 2}), (5, 7, "sync", {}),
              (20, 26, "fleet.serve", {"requests": 1}),
              (21, 22, "sync", {}))
    assert s.fleet_sync_ms() == pytest.approx(4 / 3)
    assert s.fleet_host_self_ms() == pytest.approx(12 / 3)


def test_nothing_to_read_without_spans():
    s = spans()
    for reading in (s.stream_sync_ms, s.stream_host_self_ms, s.live_slots,
                    s.queue_wait_ms, s.fleet_sync_ms, s.fleet_host_self_ms):
        assert reading() is None
    assert s.stream_device_ms("head") is None


def test_idle_is_owned_by_the_innermost_span():
    gaps = [(0.0015, 0.0025), (0.006, 0.007), (0.012, 0.014),
            (0.050, 0.060)]
    bench = {"step": [(0.0, 0.015)]}
    s = spans(*STEPS, gaps=gaps, bench=bench)
    idle = s.idle_by_owner()
    assert idle["jalad.stream.head"] == pytest.approx(0.001)
    assert idle["jalad.sync"] == pytest.approx(0.001)
    # After the first step ends, inside the benchmark's span around it.
    assert "jalad.stream.step" not in idle
    assert idle["bench:step"] == pytest.approx(0.002)
    assert idle[ps.OUTSIDE] == pytest.approx(0.010)
    assert sum(idle.values()) == pytest.approx(0.014)
    assert s.idle_in("stream.step") == pytest.approx(0.002)
    assert s.longest_gaps(2) == [(ps.OUTSIDE, pytest.approx(0.010)),
                                 ("bench:step", pytest.approx(0.002))]


def test_a_trace_without_program_spans():
    """The trace recorded before the program had spans (``stream_v5e``):
    every reading is None, and idle time falls to the benchmark's spans
    as ``trace_reduce`` labels it."""
    path = str(DATA / "stream_v5e.xplane.pb")
    s = ps.read(path)
    assert s.spans == [] and s.chips == 1
    assert s.stream_sync_ms() is None and s.fleet_sync_ms() is None
    idle = s.idle_by_owner()
    summary = tr.reduce_trace(path)
    busy = summary.busy_s["/device:TPU:0"]
    assert sum(idle.values()) == pytest.approx(summary.window_s - busy)
    assert s.longest_gaps(2) == [("bench:encode", pytest.approx(g))
                                 for _, g in summary.idle_gaps[:2]]


def test_a_run_reads_the_newest_profile_of_its_window(tmp_path,
                                                       monkeypatch):
    monkeypatch.setattr(ps, "TRACE_ROOT", tmp_path)
    src = DATA / "stream_v5e.xplane.pb"
    summary = tr.reduce_trace(str(src))
    run = SimpleNamespace(trace=summary)
    assert ps.of_run(run) is None                     # nothing recorded
    assert ps.of_run(SimpleNamespace(trace=None)) is None
    old = tmp_path / "other" / "old.xplane.pb"        # not a profile
    old.parent.mkdir()
    old.write_bytes(b"not a profile")
    os.utime(old, (1, 1))
    new = tmp_path / "cell" / "new.xplane.pb"
    new.parent.mkdir()
    shutil.copy(src, new)
    assert ps.of_run(run).window == ps.read(str(new)).window
    # A profile of another window is not this run's.
    run.trace = SimpleNamespace(window_s=summary.window_s + 1.0)
    assert ps.of_run(run) is None
    for name in ("sync_ms.stream", "host_self_ms.stream",
                 "head_device_ms.stream", "tail_device_ms.stream",
                 "live_slots.stream", "queue_wait_ms.stream",
                 "sync_ms.fleet", "host_self_ms.fleet"):
        assert load_reader(name)(run) is None
        assert load_reader(name)(SimpleNamespace(trace=summary)) is None


def _fetches(path):
    """Host intervals of the device-to-host fetches a trace records."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events
                       if e.name == "np.asarray(jax.Array)")
    return out


def _inside(iv, spans):
    return any(s.start - 1e-9 <= iv[0] and iv[1] <= s.end + 1e-9
               for s in spans)


def test_recorded_v5e_stream_spans():
    """Two engine steps of the chat cell, the first with a join, traced on
    a TPU v5 lite with the program's spans (``record_trace.py``)."""
    path = str(DATA / "stream_spans_v5e.xplane.pb")
    summary = tr.reduce_trace(path)
    s = ps.read(path)
    # The head and tail programs by their own names.
    assert "jit__lambda" not in summary.program_s
    assert max(summary.program_s, key=summary.program_s.get) == \
        "jit_decode_head"
    assert {"jit_decode_tail", "jit_prefill_head",
            "jit_prefill_tail"} <= set(summary.program_s)
    assert len(s.named("stream.step")) == 2
    assert [x.stats for x in s.named("stream.join")] == [
        {"uid": 1, "prompt_len": 192}]
    assert [x.stats for x in s.named("stream.head")] == [{"slots": 2}] * 2
    # Device time goes to the span that launched it, as the benchmark's
    # spans read it; the head, the tail and the join hold the step's.
    dev = s.program_span_device_s
    for ours, theirs in (("stream.step", "step"), ("stream.join", "join"),
                         ("codec.encode", "encode"),
                         ("codec.decode", "decode")):
        assert dev[ours] == pytest.approx(summary.span_device_s[theirs])
    assert dev["stream.prefill_head"] == pytest.approx(
        summary.program_s["jit_prefill_head"])
    assert dev["stream.head"] + dev["stream.tail"] + dev["stream.join"] == \
        pytest.approx(dev["stream.step"], rel=1e-3)
    # Waiting and the host's own work make up the steps, joins left out.
    steps = sum(x.seconds for x in s.named("stream.step"))
    joins = sum(x.seconds for x in s.named("stream.join"))
    assert s.stream_sync_ms() + s.stream_host_self_ms() == pytest.approx(
        (steps - joins) / 2 * 1e3)
    # Every fetch the host made waited inside a jalad.sync.
    fetches = _fetches(path)
    assert len(fetches) == 12
    assert all(_inside(f, s.named("sync")) for f in fetches)
    # Every idle stretch has a program span for its owner; the step's own
    # self time holds almost none of it.
    idle = s.idle_by_owner()
    assert sum(idle.values()) == pytest.approx(
        summary.window_s - summary.busy_s["/device:TPU:0"])
    assert all(owner.startswith("jalad.") for owner in idle)
    assert idle["jalad.stream.step"] < 0.05 * s.idle_in("stream.step")
    assert s.longest_gaps(1) == [("jalad.stream.head",
                                  pytest.approx(0.013661778))]


def test_recorded_v5e_fleet_spans():
    """Two serve calls of the fleet cell (one request, then two), traced on
    a TPU v5 lite with the program's spans (``record_trace.py``)."""
    path = str(DATA / "fleet_spans_v5e.xplane.pb")
    summary = tr.reduce_trace(path)
    s = ps.read(path)
    assert [x.stats for x in s.named("fleet.serve")] == [
        {"requests": 1}, {"requests": 2}]
    assert [x.stats["uid"] for x in s.named("edge")] == [0, 1, 2]
    assert [x.stats for x in s.named("cloud")] == [{"rows": 1}, {"rows": 2}]
    assert all(x.up == ("fleet.serve",) for x in s.named("edge"))
    dev, prog = s.program_span_device_s, summary.program_s
    assert dev["edge"] == pytest.approx(prog["jit_run_head"]
                                        + prog["jit_quantize_pack"])
    assert dev["cloud"] == pytest.approx(dev["fleet.serve"] - dev["edge"])
    assert dev["fleet.serve"] == pytest.approx(summary.span_device_s["serve"])
    fetches = _fetches(path)
    assert len(fetches) == 9
    assert all(_inside(f, s.named("sync")) for f in fetches)
    serve = sum(x.seconds for x in s.named("fleet.serve"))
    assert s.fleet_sync_ms() + s.fleet_host_self_ms() == pytest.approx(
        serve / 3 * 1e3)
    idle = s.idle_by_owner()
    assert idle["jalad.fleet.serve"] < 0.05 * s.idle_in("fleet.serve")


READINGS = {
    "stream": {"sync_ms.stream": 24.557005, "host_self_ms.stream": 75.25271,
               "head_device_ms.stream": 43.794333,
               "tail_device_ms.stream": 20.949942, "live_slots.stream": 2.0},
    "fleet": {"sync_ms.fleet": 1.4109363, "host_self_ms.fleet": 3.661347},
}


@pytest.mark.parametrize("name", [
    "sync_ms.stream", "host_self_ms.stream", "head_device_ms.stream",
    "tail_device_ms.stream", "live_slots.stream", "queue_wait_ms.stream",
    "sync_ms.fleet", "host_self_ms.fleet"])
def test_each_reader_on_the_recorded_traces(name, tmp_path, monkeypatch):
    """Each new per-layer metric, read from a traced run's profile the way
    ``bench.run`` hands it over; the request in the stream trace was
    submitted before its window, so no queue wait is read there."""
    monkeypatch.setattr(ps, "TRACE_ROOT", tmp_path)
    for kind in ("stream", "fleet"):
        src = DATA / f"{kind}_spans_v5e.xplane.pb"
        dst = tmp_path / kind / "run.xplane.pb"
        dst.parent.mkdir()
        shutil.copy(src, dst)
        run = SimpleNamespace(trace=tr.reduce_trace(str(dst)))
        want = READINGS[kind].get(name)
        got = load_reader(name)(run)
        assert got == (None if want is None else pytest.approx(want))
        shutil.rmtree(dst.parent)
