"""The end-to-end arithmetic: tails over every request due in the window,
unfinished requests counted at the time they have waited, rates over the
whole window; and the open-loop and closed-loop drivers."""
from __future__ import annotations

import math
import time

import pytest

from bench import harness, traffic


def _window():
    return harness.Window(start=100.0, seconds=10.0)


def test_first_token_counts_unserved_at_their_wait():
    rec, win = harness.Recorder(), _window()
    rec.arrival.update({1: 101.0, 2: 105.0, 3: 109.0, 4: 99.0})
    rec.tokens[1] = [101.5, 101.6]
    rec.tokens[2] = [112.0]                 # first token after the window
    ttft = sorted(harness.first_token_ms(rec, win))
    # uid 4 arrived before the window: not due in it.
    assert ttft == pytest.approx([500.0, 1000.0, 5000.0])


def test_request_latency_counts_unfinished_at_elapsed():
    rec, win = harness.Recorder(), _window()
    rec.arrival.update({1: 100.0, 2: 108.0})
    rec.done[1] = 100.25
    lat = sorted(harness.request_latency_ms(rec, win))
    assert lat == pytest.approx([250.0, 2000.0])


def test_token_gaps_inside_the_window_only():
    rec, win = harness.Recorder(), _window()
    rec.tokens[1] = [99.0, 100.5, 100.6, 100.8, 111.0]
    rec.tokens[2] = [105.0]
    assert harness.token_gaps_ms(rec, win) == pytest.approx([100.0, 200.0])
    assert harness.tokens_in(rec, win) == 4


def test_end_to_end_by_name():
    rec, win = harness.Recorder(), _window()
    rec.arrival[1] = 100.0
    rec.tokens[1] = [100.1 + 0.1 * i for i in range(20)]
    out = harness.end_to_end(
        ["ttft_p95_ms", "itl_p95_ms", "tokens_per_s", "setup_s"],
        rec, win, setup_s=42.0)
    assert out["ttft_p95_ms"] == pytest.approx(100.0)
    assert out["itl_p95_ms"] == pytest.approx(100.0)
    assert out["tokens_per_s"] == pytest.approx(2.0)
    assert out["setup_s"] == 42.0
    with pytest.raises(KeyError):
        harness.end_to_end(["no_such_metric"], rec, win, 0.0)
    out = harness.end_to_end(["req_latency_p95_ms"], rec, win, 0.0)
    assert out["req_latency_p95_ms"] == pytest.approx(10000.0)


def test_percentile_of_nothing_is_nan():
    assert math.isnan(harness.percentile([], 95))


class _Counter:
    """A system that serves one request per pump, each taking ``cost``
    seconds, and keeps count of the most requests it held at once."""

    def __init__(self, rec, cost=0.0):
        self.rec, self.queue, self.cost, self.most = rec, [], cost, 0

    def submit(self, req, uid):
        self.queue.append(uid)
        self.most = max(self.most, len(self.queue))

    def busy(self):
        return bool(self.queue)

    def queued(self):
        return len(self.queue)

    def pump(self):
        time.sleep(self.cost)
        uid = self.queue.pop(0)
        self.rec.token(uid)
        self.rec.finish(uid)


def test_open_loop_submits_on_schedule():
    rec = harness.Recorder()
    sched = traffic.Schedule("open_loop", [
        traffic.Request(i, 0.05 * i) for i in range(6)])
    sys_ = _Counter(rec)
    win = harness.run_window(sys_, sched, 0.2, rec)
    assert win.submitted == 4
    assert all(rec.arrival[i] == pytest.approx(win.start + 0.05 * i)
               for i in range(4))
    assert all(lag >= 0 for lag in win.lateness_s)


def test_closed_loop_keeps_one_request_per_client():
    rec = harness.Recorder()
    sched = traffic.Schedule("closed_loop", [traffic.Request(0, 0.0)],
                             {"clients": 3})
    sys_ = _Counter(rec, cost=0.002)
    win = harness.run_window(sys_, sched, 0.1, rec)
    assert sys_.most == 3                # never more than one per client
    assert win.submitted == len(rec.arrival) > 3
    # Each next request is due when the one before it finished.
    finished = sorted(rec.done.values())
    later = sorted(t for u, t in rec.arrival.items() if u >= 3)
    assert later == pytest.approx(finished[:len(later)])


def test_closed_loop_thinks_between_requests():
    rec = harness.Recorder()
    sched = traffic.Schedule("closed_loop", [traffic.Request(0, 0.0)],
                             {"clients": 1, "think_s": 0.04})
    sys_ = _Counter(rec)
    win = harness.run_window(sys_, sched, 0.1, rec)
    assert 2 <= win.submitted <= 3       # at 0, 0.04 and 0.08 s
    t = [rec.arrival[u] for u in range(win.submitted)]
    for u in range(1, win.submitted):
        assert t[u] == pytest.approx(rec.done[u - 1] + 0.04)


def test_unknown_driver_is_refused():
    with pytest.raises(KeyError):
        harness.run_window(_Counter(harness.Recorder()),
                           traffic.Schedule("no_such_driver", []), 0.01,
                           harness.Recorder())


def test_gc_watch_records_pauses_only_while_on():
    import gc

    watch = harness.GcWatch()
    gc.callbacks.append(watch)
    try:
        gc.collect()
        assert watch.pauses == []
        watch.on = True
        gc.collect()
    finally:
        gc.callbacks.remove(watch)
    assert [g for g, _ in watch.pauses] == [2]
    assert "1 collections {2: 1}" in watch.summary()


def test_ttft_reader_reads_every_request_of_the_window():
    from bench import spec
    from bench.run import RunView

    rec, win = harness.Recorder(), _window()
    rec.arrival.update({1: 101.0, 2: 105.0, 3: 99.0})
    rec.tokens[1] = [101.5, 101.6]
    rec.tokens[2] = [105.1]
    read = spec.load_reader("ttft_p95_ms.stream")
    assert read(RunView(rec, None, None, None, win)) == pytest.approx(
        harness.percentile([500.0, 100.0], 95))
    empty = RunView(harness.Recorder(), None, None, None, win)
    assert read(empty) is None
