"""The program's own spans on its two served paths, traced on the CPU:
one ``TokenStreamSession.step`` with a join, and one ``FleetServer.serve``
of a few requests, run under ``jax.profiler.trace`` and read back from the
host plane. The spans carry the names, nesting and stats the per-layer
metrics read, and every device-to-host fetch on these paths lies inside a
``jalad.sync`` span."""
from collections import Counter

import jax
import numpy as np
import pytest
from jax._src import array as jax_array

from conftest import reduced_model
from repro.config import JaladConfig, ServeConfig, get_config
from repro.config.types import EDGE_TK1, EDGE_TX2
from repro.core.decoupler import DecoupledPlan
from repro.data.synthetic import make_batch
from repro.serving.fleet import FleetRequest, build_fleet_server
from repro.serving.scheduler import GenRequest
from repro.serving.streaming import TokenStreamSession


class Watch:
    """Records, for each device-to-host fetch, the names of the spans
    open around it."""

    def __init__(self, monkeypatch):
        self.open, self.fetches, self.on = [], [], False
        real, watch = jax.profiler.TraceAnnotation, self

        class Annotation:
            def __init__(self, name, **stats):
                self.name, self.real = name, real(name, **stats)

            def __enter__(self):
                watch.open.append(self.name)
                return self.real.__enter__()

            def __exit__(self, *exc):
                watch.open.pop()
                return self.real.__exit__(*exc)

        value = jax_array.ArrayImpl._value
        asarray = np.asarray

        def fetched(x):
            if self.on:
                self.fetches.append(tuple(self.open))

        def traced_value(x):
            fetched(x)
            return value.fget(x)

        def traced_asarray(x, *a, **kw):
            if isinstance(x, jax.Array):
                fetched(x)
            return asarray(x, *a, **kw)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        monkeypatch.setattr(jax_array.ArrayImpl, "_value",
                            property(traced_value))
        monkeypatch.setattr(np, "asarray", traced_asarray)

    def run(self, trace_dir, fn):
        self.on = True
        try:
            with jax.profiler.trace(str(trace_dir)):
                return fn()
        finally:
            self.on = False

    def assert_fetches_synced(self):
        assert self.fetches, "the path fetched nothing from the device"
        for stack in self.fetches:
            assert "jalad.sync" in stack, stack


def read_spans(trace_dir):
    """Every ``jalad.*`` host event as (name, start, end, stats, parent),
    the parent being the innermost span that holds it on its thread."""
    from jax.profiler import ProfileData

    path = next(trace_dir.glob("**/*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((e.start_ns, e.start_ns + e.duration_ns,
                           e.name[len("jalad."):],
                           {k: v for k, v in e.stats})
                          for e in line.events
                          if e.name.startswith("jalad.")),
                         key=lambda e: (e[0], -e[1]))
            for s, e, name, stats in evs:
                holders = [o for o in out if o[0] <= s and e <= o[1]]
                parent = min(holders, key=lambda o: o[1] - o[0])[2] \
                    if holders else None
                out.append((s, e, name, stats, parent))
    return [(name, stats, parent) for _, _, name, stats, parent in out]


def _by_parent(spans):
    return Counter((parent, name) for name, _, parent in spans)


def test_stream_step_with_a_join(monkeypatch, tmp_path):
    model, params = reduced_model("olmo-1b")
    plan = DecoupledPlan(point=0, bits=8, predicted_latency=0.0,
                         predicted_acc_drop=0.0, solve_ms=0.0,
                         codec="bitpack")
    sess = TokenStreamSession(model, params,
                              ServeConfig(max_batch=2, max_seq_len=32),
                              plan=plan)
    rng = np.random.default_rng(0)

    def request(uid):
        return GenRequest(uid=uid, tokens=rng.integers(
            1, model.cfg.vocab_size, 8).astype(np.int32), max_new_tokens=3)

    # Every shape of the traced step compiled first: a join of 8 tokens,
    # then steps of one and two live slots.
    for uid in (1, 2):
        sess.submit(request(uid))
        sess.step()
    watch = Watch(monkeypatch)

    def step():
        sess.submit(request(3))
        return sess.step()

    watch.run(tmp_path, step)
    watch.assert_fetches_synced()
    spans = read_spans(tmp_path)
    assert _by_parent(spans) == Counter({
        (None, "stream.submit"): 1, (None, "stream.step"): 1,
        ("stream.step", "stream.admit"): 1,
        ("stream.admit", "stream.join"): 1,
        ("stream.join", "stream.prefill_head"): 1,
        ("stream.join", "codec.encode"): 1,
        ("stream.join", "codec.decode"): 1,
        ("stream.join", "stream.prefill_tail"): 1,
        ("stream.join", "stream.select"): 1,
        ("stream.step", "stream.head"): 1,
        ("stream.step", "codec.encode"): 1,
        ("stream.step", "codec.decode"): 1,
        ("stream.step", "stream.tail"): 1,
        ("stream.step", "stream.select"): 1,
        ("stream.step", "stream.record"): 1,
        ("codec.encode", "sync"): 2, ("codec.encode", "codec.frame"): 2,
        ("codec.decode", "codec.unframe"): 2,
        ("stream.select", "sync"): 2})
    stats = {(name, parent): st for name, st, parent in spans}
    assert stats[("stream.submit", None)] == {"uid": 3}
    assert stats[("stream.join", "stream.admit")] == {"uid": 3,
                                                      "prompt_len": 8}
    assert stats[("stream.head", "stream.step")] == {"slots": 2}
    assert stats[("stream.tail", "stream.step")] == {"slots": 2}
    assert stats[("codec.encode", "stream.step")] == {"rows": 2}
    assert stats[("codec.decode", "stream.step")] == {"rows": 2}
    assert stats[("codec.encode", "stream.join")] == {"rows": 1}
    assert stats[("stream.record", "stream.step")] == {"tokens": 2}


@pytest.fixture(scope="module")
def fleet():
    cfg = get_config("resnet50").reduced()
    jc = JaladConfig(bits_choices=(8,), codec_choices=("bitpack",),
                     accuracy_drop_budget=1.0, bandwidth_bytes_per_s=3e5)
    fleet, _ = build_fleet_server(cfg, jc, [EDGE_TX2, EDGE_TK1],
                                  calib_batches=1, calib_batch_size=1,
                                  points=[2])
    return fleet, cfg


def _requests(cfg, uids):
    return [FleetRequest(uid=u, device_id=d, bandwidth=3e5,
                         batch=make_batch(cfg, 1, 0, seed=u))
            for u, d in zip(uids, (0, 1, 0))]


def test_fleet_serve_of_three_requests(fleet, monkeypatch, tmp_path):
    server, cfg = fleet
    server.serve(_requests(cfg, (1, 2, 3)))   # compiles every shape
    watch = Watch(monkeypatch)
    done = watch.run(tmp_path,
                     lambda: server.serve(_requests(cfg, (10, 11, 12))))
    assert all(not r.plan.is_cloud_only for r in done)
    watch.assert_fetches_synced()
    spans = read_spans(tmp_path)
    assert _by_parent(spans) == Counter({
        (None, "fleet.serve"): 1,
        ("fleet.serve", "fleet.plan"): 2,       # two waves
        ("fleet.serve", "edge"): 3,
        ("fleet.serve", "fleet.clocks"): 4,     # two waves, cloud, logs
        ("fleet.serve", "cloud"): 1,
        ("edge", "codec.encode"): 3,
        ("codec.encode", "sync"): 3,
        ("codec.encode", "codec.frame"): 3,
        ("cloud", "codec.decode"): 1,
        ("codec.decode", "codec.unframe"): 1})
    assert [st for n, st, _ in spans if n == "fleet.serve"] == [
        {"requests": 3}]
    assert [st for n, st, _ in spans if n == "fleet.plan"] == [
        {"devices": 2}, {"devices": 1}]
    assert sorted(st["uid"] for n, st, _ in spans if n == "edge") == [
        10, 11, 12]
    assert [st for n, st, _ in spans if n == "cloud"] == [{"rows": 3}]
