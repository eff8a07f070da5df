"""Records the small v5e traces the reduction's tests read (``data/``).

On the chip, ``record`` serves a cell for a few seconds under the
profiler (the benchmark's spans on, ``bench:window`` around the whole
drive) and writes the raw profile:

    python3 -m bench.tests.record_trace record <cell> <seconds> <out dir>

``cut`` then keeps a few of the program's top-level spans (``n``
``jalad.<top>`` spans from the one that holds the ``at``-th
``jalad.<holds>``, counted from 0, or from the end where negative), moves
``bench:window`` onto them, and drops the rest: of the device planes all
but the ``XLA Ops`` and ``XLA Modules`` events in that window, each
operation kept by its name alone; of the host plane all but the
benchmark's and the program's spans, the program launches and their
links, and the host's fetches (``np.asarray(jax.Array)``). It needs
TensorFlow's ``xplane_pb2``, and no chip:

    python3 -m bench.tests.record_trace cut <raw> <out> <top> <holds> <at> <n>

with ``.xplane.pb`` files ``raw`` and ``out``. ``stream_spans_v5e`` was
recorded from ``olmo1b-chat-steady`` for 2.5 s and cut with ``stream.step
stream.join -1 2``; ``fleet_spans_v5e`` from ``resnet50-fleet-burst`` for
1.5 s, cut with ``fleet.serve fleet.serve 0 2``.
"""
from __future__ import annotations

import sys

KEEP_HOST = ("tpu::System::Execute",
             "tpu::System::Execute=>IssueSequencedEvent", "DoEnqueueProgram",
             "np.asarray(jax.Array)")


def record(cell_name: str, seconds: float, out_dir: str, seed: int = 7):
    import jax

    from bench import harness, run, spec

    cell = spec.resolve(cell_name)
    system, rec, schedule, _, _ = run.prepare(cell, seed, seconds, True)
    rec.reset()
    system.start(True)
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("bench:window"):
        harness.run_window(system, schedule, seconds, rec)
    jax.profiler.stop_trace()
    system.stop()


def _events(line, names):
    """(start ps, end ps, name, event) of a line's events."""
    for ev in line.events:
        s = line.timestamp_ns * 1000 + ev.offset_ps
        yield s, s + ev.duration_ps, names[ev.metadata_id], ev


def cut(src: str, dst: str, top: str, holds: str, at: int, n: int) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    host = next(p for p in space.planes if p.name == "/host:CPU")
    names = {k: m.name for k, m in host.event_metadata.items()}
    tops, held = [], []
    for line in host.lines:
        for s, e, name, _ in _events(line, names):
            if name == "jalad." + top:
                tops.append((s, e))
            if name == "jalad." + holds:
                held.append(s)
    tops.sort()
    t = sorted(held)[at]
    k = next(i for i, (s, e) in enumerate(tops) if s <= t < e)
    lo, hi = tops[k][0], tops[min(k + n, len(tops)) - 1][1]
    keep_planes = []
    for plane in space.planes:
        names = {k: m.name for k, m in plane.event_metadata.items()}
        device = plane.name.startswith("/device:TPU:")
        if not (device or plane is host):
            continue
        used = set()
        for line in list(plane.lines):
            kept = []
            for s, e, name, ev in _events(line, names):
                if device:
                    keep = line.name in ("XLA Ops", "XLA Modules")
                    if line.name == "XLA Ops":
                        del ev.stats[:]       # read by name alone
                else:
                    keep = (name.startswith(("bench:", "jalad."))
                            or name in KEEP_HOST)
                if name == "bench:window":
                    ev.offset_ps = lo - line.timestamp_ns * 1000
                    ev.duration_ps = hi - lo
                    kept.append(ev)
                elif keep and e > lo and s < hi:
                    kept.append(ev)
            del line.events[:]
            line.events.extend(kept)
            used.update(ev.metadata_id for ev in kept)
            if not kept:
                plane.lines.remove(line)
        for k in list(plane.event_metadata):
            if k not in used:
                del plane.event_metadata[k]
            elif device:                      # its name alone
                name = plane.event_metadata[k].name
                plane.event_metadata[k].Clear()
                plane.event_metadata[k].id, plane.event_metadata[k].name = (
                    k, name)
        keep_planes.append(plane)
    out = xplane_pb2.XSpace()
    out.planes.extend(keep_planes)
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())


if __name__ == "__main__":
    cmd, *args = sys.argv[1:]
    if cmd == "record":
        record(args[0], float(args[1]), args[2])
    else:
        cut(args[0], args[1], args[2], args[3], int(args[4]), int(args[5]))
