"""How many programs a ``TokenStreamSession`` step launches, traced on the
CPU: each side of the cut is one donated program over the whole slot
state, so the serving front's phases launch the same few programs
whatever the number of live slots. Counted as ``PjRtCpuExecutable::
Execute`` events on the host plane, each given to the innermost
``jalad.*`` span that holds it, joins left out."""
from collections import Counter, defaultdict

import jax
import numpy as np

from conftest import reduced_model
from repro.config import ServeConfig
from repro.core.decoupler import DecoupledPlan
from repro.serving.scheduler import GenRequest
from repro.serving.streaming import TokenStreamSession

LAUNCH = "PjRtCpuExecutable::Execute"
# Programs a step may launch in each phase of the serving front: the head
# program; the tail program and the (k, V) view of its logits rows; the
# select; the token record. An eviction adds one program per side. (With
# every slot live the view is the whole array, and costs no launch.)
CEILING = {"stream.head": 1, "stream.tail": 2, "stream.select": 1,
           "stream.record": 1}


def launches_by_step(trace_dir):
    """Per ``jalad.stream.step`` (in order), the launches counted per
    innermost ``jalad.*`` span, those under ``jalad.stream.join`` left
    out, and the step's live slots (``stream.head``'s ``slots``)."""
    from jax.profiler import ProfileData

    path = next(trace_dir.glob("**/*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.start_ns, e.start_ns + e.duration_ns,
                      e.name[len("jalad."):], dict(e.stats))
                     for e in line.events if e.name.startswith("jalad.")]
            starts = [e.start_ns for e in line.events if e.name == LAUNCH]
            for s, e, _, _ in (x for x in spans if x[2] == "stream.step"):
                inner = [x for x in spans if s <= x[0] and x[1] <= e]
                joins = [x for x in inner if x[2] == "stream.join"]
                slots = next(x[3]["slots"] for x in inner
                             if x[2] == "stream.head")
                count = Counter()
                for t in starts:
                    if not s <= t < e or any(a <= t < b for a, b, _, _
                                             in joins):
                        continue
                    holder = min((x for x in inner if x[0] <= t < x[1]),
                                 key=lambda x: x[1] - x[0])
                    count[holder[2]] += 1
                out.append((s, slots, count))
    return [(slots, count) for _, slots, count in sorted(out)]


def test_step_launches_do_not_grow_with_live_slots(tmp_path):
    model, params = reduced_model("olmo-1b")
    plan = DecoupledPlan(point=0, bits=8, predicted_latency=0.0,
                         predicted_acc_drop=0.0, solve_ms=0.0,
                         codec="bitpack")
    sess = TokenStreamSession(model, params,
                              ServeConfig(max_batch=4, max_seq_len=32),
                              plan=plan)
    rng = np.random.default_rng(0)

    def drive():
        # One request joins in each of the first three steps, so they run
        # with 1, 2 and 3 live slots; then all three leave together.
        for _ in range(3):
            sess.submit(GenRequest(uid=int(rng.integers(1 << 20)),
                                   tokens=rng.integers(
                                       1, model.cfg.vocab_size, 8
                                   ).astype(np.int32), max_new_tokens=6))
            sess.step()
        sess.run()

    drive()                          # compiles every shape of the trace
    base = sess.step_count
    sess.events.clear()
    with jax.profiler.trace(str(tmp_path)):
        drive()
    evicted = Counter(step for kind, step, _ in sess.events
                      if kind == "evict")
    steps = launches_by_step(tmp_path)
    assert len(steps) == sess.step_count - base
    assert [slots for slots, _ in steps[:3]] == [1, 2, 3]
    per_k = defaultdict(list)
    for i, (slots, count) in enumerate(steps):
        count["stream.record"] -= 2 * evicted[base + i + 1]
        per_k[slots].append({p: count[p] for p in CEILING})
    assert sorted(per_k) == [1, 2, 3]
    assert sum(evicted.values()) == 3
    first = per_k[1][0]
    for k, counts in per_k.items():
        for c in counts:
            assert c == first, (k, c, first)
    for phase, ceiling in CEILING.items():
        assert 1 <= first[phase] <= ceiling, (phase, first)
