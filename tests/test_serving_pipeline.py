"""Pipelined serving subsystem: continuous-batching join/evict semantics,
overlap correctness (pipelined numerics == synchronous numerics), and
live re-decoupling on a bandwidth step-change."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_model
from repro.config import EDGE_TK1, JaladConfig, ServeConfig, get_config
from repro.core.adaptation import AdaptationController
from repro.data.synthetic import make_batch
from repro.serving.edge_cloud import EdgeCloudServer, build_edge_cloud_server
from repro.serving.engine import ServeSession
from repro.serving.pipeline import PipelinedEdgeCloudServer, PipelineRequest
from repro.serving.scheduler import ContinuousBatchingEngine, GenRequest


# ---------------------------------------------------------------------------
# Continuous batching (LM serving)
# ---------------------------------------------------------------------------


def _make_engine(max_batch=3, max_seq_len=48):
    model, params = reduced_model("olmo-1b")
    return ContinuousBatchingEngine(
        model, params, ServeConfig(max_batch=max_batch,
                                   max_seq_len=max_seq_len)
    ), model, params


def _prompts(cfg, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in sizes]


def test_join_evict_order_and_slot_reuse():
    """Short requests evict before long ones; a queued request joins the
    freed slot mid-flight instead of waiting for the whole wave."""
    eng, model, _ = _make_engine(max_batch=2)
    p = _prompts(model.cfg, [5, 9, 7])
    eng.submit(GenRequest(uid=0, tokens=p[0], max_new_tokens=8))
    eng.submit(GenRequest(uid=1, tokens=p[1], max_new_tokens=2))
    eng.submit(GenRequest(uid=2, tokens=p[2], max_new_tokens=3))
    done = eng.run()

    assert [r.uid for r in done] == [1, 2, 0]      # finish order, not FIFO
    ev = eng.events
    # uid 2 must join strictly after uid 1's eviction frees the slot, and
    # strictly before uid 0 finishes (it rides along mid-decode).
    evict1 = ev.index(("evict", [e for e in ev if e[0] == "evict"
                                 and e[2] == 1][0][1], 1))
    join2 = ev.index(("join", [e for e in ev if e[0] == "join"
                               and e[2] == 2][0][1], 2))
    assert join2 > evict1
    assert done[1].slot == done[0].slot            # slot actually reused
    assert done[1].joined_step > done[0].done_step - 1
    assert done[2].done_step > done[1].done_step - 1


def test_arrival_defers_admission():
    eng, model, _ = _make_engine(max_batch=4)
    p = _prompts(model.cfg, [6, 6])
    eng.submit(GenRequest(uid=0, tokens=p[0], max_new_tokens=3))
    eng.submit(GenRequest(uid=1, tokens=p[1], max_new_tokens=3, arrival=5))
    eng.run()
    joins = {uid: step for kind, step, uid in eng.events if kind == "join"}
    assert joins[0] == 1
    assert joins[1] > 5


def test_eos_evicts_early():
    eng, model, _ = _make_engine()
    (prompt,) = _prompts(model.cfg, [8])
    # Discover the greedy continuation, then use its 2nd token as EOS.
    probe = GenRequest(uid=0, tokens=prompt, max_new_tokens=6)
    eng.submit(probe)
    eng.run()
    eos = int(probe.out_tokens[1])

    eng2, _, _ = _make_engine()
    req = GenRequest(uid=1, tokens=prompt, max_new_tokens=6, eos_id=eos)
    eng2.submit(req)
    eng2.run()
    # evicts at the FIRST occurrence of eos (greedy decode may repeat
    # tokens, so that can be earlier than index 1)
    assert len(req.out_tokens) == probe.out_tokens.index(eos) + 1
    assert req.out_tokens[-1] == eos
    assert len(req.out_tokens) < 6


def test_continuous_output_matches_synchronous_batch1():
    """The defining correctness property: continuous batching (staggered
    joins, slot reuse, batched decode) is bit-identical to serving each
    request alone through ServeSession.generate."""
    eng, model, params = _make_engine(max_batch=3, max_seq_len=48)
    sizes = [5, 9, 7, 6, 4]
    max_new = [6, 3, 8, 4, 5]
    arrivals = [0, 0, 0, 4, 6]
    prompts = _prompts(model.cfg, sizes, seed=3)
    for i in range(len(sizes)):
        eng.submit(GenRequest(uid=i, tokens=prompts[i],
                              max_new_tokens=max_new[i],
                              arrival=arrivals[i]))
    done = eng.run()
    assert len(done) == len(sizes)

    session = ServeSession(model, params,
                           ServeConfig(max_batch=3, max_seq_len=48))
    for r in done:
        ref = session.generate(
            {"tokens": jnp.asarray(r.tokens[None, :])}, r.max_new_tokens
        )[0]
        np.testing.assert_array_equal(r.result, np.asarray(ref))


def test_sampled_output_follows_each_request_key():
    """Batched sampling draws each request's tokens from its own key
    (``seed + uid``), one split per token, whatever the other slots do;
    a greedy request beside them consumes none. The reference serves each
    request alone through ServeSession's programs and samples on the
    host's side of the loop."""
    eng, model, params = _make_engine(max_batch=3, max_seq_len=48)
    temps = [0.8, 0.0, 1.3]
    prompts = _prompts(model.cfg, [5, 7, 6], seed=4)
    for i, t in enumerate(temps):
        eng.submit(GenRequest(uid=i, tokens=prompts[i], max_new_tokens=6,
                              temperature=t))
    done = {r.uid: r.result for r in eng.run()}

    session = ServeSession(model, params,
                           ServeConfig(max_batch=3, max_seq_len=48))
    for i, t in enumerate(temps):
        logits, caches = session._prefill(
            params, {"tokens": jnp.asarray(prompts[i][None, :])})
        key = jax.random.key(eng.cfg.seed + i)
        out = []
        for pos in range(len(prompts[i]), len(prompts[i]) + 6):
            row = logits[0, -1]
            if t > 0:
                key, sub = jax.random.split(key)
                out.append(int(jax.random.categorical(sub, row / t)))
            else:
                out.append(int(jnp.argmax(row)))
            logits, caches = session._decode(
                params, jnp.asarray([[out[-1]]], jnp.int32),
                jnp.int32(pos), caches)
        np.testing.assert_array_equal(done[i], out)


# ---------------------------------------------------------------------------
# Pipelined edge-cloud serving
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def jalad_setup():
    cfg = get_config("resnet50").reduced()
    jc = JaladConfig(bits_choices=(2, 4, 8), accuracy_drop_budget=0.10,
                     bandwidth_bytes_per_s=10e6)
    srv, params = build_edge_cloud_server(cfg, jc, calib_batches=2,
                                          calib_batch_size=8)
    return srv.engine, params, cfg


def test_pipelined_numerics_match_synchronous(jalad_setup):
    """Overlap must not change results: at the same plan, the pipelined
    server's logits equal the synchronous server's."""
    engine, params, cfg = jalad_setup
    batch = make_batch(cfg, 4, 0, seed=11)
    bw = 1e6

    sync = EdgeCloudServer(engine, params)
    logits_sync, bd = sync.serve_batch(dict(batch), bandwidth=bw)

    pipe = PipelinedEdgeCloudServer(engine, params)
    # Warm the pipeline's bandwidth estimator to the same true bandwidth
    # the synchronous server was told, so both decide the same plan.
    pipe.controller.observe_transfer(bw, 1.0)
    (done,) = pipe.serve([PipelineRequest(uid=0, batch=dict(batch),
                                          bandwidth=bw)])
    assert (done.timeline.plan_point, done.timeline.plan_bits) == \
        (bd.plan_point, bd.plan_bits)
    np.testing.assert_allclose(
        np.asarray(done.logits, np.float32),
        np.asarray(logits_sync, np.float32),
        rtol=1e-5, atol=1e-5,
    )


def test_pipeline_overlaps_stages(jalad_setup):
    """Simulated wall-clock: the 3-stage pipeline finishes a request
    stream strictly faster than back-to-back serving, and the stage
    intervals actually interleave."""
    engine, params, cfg = jalad_setup
    pipe = PipelinedEdgeCloudServer(engine, params)
    reqs = [PipelineRequest(uid=i, batch=make_batch(cfg, 4, 0, seed=20 + i),
                            bandwidth=500e3) for i in range(6)]
    done = pipe.serve(reqs)
    assert len(done) == 6
    assert pipe.makespan_s < pipe.synchronous_time_s()
    # Pipelining evidence: some request starts its edge compute before the
    # previous request has left the cloud stage.
    overlapped = any(
        done[i + 1].timeline.edge_start < done[i].timeline.cloud_end
        for i in range(len(done) - 1)
    )
    assert overlapped
    # Per-stage occupancy never overlaps within a stage (FIFO correctness).
    for a, b in zip(done, done[1:]):
        assert b.timeline.edge_start >= a.timeline.edge_end - 1e-12
        assert b.timeline.xfer_start >= a.timeline.xfer_end - 1e-12
        assert b.timeline.cloud_start >= a.timeline.cloud_end - 1e-12


def test_adaptation_on_bandwidth_step_change(jalad_setup):
    """A 500x bandwidth collapse mid-stream must trigger a re-decoupling
    through the live estimator (link-stage observations -> EWMA ->
    controller), and the listener hook must fire for it."""
    engine, params, cfg = jalad_setup
    # A slow edge (TK1) keeps the optimum bandwidth-sensitive: with the
    # corrected per-batch S_i(c, k) a fast TX2 edge makes the byte-minimal
    # late cut optimal at EVERY bandwidth, so there is nothing to adapt.
    # On TK1 the high-BW optimum is an early cloud-heavy cut that the
    # collapse must abandon.
    engine = engine.for_edge(EDGE_TK1)
    controller = AdaptationController(engine)
    # micro_batch=1 keeps the per-request plan-decision granularity this
    # test schedules around (micro-batching coarsens adaptation to one
    # decision burst per drained group; see the dedicated test below).
    pipe = PipelinedEdgeCloudServer(engine, params, controller=controller,
                                    micro_batch=1)

    batches = [make_batch(cfg, 4, 0, seed=40 + i) for i in range(10)]
    bws = [10e6] * 3 + [20e3] * 7          # step change after request 3
    reqs = [PipelineRequest(uid=i, batch=b, bandwidth=bw)
            for i, (b, bw) in enumerate(zip(batches, bws))]
    done = pipe.serve(reqs)

    plans = [(r.timeline.plan_point, r.timeline.plan_bits) for r in done]
    assert len(set(plans)) > 1, f"plan never adapted: {plans}"
    # history: initial plan + at least one re-decoupling event
    assert len(controller.history) >= 2
    switch = controller.history[-1]
    assert switch.old_plan is not None
    # re-planned while the EWMA tracked the collapse (below the old BW)
    assert switch.bandwidth < 10e6
    # the listener hook observed the same events
    assert len(pipe.adaptation_log) == len(controller.history)
    # after the switch the transfers shrink (edge-biased, fewer bits)
    assert done[-1].timeline.bytes_sent <= done[0].timeline.bytes_sent


def test_microbatched_edge_numerics_match_synchronous(jalad_setup):
    """The micro-batched edge stage (one batched codec launch per drained
    group) must be invisible in the results: same plans, same logits, and
    the same simulated-clock accounting as the synchronous server."""
    engine, params, cfg = jalad_setup
    bw = 1e6
    batches = [make_batch(cfg, 4, 0, seed=70 + i) for i in range(5)]

    sync = EdgeCloudServer(engine, params)
    sync.controller.observe_transfer(bw, 1.0)
    sync_out = [sync.serve_batch(dict(b), bandwidth=bw) for b in batches]

    pipe = PipelinedEdgeCloudServer(engine, params, micro_batch=4)
    pipe.controller.observe_transfer(bw, 1.0)
    done = pipe.serve([PipelineRequest(uid=i, batch=dict(b), bandwidth=bw)
                       for i, b in enumerate(batches)])
    assert len(done) == 5
    by_uid = {r.uid: r for r in done}
    for i, (logits_sync, bd) in enumerate(sync_out):
        r = by_uid[i]
        assert (r.timeline.plan_point, r.timeline.plan_bits) == \
            (bd.plan_point, bd.plan_bits)
        assert r.timeline.bytes_sent == bd.bytes_sent
        np.testing.assert_allclose(
            np.asarray(r.logits, np.float32),
            np.asarray(logits_sync, np.float32),
            rtol=1e-5, atol=1e-5,
        )


def test_adaptation_fires_under_microbatching(jalad_setup):
    """Micro-batching coarsens re-decoupling to one decision burst per
    drained group, but a sustained bandwidth collapse must still move the
    plan within a few groups."""
    engine, params, cfg = jalad_setup
    engine = engine.for_edge(EDGE_TK1)   # see step-change test above
    controller = AdaptationController(engine)
    pipe = PipelinedEdgeCloudServer(engine, params, controller=controller,
                                    micro_batch=4)
    n = 16
    batches = [make_batch(cfg, 4, 0, seed=90 + i) for i in range(n)]
    bws = [10e6] * 3 + [20e3] * (n - 3)
    done = pipe.serve([PipelineRequest(uid=i, batch=b, bandwidth=bw)
                       for i, (b, bw) in enumerate(zip(batches, bws))])
    plans = [(r.timeline.plan_point, r.timeline.plan_bits) for r in done]
    assert len(set(plans)) > 1, f"plan never adapted: {plans}"
    assert len(controller.history) >= 2
    assert done[-1].timeline.bytes_sent <= done[0].timeline.bytes_sent


def test_microbatched_sync_server_matches_per_request(jalad_setup):
    """EdgeCloudServer.serve_microbatch: one plan decision + one batched
    encode launch, per-request results identical to serve_batch."""
    engine, params, cfg = jalad_setup
    bw = 1e6
    batches = [make_batch(cfg, 4, 0, seed=110 + i) for i in range(3)]

    ref_srv = EdgeCloudServer(engine, params)
    ref_srv.controller.observe_transfer(bw, 1.0)
    ref_out = [ref_srv.serve_batch(dict(b), bandwidth=bw) for b in batches]

    srv = EdgeCloudServer(engine, params)
    srv.controller.observe_transfer(bw, 1.0)
    out = srv.serve_microbatch([dict(b) for b in batches], bandwidth=bw)
    assert len(out) == 3
    for (logits, bd), (ref_logits, ref_bd) in zip(out, ref_out):
        assert (bd.plan_point, bd.plan_bits, bd.bytes_sent) == \
            (ref_bd.plan_point, ref_bd.plan_bits, ref_bd.bytes_sent)
        np.testing.assert_allclose(
            np.asarray(logits, np.float32),
            np.asarray(ref_logits, np.float32),
            rtol=1e-5, atol=1e-5,
        )
