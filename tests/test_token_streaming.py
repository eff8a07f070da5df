"""Token-level decoupled serving: the head/tail split is bitwise-equal
to the unsplit forward, a batched TokenStreamSession reproduces each
request served alone bit for bit, join/evict keeps the batched encode
group discipline, the int8 cloud KV cache honors the bytes contract,
and decide_streaming is pinned bitwise to brute force + the ILP oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import reduced_model
from repro.codec import get_codec, list_codecs
from repro.config import JaladConfig, ServeConfig, get_config
from repro.config.types import CLOUD_1080TI, EDGE_TX2
from repro.core.decoupler import DecoupledPlan
from repro.core.ilp import solve_enumeration
from repro.core.latency import LatencyModel
from repro.core.planner import PlanSpace, StreamPlanTerms
from repro.core.predictor import PredictorTables
from repro.serving.scheduler import GenRequest
from repro.serving.streaming import TokenStreamSession, step_stream_group

POINT = 0        # reduced() LMs can have as few as 2 decoupling points;
                 # point 0 is the only cut guaranteed a non-empty tail.


def _plan(bits=8, codec="bitpack", point=POINT):
    return DecoupledPlan(point=point, bits=bits, predicted_latency=0.0,
                         predicted_acc_drop=0.0, solve_ms=0.0, codec=codec)


def _prompts(cfg, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in sizes]


def _session(model, params, max_batch=3, max_seq_len=48, **kw):
    return TokenStreamSession(
        model, params, ServeConfig(max_batch=max_batch,
                                   max_seq_len=max_seq_len),
        plan=kw.pop("plan", _plan()), **kw)


# ---------------------------------------------------------------------------
# The split forward itself
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen3-8b"])
def test_split_forward_bitwise_equals_unsplit(arch):
    """prefill_head -> prefill_tail and decode_head -> decode_tail (no
    wire in between) must reproduce the unsplit prefill/decode_step
    logits bit for bit at every decoupling point."""
    model, params = reduced_model(arch)
    L = 24
    batch = {"tokens": jnp.asarray(
        _prompts(model.cfg, [6])[0][None, :], jnp.int32)}
    ref_logits, ref_caches = model.prefill(params, batch, L)
    for point in range(len(model.decoupling_points())):
        boundary, head, _ = model.prefill_head(params, batch, L, point)
        logits, tail, _ = model.prefill_tail(params, boundary, L, point)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
        pos = jnp.asarray(6, jnp.int32)
        tok = jnp.asarray(ref_logits[:, -1].argmax(-1))[:, None]
        ref_step, _ = model.decode_step(params, tok, pos, ref_caches)
        b, *_ = model.decode_head(params, tok, pos, head, point, L)
        split_step, *_ = model.decode_tail(params, b, pos, tail, point,
                                           L)
        np.testing.assert_array_equal(np.asarray(split_step),
                                      np.asarray(ref_step))


# ---------------------------------------------------------------------------
# Session bit-identity and join/evict discipline
# ---------------------------------------------------------------------------


def test_batched_stream_matches_solo_sessions():
    """The acceptance property: a batched streaming session (staggered
    joins, slot reuse, ONE batched encode per step) emits exactly the
    tokens of serving each request's generation loop alone."""
    model, params = reduced_model("olmo-1b")
    sizes = [5, 9, 7, 6]
    max_new = [6, 3, 8, 4]
    arrivals = [0, 0, 2, 5]
    prompts = _prompts(model.cfg, sizes, seed=3)
    eng = _session(model, params, max_batch=2)
    for i in range(len(sizes)):
        eng.submit(GenRequest(uid=i, tokens=prompts[i],
                              max_new_tokens=max_new[i],
                              arrival=arrivals[i]))
    done = {r.uid: r for r in eng.run()}
    assert len(done) == len(sizes)
    for i in range(len(sizes)):
        solo = _session(model, params, max_batch=1)
        req = GenRequest(uid=i, tokens=prompts[i],
                         max_new_tokens=max_new[i])
        solo.submit(req)
        solo.run()
        np.testing.assert_array_equal(done[i].result, req.result)


def test_sampled_stream_matches_solo_sessions():
    """A greedy and a sampled request sharing a session each emit the
    tokens of being served alone: the sampled slot consumes one split of
    its own key per token from the session's device-resident key array,
    and the greedy slot none."""
    model, params = reduced_model("olmo-1b")
    prompts = _prompts(model.cfg, [6, 8], seed=7)
    temps = [0.0, 0.9]

    def serve(uids, max_batch, temp=None):
        eng = _session(model, params, max_batch=max_batch)
        for u in uids:
            eng.submit(GenRequest(uid=u, tokens=prompts[u], max_new_tokens=7,
                                  temperature=temps[u] if temp is None
                                  else temp))
        return {r.uid: r.result for r in eng.run()}

    batched = serve([0, 1], max_batch=2)
    for u in (0, 1):
        np.testing.assert_array_equal(batched[u],
                                      serve([u], max_batch=1)[u])
    # The sampled request did sample: served greedily it reads otherwise.
    assert not np.array_equal(batched[1],
                              serve([1], max_batch=1, temp=0.0)[1])


def test_join_lands_in_next_group_and_evicted_never_reencoded():
    model, params = reduced_model("olmo-1b")
    eng = _session(model, params, max_batch=2)
    prompts = _prompts(model.cfg, [5, 4, 6], seed=1)
    eng.submit(GenRequest(uid=0, tokens=prompts[0], max_new_tokens=8))
    eng.submit(GenRequest(uid=1, tokens=prompts[1], max_new_tokens=2))
    eng.submit(GenRequest(uid=2, tokens=prompts[2], max_new_tokens=3))
    eng.run()
    joins = {uid: step for kind, step, uid in eng.events if kind == "join"}
    evicts = {uid: step for kind, step, uid in eng.events if kind == "evict"}
    assert joins[2] > evicts[1]          # uid 2 waited for uid 1's slot
    for uid in (0, 1, 2):
        steps = [s for s, uids in eng.encode_groups if uid in uids]
        # prefill's boundary ships in _join; the first *grouped* encode
        # is the batched group of the step the request joined on — a
        # mid-stream join never triggers a solo group of its own.
        assert steps and min(steps) == joins[uid]
        # an evicted uid never reappears in a later encode group
        assert max(steps) <= evicts[uid]
    # every group is one batched encode over the then-active slots
    for step, uids in eng.encode_groups:
        assert len(uids) == len(set(uids)) <= 2


def test_evicted_slot_cache_rows_are_freed():
    model, params = reduced_model("olmo-1b")
    eng = _session(model, params, max_batch=2)
    prompts = _prompts(model.cfg, [5, 4], seed=2)
    eng.submit(GenRequest(uid=0, tokens=prompts[0], max_new_tokens=8))
    eng.submit(GenRequest(uid=1, tokens=prompts[1], max_new_tokens=2))
    while not any(r.uid == 1 for r in eng.completed):
        eng.step()
    slot1 = next(r for r in eng.completed if r.uid == 1).slot
    slot0 = 1 - slot1

    def assert_freed():
        for tree in (eng._head_caches, eng._tail_caches):
            for leaf in jax.tree.leaves(tree):
                assert not np.any(np.asarray(leaf[slot1]))      # freed
        assert any(np.any(np.asarray(leaf[slot0]))
                   for leaf in jax.tree.leaves(eng._tail_caches))

    assert_freed()
    # The donated step programs advance only the live slot: the freed
    # rows stay zero while uid 0 goes on decoding.
    for _ in range(3):
        eng.step()
        assert eng._active_slots() == [slot0]
        assert_freed()


def test_cross_session_group_matches_separate_sessions():
    """step_stream_group merges same-plan sessions into one encode/decode
    group without changing any session's tokens."""
    model, params = reduced_model("olmo-1b")
    prompts = _prompts(model.cfg, [5, 7, 6, 4], seed=5)

    def make(uids):
        s = _session(model, params, max_batch=2)
        for u in uids:
            s.submit(GenRequest(uid=u, tokens=prompts[u], max_new_tokens=4))
        return s

    grouped = [make([0, 1]), make([2, 3])]
    while any(s.queue or s.num_active for s in grouped):
        pairs = step_stream_group(grouped)
        assert len(pairs) == 2
    solo = [make([0, 1]), make([2, 3])]
    for s in solo:
        s.run()
    for sg, ss in zip(grouped, solo):
        for rg, rs in zip(sg.completed, ss.completed):
            assert rg.uid == rs.uid
            np.testing.assert_array_equal(rg.result, rs.result)
    assert step_stream_group([]) == []
    bad = make([0])
    bad.plan = _plan(bits=2)
    with pytest.raises(ValueError, match="mixes plans"):
        step_stream_group([grouped[0], bad])


# ---------------------------------------------------------------------------
# int8 cloud tail KV cache
# ---------------------------------------------------------------------------


def test_int8_tail_kv_bytes_contract():
    model, params = reduced_model("olmo-1b")
    sess = _session(model, params)
    assert sess.kv_bytes_ratio is not None
    assert sess.kv_bytes_ratio < 0.6          # bytes-halved at serving time
    assert any(jnp.dtype(a.dtype) == jnp.int8
               for a in jax.tree.leaves(sess._tail_caches))
    fp = _session(model, params, cloud_kv_bits=0)
    assert fp.kv_bytes_ratio is None
    assert not any(jnp.dtype(a.dtype) == jnp.int8
                   for a in jax.tree.leaves(fp._tail_caches))


def test_session_rejects_cloud_only_plan():
    model, params = reduced_model("olmo-1b")
    with pytest.raises(ValueError, match="cloud-only"):
        _session(model, params, plan=_plan(point=-1))


# ---------------------------------------------------------------------------
# decide_streaming: fused argmin pinned to brute force + the ILP oracle
# ---------------------------------------------------------------------------


def _random_stream_terms(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 10))
    c = int(rng.integers(1, 4))
    codecs = list(list_codecs())[: int(rng.integers(1, 4))]
    fmacs = rng.random(n) * 1e9 + 1e8
    lat = LatencyModel(fmacs, EDGE_TX2, CLOUD_1080TI, input_bytes=2048.0)
    tables = PredictorTables(
        points=[f"p{i}" for i in range(n)],
        bits_choices=[2 + i for i in range(c)],
        codecs=codecs,
        acc_drop=rng.random((n, c, len(codecs))) * 0.3,
        size_bytes=rng.random((n, c, len(codecs))) * 1e6 + 1e3,
        base_accuracy=0.9,
    )
    space = PlanSpace.build(tables, lat, float(rng.random() * 0.3))
    d_model = int(rng.integers(8, 512))
    tpb = float(rng.integers(1, 64))
    return space.with_streaming(d_model, tpb), d_model


def _scalar_stream_cost(terms, i, j, bw, expected_tokens):
    """Hand-rolled Z_stream of one cell, SAME float op order as the
    vectorized decide (float a+b is commutative bitwise)."""
    sp = terms.space
    cost = sp.size_flat[i, j] / float(bw)
    cost += sp.base[i, j]
    extra = (sp.edge_vec[i] + sp.cloud_vec[i]) / terms.tokens_per_batch
    extra = extra + terms.token_bytes[j] / float(bw)
    extra = extra * float(expected_tokens)
    cost += extra
    return cost


@given(st.integers(0, 2**31))
@settings(max_examples=60, deadline=None)
def test_decide_streaming_matches_oracles(seed):
    terms, _ = _random_stream_terms(seed)
    sp = terms.space
    rng = np.random.default_rng(seed ^ 0xABC)
    bw = float(10 ** rng.uniform(4, 8))
    e_tok = float(rng.integers(1, 512))
    plan = terms.decide(bw, e_tok)
    # brute force over every cell, bitwise
    best = np.inf
    for i in range(sp.base.shape[0]):
        for j in range(sp.base.shape[1]):
            best = min(best, _scalar_stream_cost(terms, i, j, bw, e_tok))
    if not np.isfinite(best):
        assert plan.is_cloud_only
        assert plan.predicted_latency == terms.cloud_only_stream_time(
            bw, e_tok)
        assert solve_enumeration(terms.ilp_problem(bw, e_tok)) is None
        return
    assert plan.predicted_latency == best
    sol = solve_enumeration(terms.ilp_problem(bw, e_tok))
    assert sol is not None
    assert plan.predicted_latency == sol.objective
    enum_plan = terms.plan_from_solution(sol)
    assert plan.predicted_latency == enum_plan.predicted_latency


def test_steady_state_term_shifts_the_plan():
    """Per-token wire cost must matter: token_bytes is exact per-frame
    accounting, and large E favors cheaper per-token wires."""
    terms, d_model = _random_stream_terms(12345)
    codec = get_codec(terms.space.codecs[0])
    assert terms.token_bytes[0] == codec.wire_size_bytes(
        (1, 1, d_model), terms.space.bits_choices[0]) - 1
    bw = 1e5
    t1 = terms.decide(bw, 1.0)
    t2 = terms.decide(bw, 1e6)
    if not (t1.is_cloud_only or t2.is_cloud_only):
        # huge E: the chosen cell's per-token cost can never be worse
        assert (terms.token_time(t2, bw) <= terms.token_time(t1, bw))


def test_stream_byte_accounting_matches_header_framing():
    """bytes_sent starts at the StreamHeader handshake and grows by the
    amortized stream-frame size per encode — the same accounting the
    planner's token_bytes column uses."""
    model, params = reduced_model("olmo-1b")
    sess = _session(model, params, max_batch=1)
    sess.submit(GenRequest(
        uid=0, tokens=_prompts(model.cfg, [4])[0], max_new_tokens=3))
    b0 = sess.bytes_sent
    assert b0 == sess.header.nbytes           # session-open handshake only
    sess.run()
    frame = get_codec("bitpack").wire_size_bytes(
        (1, 1, model.cfg.d_model), 8) - 1
    # prefill boundary (seq-len 4 frame) + one stream frame per decode
    # step after the prefill token
    assert sess.bytes_sent - b0 >= frame * (3 - 1)
    assert dataclasses.is_dataclass(sess.header)


# ---------------------------------------------------------------------------
# Server integration: Servable protocol and streaming plans
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lm_server():
    from repro.serving.edge_cloud import build_edge_cloud_server

    cfg = get_config("olmo-1b").reduced()
    jc = JaladConfig(bits_choices=(2, 4, 8), accuracy_drop_budget=0.5,
                     bandwidth_bytes_per_s=1e6)
    srv, params = build_edge_cloud_server(cfg, jc, calib_batches=1,
                                          calib_batch_size=2, seq_len=16)
    return srv, params


def test_serve_trace_mixes_batches_and_sessions(lm_server):
    """serve_trace takes any Servable next to plain batches — a streaming
    session advances one engine step per trace item, priced with the
    planner's per-token stage times on the shared server clock."""
    from repro.data.synthetic import make_batch
    from repro.serving.edge_cloud import Servable

    srv, params = lm_server
    cfg = srv.engine.model.cfg
    sess = TokenStreamSession(
        srv.engine.model, params, ServeConfig(max_batch=2, max_seq_len=32),
        plan=_plan())
    assert isinstance(sess, Servable)
    for i in range(2):
        sess.submit(GenRequest(uid=i,
                               tokens=_prompts(cfg, [4, 5], seed=i)[0],
                               max_new_tokens=3))
    items = [make_batch(cfg, 2, 16, seed=0), sess, sess, sess, sess]
    log = srv.serve_trace(items, [1e6] * len(items))
    assert len(log) == len(items)
    stream_bds = log[1:]
    assert all(bd.plan_point == POINT for bd in stream_bds)
    assert sum(bd.bytes_sent for bd in stream_bds) > 0
    assert all(bd.total_s >= 0.0 for bd in stream_bds)
    assert srv.clock >= sum(bd.total_s for bd in log) - 1e-9


def test_decide_streaming_on_a_real_engine(lm_server):
    """End to end on calibrated tables: decide_streaming returns a plan
    from the engine's own grid and agrees with the enumeration oracle."""
    srv, params = lm_server
    eng = srv.engine
    plan = eng.decide_streaming(2e5, expected_tokens=256.0)
    oracle = eng.decide_streaming(2e5, expected_tokens=256.0,
                                  method="enumeration")
    assert plan.predicted_latency == oracle.predicted_latency
    assert (plan.point, plan.bits, plan.codec) == (
        oracle.point, oracle.bits, oracle.codec)
    sess = srv.engine.make_runner(params, plan).stream_session(
        ServeConfig(max_batch=2, max_seq_len=32))
    assert sess.plan_key == (plan.point, plan.bits, plan.codec)


def test_stream_terms_refuse_cnn():
    from repro.serving.edge_cloud import build_edge_cloud_server

    cfg = get_config("resnet50").reduced()
    jc = JaladConfig(bits_choices=(4, 8), accuracy_drop_budget=0.5)
    srv, _ = build_edge_cloud_server(cfg, jc, calib_batches=1,
                                     calib_batch_size=2)
    with pytest.raises(ValueError, match="autoregressive"):
        srv.engine.decide_streaming(1e6)
