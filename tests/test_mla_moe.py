"""The DeepSeek-V3 block (Moonlight-16B-A3B) at a tiny size on the CPU, on
seeded random weights, against the benchmark's plain float32 reference:
latent attention (absorbed decode against the non-absorbed form), the
dropless share-aware expert layer (shares sum to the uncut layer, nothing
dropped), the head/tail split at every cut, and a ``TokenStreamSession``
serving it with an int8 latent tail."""
import copy
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))      # the benchmark's reference

from bench.reference import mla_moe_decoder as ref  # noqa: E402
from bench.systems.common import model_config, same_layout, seed_key  # noqa: E402
from repro.config import ServeConfig  # noqa: E402
from repro.core.decoupler import DecoupledPlan  # noqa: E402
from repro.models.api import build_model  # noqa: E402
from repro.models.layers import mla as mla_lib  # noqa: E402
from repro.models.layers import moe as moe_lib  # noqa: E402
from repro.models.layers.mlp import apply_swiglu  # noqa: E402
from repro.serving.scheduler import GenRequest  # noqa: E402
from repro.serving.streaming import TokenStreamSession  # noqa: E402

CONFIG = ROOT / "bench" / "configs" / "moonlight-16b-a3b.json"


def tiny(layers=4, experts=16, held=(0, 16), k=4, dtype="float32"):
    """A configuration file of the benchmark's form at a tiny size, and the
    program's ModelConfig for it."""
    cfg = copy.deepcopy(json.loads(CONFIG.read_text()))
    lo, n = held
    cfg.update(hidden_size=64, d_model=64, intermediate_size=96, d_ff=96,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, moe_intermediate_size=24,
               num_attention_heads=4, num_heads=4, num_key_value_heads=4,
               num_kv_heads=4, num_experts_per_tok=k, n_routed_experts=n,
               num_hidden_layers=layers, num_layers=layers, vocab_size=97,
               dtype=dtype, param_dtype=dtype)
    cfg["deployment"].update(published_n_routed_experts=experts,
                             experts_here=[lo, n])
    mc = model_config(cfg).replace(
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_d_ff=24, num_experts=experts,
        experts_per_token=k, expert_lo=lo, experts_held=n,
        block_pattern="d" + "e" * (layers - 1))
    return cfg, mc


def _weights(cfg, mc, seed=2**33 + 5):
    model = build_model(mc)
    key = seed_key(seed)
    params = ref.make_params(cfg, key)
    assert same_layout(params, jax.eval_shape(model.init, key)) is None
    return model, params


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _tokens(n, vocab=97, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def test_prefill_then_decode_matches_the_reference():
    cfg, mc = tiny(held=(4, 8))
    model, params = _weights(cfg, mc)
    toks = _tokens(12)
    want = np.asarray(ref.forward(cfg, params, jnp.asarray(toks)))
    n0 = 7
    logits, caches = model.prefill(params, {"tokens": jnp.asarray(
        toks[None, :n0])}, 16)
    assert _rel(logits[0], want[:n0]) < 1e-4
    step = jax.jit(model.decode_step)
    for t in range(n0, len(toks)):
        logits, caches = step(
            params, jnp.asarray(toks[None, t:t + 1]), jnp.int32(t), caches)
        assert _rel(logits[0, 0], want[t]) < 1e-4, t


@pytest.mark.parametrize("point", [0, 1, 2, 3])
def test_split_decode_bitwise_equals_unsplit(point):
    """Decode through the caches, split at ``point`` (0: right after the
    dense layer; 3: the last layer, an empty tail) against the unsplit
    decode: the logits bit for bit, and the routes of the two sides
    together those of the whole model in the head. The split prefill
    agrees within float32 rounding (the grouped expert matmuls are not
    bitwise across scan lengths on the CPU)."""
    cfg, mc = tiny(held=(0, 8))
    model, params = _weights(cfg, mc)
    L = 16
    toks = _tokens(6)
    last = 3
    full = model.init_caches(1, L)
    whole = model.init_head_caches(1, L, last)
    head = model.init_head_caches(1, L, point)
    tail = model.init_tail_caches(1, L, point)
    step = jax.jit(model.decode_step)
    decode_whole = jax.jit(lambda p, t, i, c: model.decode_head(
        p, t, i, c, last, L))
    decode_head = jax.jit(lambda p, t, i, c: model.decode_head(
        p, t, i, c, point, L))
    decode_tail = jax.jit(lambda p, x, i, c: model.decode_tail(
        p, x, i, c, point, L))
    for t in range(len(toks)):
        tok, pos = jnp.asarray(toks[None, t:t + 1]), jnp.int32(t)
        want, full = step(params, tok, pos, full)
        _, whole, routes = decode_whole(params, tok, pos, whole)
        b, head, r_head = decode_head(params, tok, pos, head)
        got, tail, r_tail = decode_tail(params, b, pos, tail)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        split = [r for r in (r_head, r_tail) if r is not None]
        assert routes.shape == (last, 1, 1, mc.experts_per_token)
        np.testing.assert_array_equal(np.concatenate(split),
                                      np.asarray(routes))
    batch = {"tokens": jnp.asarray(toks[None])}
    want, _ = model.prefill(params, batch, L)
    boundary, _, _ = model.prefill_head(params, batch, L, point)
    got, _, _ = model.prefill_tail(params, boundary, L, point)
    assert _rel(got, want) < 1e-6


def _layer(cfg, params):
    """The first expert layer's weights, unstacked."""
    return jax.tree.map(lambda a: a[0], params["segments"][1]["mlp"])


def test_expert_shares_sum_to_the_uncut_layer():
    """Four shares of a 16-expert layer: the parts the shares compute,
    each without the shared experts, plus the shared experts once, add up
    to the uncut layer, by the program and by the reference."""
    cfg, mc = tiny(experts=16, held=(0, 16))
    _, params = _weights(cfg, mc)
    whole = _layer(cfg, params)
    x = jax.random.normal(jax.random.key(1), (1, 40, 64), jnp.float32)
    want = moe_lib.apply_moe(whole, x, mc)[0]
    shared = apply_swiglu(whole["shared"], x)
    with jax.default_matmul_precision("highest"):
        ref_whole = ref.moe(cfg, x[0], whole)[0]
        ref_shared = ref._swiglu(x[0], whole["shared"], ref._identity_cast)
    parts, ref_parts = [], []
    for lo in range(0, 16, 4):
        share = dict(whole, **{k: whole[k][lo:lo + 4]
                               for k in ("w_gate", "w_up", "w_down")})
        c = mc.replace(expert_lo=lo, experts_held=4)
        part = moe_lib.apply_moe(share, x, c)[0]
        parts.append(part - shared)
        dec, ids = moe_lib.apply_moe_decode(share, x.reshape(40, 1, 64), c)
        np.testing.assert_allclose(np.asarray(dec).reshape(1, 40, 64),
                                   np.asarray(part), rtol=1e-5, atol=1e-5)
        rc, _ = tiny(experts=16, held=(lo, 4))
        with jax.default_matmul_precision("highest"):
            ref_parts.append(ref.moe(rc, x[0], share)[0] - ref_shared)
            g = ref.gates(rc, x[0], share)[0]
        local = np.asarray(ids).ravel() - lo
        counts = np.bincount(local[(local >= 0) & (local < 4)], minlength=4)
        np.testing.assert_array_equal(counts, np.asarray((g > 0).sum(0)))
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    assert _rel(sum(ref_parts) + ref_shared, ref_whole) < 1e-6
    assert _rel(want[0], ref_whole) < 1e-5


@pytest.mark.parametrize("tokens", [1, 64, 257])
def test_every_token_to_one_expert_drops_none(tokens):
    """A bias that sends every token to the same experts: each one is
    computed, at every length, as the reference computes it."""
    cfg, mc = tiny(experts=16, held=(0, 8))
    _, params = _weights(cfg, mc)
    layer = _layer(cfg, params)
    layer = dict(layer, router_bias=layer["router_bias"].at[3].set(100.0))
    x = jax.random.normal(jax.random.key(2), (1, tokens, 64), jnp.float32)
    got = moe_lib.apply_moe(layer, x, mc)[0]
    with jax.default_matmul_precision("highest"):
        want = ref.moe(cfg, x[0], layer)[0]
        g = ref.gates(cfg, x[0], layer)[0]
    assert bool((g[:, 3] > 0).all())          # every token chose expert 3
    assert _rel(got[0], want) < 1e-5


@pytest.mark.parametrize("kv_bits", [16, 8])
def test_absorbed_decode_equals_the_non_absorbed_form(kv_bits):
    """The absorbed decode over the latent cache against each head's keys
    and values built from the latent; the int8 latent within its rounding."""
    cfg, mc = tiny()
    _, params = _weights(cfg, mc)
    c = mc.replace(kv_cache_bits=kv_bits)
    attn = jax.tree.map(lambda a: a[0], params["segments"][1]["attn"])
    s = 9
    h = jax.random.normal(jax.random.key(3), (1, s, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (1, s))
    want = mla_lib.attend_seq(attn, *mla_lib.project(attn, h, pos, c), c)
    cache = mla_lib.init_cache(c, 1, 16, jnp.float32)
    decode = jax.jit(mla_lib.decode, static_argnums=4)
    for t in range(s):
        out, cache = decode(attn, h[:, t:t + 1], cache, jnp.int32(t), c)
        tol = 1e-5 if kv_bits == 16 else 2e-2
        assert _rel(out[0, 0], want[0, t]) < tol, t


def test_stream_session_serves_each_request_as_alone():
    """In bf16, as the model is served: the batched session's tokens are
    those of each request alone, and the int8 latent tail holds at most
    0.6 of the bf16 tail's bytes."""
    cfg, mc = tiny(held=(0, 8), dtype="bfloat16")
    model, params = _weights(cfg, mc)
    plan = DecoupledPlan(point=1, bits=8, predicted_latency=0.0,
                         predicted_acc_drop=0.0, solve_ms=0.0,
                         codec="bitpack")

    def session(n):
        return TokenStreamSession(model, params,
                                  ServeConfig(max_batch=n, max_seq_len=32),
                                  plan=plan)

    prompts = [_tokens(n, seed=i) for i, n in enumerate([5, 9, 3])]
    lens = [6, 3, 8]
    sess = session(3)
    assert sess.kv_bytes_ratio is not None and sess.kv_bytes_ratio <= 0.6
    for i, (p, n) in enumerate(zip(prompts, lens)):
        sess.submit(GenRequest(uid=i, tokens=p, max_new_tokens=n))
    batched = {r.uid: r.out_tokens for r in sess.run()}
    alone = session(1)             # one slot: each request served alone
    for i, (p, n) in enumerate(zip(prompts, lens)):
        alone.submit(GenRequest(uid=i, tokens=p, max_new_tokens=n))
    assert {r.uid: r.out_tokens for r in alone.run()} == batched
    assert [len(batched[i]) for i in range(3)] == lens


def test_routing_counts_ride_in_the_step_fetch(monkeypatch, tmp_path):
    """Each side's program returns the served routing with its outputs;
    it comes back in the select's one fetch (no further sync, no further
    launch) and its counts become the stats of one ``moe.route`` span a
    side."""
    from test_program_spans import Watch, read_spans
    from test_stream_launches import CEILING, launches_by_step

    cfg, mc = tiny(held=(0, 16))   # all held: k routed pairs a layer
    model, params = _weights(cfg, mc)
    plan = DecoupledPlan(point=1, bits=8, predicted_latency=0.0,
                         predicted_acc_drop=0.0, solve_ms=0.0,
                         codec="bitpack")
    sess = TokenStreamSession(model, params,
                              ServeConfig(max_batch=2, max_seq_len=32),
                              plan=plan)
    for uid, n in ((1, 5), (2, 7)):
        sess.submit(GenRequest(uid=uid, tokens=_tokens(n, seed=uid),
                               max_new_tokens=6))
        sess.step()                  # compiles every shape of the trace
    watch = Watch(monkeypatch)
    watch.run(tmp_path, sess.step)
    watch.assert_fetches_synced()
    spans = read_spans(tmp_path)
    syncs = [p for name, _, p in spans if name == "sync"]
    assert syncs == ["codec.encode", "stream.select"]
    routes = {st["side"]: (st, parent) for name, st, parent in spans
              if name == "moe.route"}
    assert sorted(routes) == ["head", "tail"]
    k = mc.experts_per_token
    # The head holds the dense layer and one expert layer, the tail two.
    # Each step decodes after its joins, so the slots have served 3 and 2
    # tokens, and read their prompts and those.
    rows = (5 + 3) + (7 + 2)
    for side, layers in (("head", 1), ("tail", 2)):
        st, parent = routes[side]
        assert parent == "sync"
        assert st["routes"] == 2 * k * layers
        assert k <= st["experts_hit"] <= 2 * k * layers
        assert st["rows"] == rows
    (slots, count), = launches_by_step(tmp_path)
    assert slots == 2
    for phase, ceiling in CEILING.items():
        assert 1 <= count[phase] <= ceiling, (phase, count)
