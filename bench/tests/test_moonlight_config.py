"""The Moonlight-16B-A3B configuration file against the program's
registered share of it, and the plain reference's weights and counts
against the program's shapes."""
from __future__ import annotations

import copy
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench.run  # noqa: F401  (puts the program's sources on the path)
from bench import spec
from bench.reference import mla_moe_decoder as ref
from bench.systems.common import MODEL_KEYS, model_config, same_layout

CELL = "moonlight16b-chat-steady"
FILE = spec.BENCH_DIR / "configs" / "moonlight-16b-a3b.json"


def _file():
    return copy.deepcopy(spec.load_json(FILE))


def _registered(cfg):
    from repro.config import get_config

    return get_config(cfg["arch_id"])


def test_file_agrees_with_the_registered_share():
    """Every key the file shares with the registered config, under the
    published name or the program's, reads the same; the file's model keys
    change nothing of it."""
    from repro.models.layers.norms import apply_norm

    cfg = _file()
    mc = _registered(cfg)
    assert cfg["arch_id"] == "moonlight-16b-a3b-ep8"
    dep = cfg["deployment"]
    published = {
        "hidden_size": mc.d_model, "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads,
        "intermediate_size": mc.d_ff, "vocab_size": mc.vocab_size,
        "kv_lora_rank": mc.kv_lora_rank,
        "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim,
        "v_head_dim": mc.v_head_dim, "rope_theta": mc.rope_theta,
        "moe_intermediate_size": mc.moe_d_ff,
        "n_routed_experts": mc.experts_held_,
        "num_experts_per_tok": mc.experts_per_token,
        "n_shared_experts": mc.n_shared_experts,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "scoring_func": mc.scoring_func,
        "tie_word_embeddings": mc.tie_embeddings,
        "first_k_dense_replace": len(mc.block_pattern)
        - len(mc.block_pattern.lstrip("d")),
        "rms_norm_eps": inspect.signature(apply_norm).parameters[
            "eps"].default,
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    assert dep["published_n_routed_experts"] == mc.num_experts == 64
    assert dep["experts_here"] == [mc.expert_lo,
                                   mc.expert_lo + mc.experts_held_]
    assert dep["expert_parallel"] * cfg["n_routed_experts"] == 64
    assert set(mc.block_pattern) == {"d", "e"}
    assert cfg["q_lora_rank"] is None and not mc.scale_embeddings
    assert cfg["n_group"] == cfg["topk_group"] == 1
    for key in MODEL_KEYS:
        if key in cfg:
            assert getattr(mc, key) == cfg[key], key
    assert model_config(cfg) == mc


def test_the_cell_is_the_chat_mix_with_32_slots():
    cell = spec.resolve(CELL)
    assert cell.chips == 1 and cell.config_name == "moonlight-16b-a3b"
    olmo = spec.resolve("olmo1b-chat-steady").traffic
    assert {k: v for k, v in cell.traffic.items() if k != "arrivals"} == \
        {k: v for k, v in olmo.items() if k != "arrivals"}
    assert cell.traffic["arrivals"]["process"] == "poisson"
    assert cell.config["plan"]["max_batch"] == 32
    names = {m.name for m in cell.per_layer}
    assert {"routes_per_expert.stream", "decode_roofline.stream",
            "launches.stream", "step_mfu.stream"} <= names


def test_make_params_has_the_program_layout():
    """At the published widths, from shapes alone."""
    from repro.models.api import build_model

    cfg = _file()
    model = build_model(model_config(cfg))
    key = jax.random.key(0)
    ours = jax.eval_shape(lambda k: ref.make_params(cfg, k), key)
    assert same_layout(ours, jax.eval_shape(model.init, key)) is None


def _tiny():
    cfg = _file()
    cfg.update(hidden_size=64, d_model=64, intermediate_size=96, d_ff=96,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, moe_intermediate_size=24,
               num_attention_heads=4, num_heads=4, num_key_value_heads=4,
               num_kv_heads=4, num_experts_per_tok=4, n_routed_experts=4,
               num_hidden_layers=5, num_layers=5, vocab_size=97)
    cfg["deployment"].update(published_n_routed_experts=16,
                             experts_here=[4, 8])
    cfg["plan"].update(point=2)
    mc = model_config(cfg).replace(
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_d_ff=24, num_experts=16, experts_per_token=4,
        expert_lo=4, experts_held=4, block_pattern="d" + "e" * 4)
    return cfg, mc


def _nbytes(tree) -> int:
    return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(tree))


def _macs(tree, cfg) -> float:
    """Multiply-accumulates a token makes with the weights of ``tree``:
    each matrix once (the embedding is a lookup, norm scales and the
    correction bias are not matrices), each routed expert by the share of
    tokens expected to choose it, k / E."""
    k = cfg["num_experts_per_tok"]
    e = cfg["deployment"]["published_n_routed_experts"]
    out = 0.0
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        names = [getattr(p, "key", None) for p in path]
        nd = len(a.shape)
        if "embed" in names or nd < 3 and "lm_head" not in names:
            continue                     # lookups, norms, bias
        n = float(np.prod(a.shape))
        routed = "mlp" in names and "shared" not in names and nd == 4
        out += n * k / e if routed else n
    return out


def test_flops_per_token_counts_the_shapes():
    from repro.models.api import build_model

    cfg, mc = _tiny()
    model = build_model(mc)
    params = jax.eval_shape(model.init, jax.random.key(0))
    m = ref._dims(cfg)
    context = 13
    attn = m["L"] * m["h"] * (m["nope"] + m["rope"] + m["vd"]) * context
    want = 2.0 * (_macs(params, cfg) + attn)
    assert ref.flops_per_token(cfg, context) == pytest.approx(want)
    # The planner prices the same per-token work: its blocks' FMACs and
    # the logits are the reference's FLOPs over 2 (no context).
    fmacs = sum(model.per_point_fmacs(1, 1)) + m["d"] * m["V"]
    assert fmacs == pytest.approx(ref.flops_per_token(cfg, 0) / 2)


@pytest.mark.parametrize("side", ["head", "tail"])
@pytest.mark.parametrize("kv_bits", [8, 16])
def test_decode_bytes_counts_the_shapes(side, kv_bits):
    """Each side's weights, the routed experts only as hit, and its cache
    rows, from the program's own trees: the head's layers up to the cut,
    the tail's the rest with the final norm and the output head (the
    embedding is a lookup)."""
    from repro.models.api import build_model

    cfg, mc = _tiny()
    cfg["plan"]["cloud_kv_bits"] = kv_bits
    model = build_model(mc)
    point = cfg["plan"]["point"]
    params = model.init(jax.random.key(0))
    head = model.head_params(params, point)
    if side == "head":
        weights = _nbytes(head["segments"])
        segs = head["segments"]
        caches = model.init_head_caches(1, 1, point)
    else:
        weights = (_nbytes(params) - _nbytes(head["segments"])
                   - _nbytes(params["embed"]))
        segs = [jax.tree.map(lambda a, b: a[b.shape[0]:], s, h)
                for s, h in zip(params["segments"], head["segments"])]
        tail_model = build_model(mc.replace(kv_cache_bits=kv_bits)
                                 if kv_bits == 8 else mc)
        caches = tail_model.init_tail_caches(1, 1, point)
    experts = sum(_nbytes({k: s["mlp"][k] for k in ("w_gate", "w_up",
                                                    "w_down")})
                  for s in segs[1:])
    one = _nbytes({k: params["segments"][1]["mlp"][k][0, 0]
                   for k in ("w_gate", "w_up", "w_down")})
    hit, rows = 5, 37
    want = weights - experts + hit * one + rows * _nbytes(caches)
    assert ref.decode_bytes(cfg, side, hit, rows) == pytest.approx(want)


def test_a_tiny_traced_run_of_the_cell_is_correct(monkeypatch):
    """The cell's served path, comparison and readers end to end at tiny
    widths on the CPU, traced, chip look skipped, in bf16 with the 8-bit
    boundary and the int8 latent tail as served: the served routing covers
    every compared position, and the comparison on it holds the program
    to the cell's limits."""
    import bench.systems.stream as stream_system
    from bench import run as bench_run
    from bench.systems import common

    cell = spec.resolve(CELL)
    c = cell.config
    c.update(hidden_size=256, d_model=256, intermediate_size=512, d_ff=512,
             num_hidden_layers=4, num_layers=4, vocab_size=2048,
             moe_intermediate_size=128, num_attention_heads=4, num_heads=4,
             num_key_value_heads=4, num_kv_heads=4, kv_lora_rank=64,
             qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32)
    c["plan"].update(point=1, max_batch=4, max_seq_len=64)
    c["check"].update(requests=4)
    cell.traffic["prompt"] = {"dist": "uniform", "min": 16, "max": 32,
                              "round_up": 16}
    cell.traffic["output"] = {"dist": "uniform", "min": 4, "max": 8}
    cell.traffic["arrivals"] = {"process": "poisson", "per_s": 8.0}

    def tiny_model_config(cfg):
        return common.model_config(cfg).replace(
            kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, moe_d_ff=128, block_pattern="d" + "e" * 3)

    monkeypatch.setattr(stream_system, "model_config", tiny_model_config)
    out = bench_run.run(cell, 2**35 + 7, 3.0, True, require_chip=False,
                        peaks=bench_run.peaks_for("TPU v5 lite"))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 3
    assert out["checks"]["route_missing"]["value"] == 0
    # At most all 6 choices of each live slot's token land on the 8 held
    # experts of a layer (6 * 8/64 of them are expected to).
    routes = out["metrics"]["routes_per_expert.stream"]["value"]
    live = out["metrics"]["live_slots.stream"]["value"]
    assert 0 < routes <= live * 6 / 8


def test_the_comparison_holds_the_served_routing(monkeypatch):
    """Two requests served in float32 with an exact boundary and tail, then
    compared: the routing the session recorded is the one its programs
    chose, at every position and on both sides, and a routing altered
    after the window is found by the comparison."""
    import bench.systems.stream as stream_system
    from bench.harness import Recorder
    from bench.systems import stream_moe
    from bench.traffic import Request

    cfg, mc = _tiny()
    cfg.update(dtype="float32", param_dtype="float32")
    cfg["plan"].update(bits=16, cloud_kv_bits=0, max_batch=2,
                       max_seq_len=32)
    cfg["check"].update(requests=2, select_calls=8)
    monkeypatch.setattr(stream_system, "model_config", lambda c: mc.replace(
        dtype="float32", param_dtype="float32"))
    system = stream_moe.build(cfg, 2**33 + 3, Recorder(annotate=False))
    system.start(False)
    for uid, (plen, n) in enumerate(((7, 5), (12, 4))):
        system.submit(Request(uid, 0.0, plen, n), uid)
    while system.busy():
        system.pump()
    system.stop()
    uids = [0, 1]
    for u in uids:
        for side, _, ids in system.routes[u]:
            assert ids.shape[-1] == 4 and side in ("head", "tail")
    system.release(uids)
    sound = system.compare(uids)
    assert sound["route_missing"] == 0
    assert sound["route_flips"] == 0 and sound["route_deficit"] == 0
    assert sound["logits_rel_l2"] < 1e-4
    # Another expert in place of each token's first choice at the last
    # expert layer.
    for u in uids:
        for side, _, ids in system.routes[u]:
            if side == "tail":
                ids[-1, :, 0] = (ids[-1, :, 0] + 1) % 16
    bad = system.compare(uids)
    assert bad["route_flips"] > 0 and bad["route_deficit"] > 0
