"""Per-architecture smoke tests (assignment requirement): a REDUCED variant
of each assigned architecture's family runs one forward and one train step
on CPU, asserting output shapes and finite values."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_model
from repro.config import TrainConfig, get_config, list_archs
from repro.config.registry import assigned_archs
from repro.data.synthetic import make_batch
from repro.optim import adamw
from repro.training.loop import make_train_step

ARCHS = assigned_archs()


def _batch(model, n=2, s=24, seed=0):
    return {
        k: jnp.asarray(v)
        for k, v in make_batch(model.cfg, n, s, seed=seed).items()
    }


def test_all_ten_assigned_archs_registered():
    expected = {
        "yi-6b", "llama4-maverick-400b-a17b", "xlstm-1.3b", "qwen2-vl-7b",
        "granite-34b", "seamless-m4t-large-v2", "zamba2-2.7b", "olmo-1b",
        "qwen3-8b", "grok-1-314b",
        # Drawn later: the published model and its one-chip expert share.
        "moonlight-16b-a3b", "moonlight-16b-a3b-ep8",
    }
    assert set(ARCHS) == expected


def test_exact_assigned_dimensions():
    """The full configs must match the assignment sheet exactly."""
    spec = {
        "yi-6b": (32, 4096, 32, 4, 11008, 64000),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
        "granite-34b": (88, 6144, 48, 1, 24576, 49152),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
        "moonlight-16b-a3b-ep8": (27, 2048, 16, 16, 11264, 163840),
    }
    for arch, (L, d, h, kv, ff, v) in spec.items():
        c = get_config(arch)
        assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
                c.d_ff, c.vocab_size) == (L, d, h, kv, ff, v), arch
    assert get_config("llama4-maverick-400b-a17b").num_experts == 128
    assert get_config("llama4-maverick-400b-a17b").experts_per_token == 1
    assert get_config("grok-1-314b").num_experts == 8
    assert get_config("grok-1-314b").experts_per_token == 2
    assert get_config("zamba2-2.7b").ssm_state_dim == 64
    assert get_config("qwen3-8b").qk_norm
    assert get_config("olmo-1b").norm_kind == "nonparametric"
    for arch in ("moonlight-16b-a3b", "moonlight-16b-a3b-ep8"):
        c = get_config(arch)
        assert (c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
                c.v_head_dim) == (512, 128, 64, 128)
        assert (c.num_experts, c.experts_per_token, c.moe_d_ff,
                c.n_shared_experts) == (64, 6, 1408, 2)
        assert (c.scoring_func, c.routed_scaling_factor) == ("sigmoid",
                                                             2.446)
        assert c.block_pattern == "d" + "e" * 26
        assert c.rope_theta == 50000.0 and not c.scale_embeddings
    assert get_config("moonlight-16b-a3b").experts_held_ == 64
    ep8 = get_config("moonlight-16b-a3b-ep8")
    assert (ep8.expert_lo, ep8.experts_held_) == (0, 8)


def test_reduced_meets_smoke_budget():
    for arch in ARCHS:
        r = get_config(arch).reduced()
        assert r.d_model <= 512
        assert (r.num_layers or len(r.block_pattern)) <= 4
        assert r.num_experts <= 4


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b",
                                  "moonlight-16b-a3b-ep8"])
def test_reduced_keeps_latent_attention_and_the_expert_share(arch):
    full, r = get_config(arch), get_config(arch).reduced()
    assert r.is_mla and r.n_shared_experts == full.n_shared_experts
    assert r.scoring_func == "sigmoid" and not r.scale_embeddings
    assert 0 < r.experts_per_token <= r.num_experts
    assert 0 < r.experts_held_ <= r.num_experts
    assert r.expert_lo + r.experts_held_ <= r.num_experts
    if full.experts_held:
        assert r.experts_held_ < r.num_experts      # a share stays a share


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    model, params = reduced_model(arch)
    batch = _batch(model)
    logits = model.forward(params, batch)
    b = batch["tokens"].shape[0]
    s_text = batch["tokens"].shape[1]
    s_total = s_text + (
        batch["vision_embeds"].shape[1] if "vision_embeds" in batch else 0
    )
    assert logits.shape == (b, s_total, model.cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch):
    model, params = reduced_model(arch)
    batch = _batch(model, seed=1)
    tc = TrainConfig(learning_rate=1e-3, total_steps=10)
    step = make_train_step(model, tc)
    opt = adamw.init_state(params)
    new_params, new_opt, metrics = jax.jit(step)(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # parameters actually moved
    moved = any(
        float(jnp.max(jnp.abs(a - b))) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params))
    )
    assert moved
    assert int(new_opt.step) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_and_cache(arch):
    model, params = reduced_model(arch)
    caches = model.init_caches(2, 16)
    logits, caches2 = model.decode_step(
        params, jnp.ones((2, 1), jnp.int32), jnp.int32(0), caches
    )
    assert logits.shape == (2, 1, model.cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    # cache structure preserved
    assert jax.tree.structure(caches) == jax.tree.structure(caches2)


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen3-8b", "zamba2-2.7b",
                                  "xlstm-1.3b", "moonlight-16b-a3b-ep8",
                                  "grok-1-314b"])
def test_prefill_then_decode_matches_forward(arch):
    """Teacher-forced decode over the same tokens must reproduce the
    full-sequence forward logits (KV cache / state correctness)."""
    model, params = reduced_model(arch)
    cfg = model.cfg
    b, s = 2, 8
    toks = jax.random.randint(jax.random.key(3), (b, s), 0, cfg.vocab_size)
    full = np.asarray(model.forward(params, {"tokens": toks}))

    caches = model.init_caches(b, s)
    outs = []
    for t in range(s):
        logits, caches = model.decode_step(
            params, toks[:, t: t + 1], jnp.int32(t), caches
        )
        outs.append(np.asarray(logits[:, 0]))
    got = np.stack(outs, axis=1)
    np.testing.assert_allclose(got, full, rtol=3e-3, atol=3e-3)
