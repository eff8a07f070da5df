"""Moonlight-16B-A3B — DeepSeek-V3 block: multi-head latent attention,
64 routed experts (top 6, sigmoid scores with a correction bias) and 2
shared experts, after one dense layer
[hf:moonshotai/Moonlight-16B-A3B, config.json; model_type deepseek_v3].

``moonlight-16b-a3b`` holds all 64 experts of each expert layer.
``moonlight-16b-a3b-ep8`` is one chip's share of the same model with each
expert layer spread over eight chips (expert parallelism): experts 0-7 of
64 held, the router still scoring all 64; attention, the shared experts,
the dense layer and the vocabulary whole on the chip.

Published settings the model does not need a field for: ``q_lora_rank``
null (queries projected directly), ``n_group`` = ``topk_group`` = 1
(group-limited routing picks from all experts), ``norm_topk_prob`` true,
no rope scaling, RMSNorm eps 1e-5, context 8192.
"""
from repro.config.registry import register
from repro.config.types import ModelConfig

CONFIG = register(
    ModelConfig(
        arch_id="moonlight-16b-a3b",
        family="moe",
        source="hf:moonshotai/Moonlight-16B-A3B",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=11264,
        vocab_size=163840,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=50000.0,
        norm_kind="rmsnorm",
        tie_embeddings=False,
        scale_embeddings=False,
        num_experts=64,
        experts_per_token=6,
        moe_d_ff=1408,
        scoring_func="sigmoid",
        routed_scaling_factor=2.446,
        n_shared_experts=2,
        block_pattern="d" + "e" * 26,
    )
)

EP8 = register(CONFIG.replace(arch_id="moonlight-16b-a3b-ep8",
                              expert_lo=0, experts_held=8))
