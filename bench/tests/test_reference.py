"""Each plain reference against the program's model at a tiny size, in
float32 on the CPU: same weights layout, same logits."""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench.run  # noqa: F401  (puts the program's sources on the path)
from bench import spec
from bench.reference import dense_decoder, resnet
from bench.systems.common import model_config, same_layout, seed_key


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _tiny_decoder(norm_kind="nonparametric", tie=True, kv=4):
    cfg = copy.deepcopy(spec.load_json(spec.BENCH_DIR / "configs"
                                       / "olmo-1b.json"))
    cfg.update(num_layers=2, d_model=128, num_heads=4, num_kv_heads=kv,
               d_ff=256, vocab_size=300, dtype="float32",
               param_dtype="float32", norm_kind=norm_kind,
               tie_embeddings=tie)
    return cfg


@pytest.mark.parametrize("norm_kind,tie,kv", [
    ("nonparametric", True, 4),      # olmo-1b's block
    ("layernorm", False, 1),         # granite-34b's block (MQA)
])
def test_dense_decoder_matches_the_program(norm_kind, tie, kv):
    from repro.models.api import build_model

    cfg = _tiny_decoder(norm_kind, tie, kv)
    model = build_model(model_config(cfg))
    key = seed_key(2**40 + 3)
    params = dense_decoder.make_params(cfg, key)
    assert same_layout(params, jax.eval_shape(model.init, key)) is None
    toks = np.random.default_rng(0).integers(0, 300, 24).astype(np.int32)
    want = model.forward(params, {"tokens": jnp.asarray(toks[None])})[0]
    got = dense_decoder.forward(cfg, params, jnp.asarray(toks))
    assert _rel(got, want) < 1e-4


def test_dense_prefill_flops_sum_the_tokens():
    cfg = _tiny_decoder()
    n = 17
    want = sum(dense_decoder.flops_per_token(cfg, c) for c in range(1, n + 1))
    assert dense_decoder.prefill_flops(cfg, n) == pytest.approx(want)


def _tiny_resnet():
    cfg = copy.deepcopy(spec.load_json(spec.BENCH_DIR / "configs"
                                       / "resnet50.json"))
    cfg.update(image_size=32, num_classes=10)
    return cfg


def test_resnet_matches_the_program():
    from repro.models.api import build_model

    cfg = _tiny_resnet()
    model = build_model(model_config(cfg))
    key = seed_key(7)
    params = resnet.make_params(cfg, key)
    assert same_layout(params, jax.eval_shape(model.init, key)) is None
    x = resnet.make_images(cfg, seed_key(7, 1), 2)
    want = model.forward(params, {"images": x})
    got = resnet.forward(cfg, params, x)
    assert _rel(got, want) < 1e-4
    # The two halves at the cut: the head, and the tail on the wire's
    # values (the oracle's codes of the program's head).
    from bench import oracle

    head = model.run_head(params, {"images": x[:1]}, 4)
    assert _rel(resnet.forward(cfg, params, x[:1], stop=5), head) < 1e-5
    q, mn, mx = oracle.codes(head, 8)
    deq = oracle.dequantize(q, mn, mx, 8).reshape(head.shape)
    want = model.run_tail(params, jnp.asarray(deq), 4)
    got = resnet.forward(cfg, params, jnp.asarray(deq), start=5)
    assert _rel(got, want) < 1e-5


def test_resnet_flops_match_the_program():
    from repro.models.api import build_model

    cfg = _tiny_resnet()
    model = build_model(model_config(cfg))
    assert resnet.image_flops(cfg) == pytest.approx(model.model_flops(1))
