"""Plain float32 reference of the dense decoder (olmo-1b, granite-34b),
and the benchmark's own weights for it.

Written from the published description as the program runs it, in
``jax.numpy`` at ``highest`` matmul precision, with no cache, batching or
kernels. It reads the weights by their keys; it imports nothing of the
program. One pre-norm block, repeated ``num_layers`` times:

    x = embed[tokens] * sqrt(d_model)
    h = norm(x);  x += attn(h) @ wo      (RoPE on q, k; causal softmax)
    h = norm(x);  x += (silu(h @ w_gate) * (h @ w_up)) @ w_down
    logits = norm(x) @ embed.T  (tied)   or  norm(x) @ lm_head

``norm`` is LayerNorm over the last axis with eps 1e-5: without scale
and bias for ``nonparametric`` (OLMo), with them for ``layernorm``.
Departure from OLMo's paper, kept because the program does it: the
embedding is scaled by sqrt(d_model).

The weight tree has the program's layout: one stacked segment of
``num_layers`` blocks, ``embed`` (vocab, d), ``final_norm``.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5
EMBED_STD = 0.02


def _norm_params(cfg: Dict[str, Any], lead=()) -> Dict[str, Any]:
    if cfg["norm_kind"] == "nonparametric":
        return {}
    d = cfg["d_model"]
    return {"scale": ("ones", lead + (d,)), "bias": ("zeros", lead + (d,))}


def param_layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """(init, shape, fan-in) of every weight, in the program's tree."""
    L, d, h = cfg["num_layers"], cfg["d_model"], cfg["num_heads"]
    kv, f, v = cfg["num_kv_heads"], cfg["d_ff"], cfg["vocab_size"]
    hd = cfg.get("head_dim") or d // h
    layer = {
        "ln1": _norm_params(cfg, (L,)),
        "attn": {"wq": ("normal", (L, d, h, hd), d),
                 "wk": ("normal", (L, d, kv, hd), d),
                 "wv": ("normal", (L, d, kv, hd), d),
                 "wo": ("normal", (L, h, hd, d), h * hd)},
        "ln2": _norm_params(cfg, (L,)),
        "mlp": {"w_gate": ("normal", (L, d, f), d),
                "w_up": ("normal", (L, d, f), d),
                "w_down": ("normal", (L, f, d), f)},
    }
    out = {"embed": ("embed", (v, d)), "final_norm": _norm_params(cfg),
           "segments": [layer]}
    if not cfg["tie_embeddings"]:
        out["lm_head"] = ("normal", (d, v), d)
    return out


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and isinstance(x[0], str)


def make_params(cfg: Dict[str, Any], key, dtype=None):
    """Random weights from ``key`` in one jitted call, on the device, in
    the served dtype: normal / sqrt(fan-in) matrices, embeddings of std
    0.02, unit norm scales and zero norm biases."""
    layout = param_layout(cfg)
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_leaf)
    dt = jnp.dtype(dtype or cfg["param_dtype"])

    def build(k):
        keys = jax.random.split(k, len(leaves))
        vals = []
        for kk, (init, shape, *fan) in zip(keys, leaves):
            if init == "ones":
                vals.append(jnp.ones(shape, dt))
            elif init == "zeros":
                vals.append(jnp.zeros(shape, dt))
            else:
                std = EMBED_STD if init == "embed" else 1.0 / math.sqrt(fan[0])
                vals.append((jax.random.normal(kk, shape, jnp.float32)
                             * std).astype(dt))
        return jax.tree.unflatten(treedef, vals)

    return jax.jit(build)(key)


def _layer_norm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + EPS)
    if p:
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y


def _rope(x, theta: float):
    """x (S, H, K): rotate the two halves of each head by position."""
    s, _, k = x.shape
    half = k // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang))[:, None, :]
    sin = jnp.asarray(np.sin(ang))[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(cfg, x, p, cast):
    f32 = jnp.float32
    s = x.shape[0]
    h = _layer_norm(x, p["ln1"])
    a = p["attn"]
    q = _rope(jnp.einsum("sd,dhk->shk", h, cast(a["wq"])), cfg["rope_theta"])
    k = _rope(jnp.einsum("sd,dhk->shk", h, cast(a["wk"])), cfg["rope_theta"])
    v = jnp.einsum("sd,dhk->shk", h, cast(a["wv"]))
    heads, kvh, hd = q.shape[1], k.shape[1], q.shape[2]
    group = heads // kvh
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(f32), axis=-1)
    out = jnp.einsum("hqs,shk->qhk", probs, v)
    x = x + jnp.einsum("qhk,hkd->qd", out, cast(a["wo"]))
    h = _layer_norm(x, p["ln2"])
    m = p["mlp"]
    g = jax.nn.silu(h @ cast(m["w_gate"]))
    x = x + (g * (h @ cast(m["w_up"]))) @ cast(m["w_down"])
    return x


def _identity_cast(w):
    return w.astype(jnp.float32)


def forward(cfg: Dict[str, Any], params, tokens, cast=_identity_cast):
    """Logits (S, vocab) in float32 for one sequence ``tokens`` (S,).
    ``cast`` maps each matrix to the float32 the reference computes
    with; a control passes one that rounds it to a lower precision."""
    with jax.default_matmul_precision("highest"):
        x = cast(params["embed"])[tokens] * math.sqrt(cfg["d_model"])
        seg = params["segments"][0]

        def body(x, p):
            return _block(cfg, x, p, cast), None

        x, _ = jax.lax.scan(body, x, seg)
        x = _layer_norm(x, params["final_norm"])
        if cfg["tie_embeddings"]:
            return x @ cast(params["embed"]).T
        return x @ cast(params["lm_head"])


def flops_per_token(cfg: Dict[str, Any], context: int) -> float:
    """Model FLOPs of one token whose attention reads ``context``
    positions: 2 per multiply-accumulate of every matrix, the attention
    scores and values, and the logits."""
    d, h, kv, f = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                   cfg["d_ff"])
    hd = cfg.get("head_dim") or d // h
    per_layer = (d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
                 + 2 * h * hd * context)
    return 2.0 * (cfg["num_layers"] * per_layer + d * cfg["vocab_size"])


def prefill_flops(cfg: Dict[str, Any], n: int) -> float:
    """Model FLOPs of a causal prefill of ``n`` tokens: token i reads
    i + 1 positions."""
    h = cfg["num_heads"]
    hd = cfg.get("head_dim") or cfg["d_model"] // h
    attn = 2.0 * cfg["num_layers"] * 2 * h * hd * (n * (n + 1) / 2)
    return n * flops_per_token(cfg, 0) + attn
