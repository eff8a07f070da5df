"""Mixture-of-Experts layer: dropless, and aware of the expert share it
holds.

The router scores every one of the ``num_experts`` experts and picks the
top ``experts_per_token`` of each token:

* ``softmax``: the top-k of the softmax scores;
* ``sigmoid`` (DeepSeek-V3's ``noaux_tc``): the top-k of the sigmoid
  scores plus a correction bias; the bias only picks, the weights are the
  scores without it.

The k weights are normalised over their sum and scaled by
``routed_scaling_factor``. The layer holds experts ``[expert_lo,
expert_lo + experts_held)`` (expert parallelism: one chip's share of a
layer spread over several) and computes only its own experts' part of
the result for the tokens routed to them; the part the other experts
would add is left out. Shared experts (one SwiGLU of width
``n_shared_experts * moe_d_ff``) see every token and are added once.

No token is dropped at any length: there is no capacity. Two forms:

* a whole sequence (prefill, forward) computes only the routed
  (token, held-expert) pairs: they are sorted by expert and run through
  ``jax.lax.ragged_dot``, one group an expert, so the work is the routed
  pairs padded to the grouped matmul's tiles;
* one decode token per slot computes every held expert for it and weighs
  the routed ones in: under the session's vmap over slots this reads each
  held expert's weights once a step for all slots, with no per-slot copy
  and no padding. Its FLOPs are ``experts_held / experts_per_token`` times
  the routed ones (moonlight's share 8/6; grok-1 8/2; llama4-maverick,
  held whole, 128/1), which a decode step bound by reading the weights
  does not feel while the slots' routes reach most held experts.

Both forms return each token's chosen expert ids (over all
``num_experts``), the served routing.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.config.types import ModelConfig
from repro.models.init import spec
from repro.models.layers.mlp import apply_swiglu, swiglu_spec

_F32 = jnp.float32


def moe_spec(cfg: ModelConfig):
    d, e, f = cfg.d_model, cfg.experts_held_, cfg.moe_d_ff_
    p = {
        "router": spec((d, cfg.num_experts), ("embed", "expert_in"),
                       "float32", scale=0.1),
        "w_gate": spec((e, d, f), ("expert", "embed", "ffn"), cfg.param_dtype),
        "w_up": spec((e, d, f), ("expert", "embed", "ffn"), cfg.param_dtype),
        "w_down": spec((e, f, d), ("expert", "ffn", "embed"), cfg.param_dtype),
    }
    if cfg.scoring_func == "sigmoid":
        p["router_bias"] = spec((cfg.num_experts,), ("expert_in",),
                                "float32", init="zeros")
    if cfg.n_shared_experts:
        p["shared"] = swiglu_spec(d, cfg.n_shared_experts * f,
                                  cfg.param_dtype)
    return p


def route(params, x: jnp.ndarray, cfg: ModelConfig):
    """Router over all experts for tokens ``x`` (N, d). Returns the k
    weights (N, k) float32, the chosen expert ids (N, k) and the scores
    (N, E) float32."""
    logits = jnp.einsum("nd,de->ne", x.astype(_F32), params["router"])
    k = cfg.experts_per_token
    if cfg.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + params["router_bias"], k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(scores, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w * cfg.routed_scaling_factor, ids, scores


def held_ids(ids: jnp.ndarray, cfg: ModelConfig):
    """Each chosen expert's index in the held share, or ``experts_held``
    where another chip holds it; and whether it is held."""
    local = ids - cfg.expert_lo
    held = (local >= 0) & (local < cfg.experts_held_)
    return jnp.where(held, local, cfg.experts_held_), held


def _aux_loss(ids, scores, cfg: ModelConfig) -> jnp.ndarray:
    """Switch-style load-balance loss of softmax routing, per batch row
    and averaged over the rows, from ids (B, S, k) and scores (B, S, E);
    sigmoid routing balances through its bias and has none."""
    if cfg.scoring_func == "sigmoid":
        return jnp.zeros((), _F32)
    e = cfg.num_experts
    frac_tokens = jax.nn.one_hot(ids, e, dtype=_F32).sum(2).mean(1) \
        / cfg.experts_per_token                                   # (B, E)
    return e * jnp.mean(jnp.sum(frac_tokens * scores.mean(1), axis=-1))


def _with_shared(params, y, x):
    return y + apply_swiglu(params["shared"], x) if "shared" in params else y


def apply_moe(params, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A whole sequence ``x`` (B, S, d): the held share's part for every
    routed (token, held expert) pair, plus the shared experts. Returns
    (output (B, S, d), aux loss, chosen ids (B, S, k) int32)."""
    b, s, d = x.shape
    k, e = cfg.experts_per_token, cfg.experts_held_
    xf = x.reshape(b * s, d)
    w, ids, scores = route(params, xf, cfg)
    local, held = held_ids(ids, cfg)
    flat = local.reshape(-1)                          # (N k,)
    order = jnp.argsort(flat, stable=True)            # held pairs first
    sizes = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
    xs = xf[order // k]
    g = jax.lax.ragged_dot(xs, params["w_gate"], sizes)
    u = jax.lax.ragged_dot(xs, params["w_up"], sizes)
    h = jax.nn.silu(g.astype(_F32)).astype(x.dtype) * u
    ys = jax.lax.ragged_dot(h, params["w_down"], sizes)
    # Back to (token, choice); rows past the held groups are not computed.
    ys = ys[jnp.argsort(order)].reshape(b * s, k, d)
    ys = jnp.where(held[..., None], ys.astype(_F32) * w[..., None], 0.0)
    y = ys.sum(1).astype(x.dtype).reshape(b, s, d)
    ids = ids.reshape(b, s, k)
    aux = _aux_loss(ids, scores.reshape(b, s, -1), cfg)
    return _with_shared(params, y, x), aux, ids


def apply_moe_decode(params, x: jnp.ndarray, cfg: ModelConfig
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Decode tokens ``x`` (B, 1, d): every held expert computed for each
    token and the routed ones weighed in, plus the shared experts. Returns
    (output (B, 1, d), chosen ids (B, 1, k) int32)."""
    b, s, d = x.shape
    e = cfg.experts_held_
    xf = x.reshape(b * s, d)
    w, ids, _ = route(params, xf, cfg)
    local, _ = held_ids(ids, cfg)
    hit = local[..., None] == jnp.arange(e)           # (N, k, e)
    gates = jnp.einsum("nk,nke->ne", w, hit.astype(_F32))
    g = jnp.einsum("nd,edf->enf", xf, params["w_gate"])
    u = jnp.einsum("nd,edf->enf", xf, params["w_up"])
    h = jax.nn.silu(g.astype(_F32)).astype(x.dtype) * u
    ye = jnp.einsum("enf,efd->end", h, params["w_down"])
    y = jnp.einsum("ne,end->nd", gates, ye.astype(_F32)).astype(x.dtype)
    return (_with_shared(params, y.reshape(b, s, d), x),
            ids.reshape(b, s, -1))
