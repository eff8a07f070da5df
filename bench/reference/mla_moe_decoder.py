"""Plain float32 reference of the DeepSeek-V3 decoder (Moonlight-16B-A3B):
latent attention, sigmoid-routed experts with shared experts, after
``first_k_dense_replace`` dense layers; and the benchmark's own weights
for it.

Written from the published description (the model's ``config.json`` and
DeepSeek-V3's modelling code) in ``jax.numpy`` at ``highest`` matmul
precision, with no cache, batching or kernels. It reads the weights by
their keys and the configuration file's own keys (the published
``config.json`` names); it imports nothing of the program. One pre-norm
block, repeated ``num_hidden_layers`` times:

    x = embed[tokens]
    h = rms(x);  x += mla(h)
    h = rms(x);  x += swiglu(h)            (the first k layers)
                 x += moe(h)               (the others)
    logits = rms(x) @ lm_head

``mla``, written out in the non-absorbed form: per token
``[q_nope | q_pe] = h Wq`` per head (``q_lora_rank`` null),
``[c | k_pe] = h Wkv_a``, ``c = rms(c)``, each head's
``[k_nope | v] = c Wkv_b``, ``k_pe`` one rope key shared by the heads;
causal softmax of ``(q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)``
over the values, then ``Wo``. ``moe``: sigmoid scores of the router over
every published expert; the top ``num_experts_per_tok`` of scores plus the
correction bias; weights the chosen scores over their sum, times
``routed_scaling_factor``; the shared experts, one SwiGLU of width
``n_shared_experts * moe_intermediate_size``, on every token.

``forward_routed`` runs the same model on a routing it is given (each
token's chosen experts in each expert layer, such as the served path's),
weighing the given experts by its own scores; it also returns, for each
token and layer, how far below its own k-th best score plus bias the
given experts reach (0 where they are its own top k).

Departures from the published model, each shared with the program:

* the expert share: the file holds ``n_routed_experts`` experts, those of
  ``deployment.experts_here`` (the first held id), out of the router's
  ``deployment.published_n_routed_experts``; each token's experts are its
  chosen held experts, and what the others would add is left out;
* the correction bias is random from the seed (the published one is
  learned), and so is every weight;
* rope pairs the first and second halves of the 64 rope dimensions
  (DeepSeek's code pairs interleaved ones: a fixed permutation of the
  projections' columns, which random weights do not see);
* ``n_group`` = ``topk_group`` = 1 makes group-limited routing the plain
  top-k, and the sequence-wise auxiliary loss is a training term.

The weight tree has the program's layout: one stacked segment of the
dense layers and one of the expert layers, ``embed`` (vocab, d),
``final_norm``, ``lm_head`` (d, vocab).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
# The correction bias's spread, against sigmoid scores that spread ~0.2.
BIAS_STD = 0.05


def _dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    dep = cfg["deployment"]
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "r": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
        "ff": cfg["intermediate_size"], "f": cfg["moe_intermediate_size"],
        "E": dep["published_n_routed_experts"],
        "held": cfg["n_routed_experts"], "lo": dep["experts_here"][0],
        "k": cfg["num_experts_per_tok"], "shared": cfg["n_shared_experts"],
        "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"]}


def _attn_layout(n, m):
    d, h, r = m["d"], m["h"], m["r"]
    return {"wq": ("normal", (n, d, h, m["nope"] + m["rope"]), d),
            "wkv_a": ("normal", (n, d, r + m["rope"]), d),
            "kv_norm": ("ones", (n, r)),
            "wkv_b": ("normal", (n, r, h, m["nope"] + m["vd"]), r),
            "wo": ("normal", (n, h, m["vd"], d), h * m["vd"])}


def _swiglu_layout(lead, d, f):
    return {"w_gate": ("normal", lead + (d, f), d),
            "w_up": ("normal", lead + (d, f), d),
            "w_down": ("normal", lead + (f, d), f)}


def param_layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """(init, shape, fan-in[, dtype]) of every weight, in the program's
    tree."""
    m = _dims(cfg)
    d = m["d"]
    nd, ne = m["dense"], m["L"] - m["dense"]

    def norm(n):
        return {"scale": ("ones", (n, d))}

    dense = {"ln1": norm(nd), "attn": _attn_layout(nd, m), "ln2": norm(nd),
             "mlp": _swiglu_layout((nd,), d, m["ff"])}
    experts = _swiglu_layout((ne, m["held"]), d, m["f"])
    experts["router"] = ("normal", (ne, d, m["E"]), d, "float32")
    experts["router_bias"] = ("bias", (ne, m["E"]), 0, "float32")
    experts["shared"] = _swiglu_layout((ne,), d, m["shared"] * m["f"])
    moe = {"ln1": norm(ne), "attn": _attn_layout(ne, m), "ln2": norm(ne),
           "mlp": experts}
    return {"embed": ("embed", (m["V"], d)),
            "final_norm": {"scale": ("ones", (d,))},
            "segments": [dense, moe],
            "lm_head": ("normal", (d, m["V"]), d)}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and isinstance(x[0], str)


def make_params(cfg: Dict[str, Any], key, dtype=None):
    """Random weights from ``key`` in one jitted call, on the device:
    normal / sqrt(fan-in) matrices and embeddings of std 0.02 in the
    served dtype, unit norm scales; the router's matrix (normal /
    sqrt(fan-in)) and its correction bias (normal, std ``BIAS_STD``) in
    float32, as the program keeps them."""
    leaves, treedef = jax.tree.flatten(param_layout(cfg), is_leaf=_is_leaf)
    served = jnp.dtype(dtype or cfg["param_dtype"])

    def build(k):
        keys = jax.random.split(k, len(leaves))
        vals = []
        for kk, (init, shape, *rest) in zip(keys, leaves):
            dt = jnp.dtype(rest[1]) if len(rest) > 1 else served
            if init == "ones":
                vals.append(jnp.ones(shape, dt))
                continue
            std = {"embed": EMBED_STD, "bias": BIAS_STD}.get(init)
            std = std if std is not None else 1.0 / math.sqrt(rest[0])
            vals.append((jax.random.normal(kk, shape, jnp.float32)
                         * std).astype(dt))
        return jax.tree.unflatten(treedef, vals)

    return jax.jit(build)(key)


def _rms(x, scale, eps):
    x = x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


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


def _mla(cfg, m, h, a, cast):
    s = h.shape[0]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = jnp.einsum("sd,dhk->shk", h, cast(a["wq"]))
    q_nope, q_pe = q[..., :m["nope"]], _rope(q[..., m["nope"]:], theta)
    kv = h @ cast(a["wkv_a"])
    c = _rms(kv[:, :m["r"]], a["kv_norm"], eps)
    k_pe = _rope(kv[:, None, m["r"]:], theta)                 # (S, 1, rope)
    kvb = jnp.einsum("sr,rhk->shk", c, cast(a["wkv_b"]))
    k_nope, v = kvb[..., :m["nope"]], kvb[..., m["nope"]:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (s, m["h"], m["rope"]))], -1)
    qq = jnp.concatenate([q_nope, q_pe], -1)
    scores = jnp.einsum("qhk,shk->hqs", qq, k) / math.sqrt(m["nope"]
                                                           + m["rope"])
    causal = np.tril(np.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hqs,shk->qhk", probs, v)
    return jnp.einsum("qhk,hkd->qd", out, cast(a["wo"]))


def _swiglu(h, p, cast):
    g = jax.nn.silu(h @ cast(p["w_gate"]))
    return (g * (h @ cast(p["w_up"]))) @ cast(p["w_down"])


def gates(cfg: Dict[str, Any], h, p, cast=None, given=None):
    """Per token (S,) the weight of each held expert (S, held), zero where
    the token did not choose it: the router over every published expert,
    top-k of sigmoid scores plus the bias (or the ``given`` ids (S, k),
    where not negative), the chosen scores over their sum times the routed
    scale. Also the chosen ids (S, k) and each token's deficit (S,): how
    far the lowest chosen score plus bias lies below the k-th best."""
    m = _dims(cfg)
    cast = cast or _identity_cast
    scores = jax.nn.sigmoid(h @ cast(p["router"]))             # (S, E)
    biased = scores + p["router_bias"]
    top, ids = jax.lax.top_k(biased, m["k"])
    if given is not None:
        ids = jnp.where(given >= 0, given, ids)
    deficit = jnp.max(top[:, -1:] - jnp.take_along_axis(biased, ids, -1),
                      -1)
    w = jnp.take_along_axis(scores, ids, -1)
    w = w / jnp.sum(w, -1, keepdims=True) * cfg["routed_scaling_factor"]
    held = jnp.arange(m["lo"], m["lo"] + m["held"])
    chose = ids[:, :, None] == held[None, None, :]             # (S, k, held)
    return (jnp.einsum("sk,ske->se", w, chose.astype(jnp.float32)), ids,
            jnp.maximum(deficit, 0.0))


def moe(cfg: Dict[str, Any], h, p, cast=None, given=None):
    """The expert layer on tokens ``h`` (S, d): each held expert weighed by
    its gate (zero where not chosen), plus the shared experts. Returns
    (output (S, d), chosen ids, deficits) (``gates``)."""
    cast = cast or _identity_cast
    g, ids, deficit = gates(cfg, h, p, cast, given)
    up = jnp.einsum("sd,edf->esf", h, cast(p["w_up"]))
    act = jax.nn.silu(jnp.einsum("sd,edf->esf", h, cast(p["w_gate"])))
    out = jnp.einsum("esf,efd->esd", act * up, cast(p["w_down"]))
    return (jnp.einsum("se,esd->sd", g, out) + _swiglu(h, p["shared"], cast),
            ids, deficit)


def _block(cfg, m, x, p, cast, given):
    """One block; ``given`` is None for a dense layer, else the expert
    layer's given ids (S, k). Returns (x, (ids, deficits) or None)."""
    eps = cfg["rms_norm_eps"]
    x = x + _mla(cfg, m, _rms(x, p["ln1"]["scale"], eps), p["attn"], cast)
    h = _rms(x, p["ln2"]["scale"], eps)
    if given is None:
        return x + _swiglu(h, p["mlp"], cast), None
    y, ids, deficit = moe(cfg, h, p["mlp"], cast, given)
    return x + y, (ids, deficit)


def _identity_cast(w):
    return w.astype(jnp.float32)


def forward_routed(cfg: Dict[str, Any], params, tokens, routing=None,
                   cast=_identity_cast):
    """The forward over one sequence ``tokens`` (S,) on a given routing:
    ``routing`` (expert layers, S, k) ids, each used where not negative
    (None: the reference's own everywhere). Returns the logits (S, vocab)
    in float32, the ids used (expert layers, S, k) and the deficits
    (expert layers, S) (``gates``). ``cast`` maps each matrix to the
    float32 the reference computes with; a control passes one that rounds
    it to a lower precision."""
    m = _dims(cfg)
    if routing is None:
        routing = jnp.full((m["L"] - m["dense"], tokens.shape[0], m["k"]),
                           -1, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = cast(params["embed"])[tokens]
        dense, experts = params["segments"]
        x, _ = jax.lax.scan(
            lambda x, p: _block(cfg, m, x, p, cast, None), x, dense)
        x, (ids, deficit) = jax.lax.scan(
            lambda x, pg: _block(cfg, m, x, pg[0], cast, pg[1]), x,
            (experts, routing))
        x = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
        return x @ cast(params["lm_head"]), ids, deficit


def forward(cfg: Dict[str, Any], params, tokens, cast=_identity_cast):
    """Logits (S, vocab) in float32 for one sequence ``tokens`` (S,), on
    the reference's own routing."""
    return forward_routed(cfg, params, tokens, None, cast)[0]


# ---------------------------------------------------------------------------
# Counts from shapes
# ---------------------------------------------------------------------------


def _attn_macs(m) -> float:
    d, h, r = m["d"], m["h"], m["r"]
    return (d * h * (m["nope"] + m["rope"]) + d * (r + m["rope"])
            + r * h * (m["nope"] + m["vd"]) + h * m["vd"] * d)


def _expert_macs(m) -> float:
    """An expert layer's MLP a token: the router over every expert, the
    routed experts of the held share (expected: k * held / E), the shared
    experts."""
    routed = m["k"] * m["held"] / m["E"]
    return m["d"] * m["E"] + 3.0 * m["d"] * m["f"] * (routed + m["shared"])


def flops_per_token(cfg: Dict[str, Any], context: int) -> float:
    """Model FLOPs of one token whose attention reads ``context``
    positions, in the published (non-absorbed) form: 2 per
    multiply-accumulate of every matrix, the attention scores and values
    over the context, and the logits."""
    m = _dims(cfg)
    ne = m["L"] - m["dense"]
    macs = (m["L"] * _attn_macs(m) + m["dense"] * 3.0 * m["d"] * m["ff"]
            + ne * _expert_macs(m) + m["d"] * m["V"]
            + m["L"] * m["h"] * (m["nope"] + m["rope"] + m["vd"]) * context)
    return 2.0 * macs


def prefill_flops(cfg: Dict[str, Any], n: int) -> float:
    """Model FLOPs of a causal prefill of ``n`` tokens: token i reads
    i + 1 positions."""
    m = _dims(cfg)
    attn = 2.0 * m["L"] * m["h"] * (m["nope"] + m["rope"] + m["vd"]) \
        * (n * (n + 1) / 2)
    return n * flops_per_token(cfg, 0) + attn


def _side_layers(cfg: Dict[str, Any], side: str):
    """(dense layers, expert layers) a side of the cut at ``plan.point``
    holds: the head layers [0, point], the tail the rest."""
    m = _dims(cfg)
    point = int(cfg["plan"]["point"])
    head = point + 1
    layers = range(head) if side == "head" else range(head, m["L"])
    dense = sum(1 for i in layers if i < m["dense"])
    return dense, len(layers) - dense


def decode_bytes(cfg: Dict[str, Any], side: str, experts_hit: int,
                 rows: int) -> float:
    """Bytes the ``side`` ("head" or "tail") decode program must move in
    one step: each of its weights once, except the routed experts, of
    which only the ``experts_hit`` (summed over its expert layers) count;
    and ``rows`` cache positions (summed over the live slots) of each of
    its layers, bf16 on the head, int8 codes with a float32 scale for
    ``c_kv`` and for ``k_pe`` on the tail where ``plan.cloud_kv_bits`` is
    8. The new tokens' embedding rows and the logits written are left
    out."""
    m = _dims(cfg)
    d = m["d"]
    wb = jnp.dtype(cfg["param_dtype"]).itemsize
    dense, experts = _side_layers(cfg, side)
    layer = _attn_macs(m) * wb + (2 * d + m["r"]) * wb    # + norm scales
    weights = (dense * (layer + 3 * d * m["ff"] * wb)
               + experts * (layer + (d + 1) * m["E"] * 4
                            + 3 * d * m["shared"] * m["f"] * wb)
               + experts_hit * 3 * d * m["f"] * wb)
    if side == "tail":
        weights += d * m["V"] * wb + d * wb            # lm_head, final norm
    if side == "tail" and int(cfg["plan"]["cloud_kv_bits"]) == 8:
        row = m["r"] + m["rope"] + 2 * 4
    else:
        row = (m["r"] + m["rope"]) * wb
    return float(weights + rows * row * (dense + experts))
