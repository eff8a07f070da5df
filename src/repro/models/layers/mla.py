"""Multi-head latent attention (MLA: DeepSeek-V2, arXiv:2405.04434; the
attention of DeepSeek-V3 and Moonlight).

Per token, with ``nope``/``rope`` the two parts of a query-key head and
``r`` the latent rank:

    [q_nope_h | q_pe_h] = x Wq_h                     (q_pe_h roped)
    [c_kv | k_pe]       = x Wkv_a;   c_kv = RMSNorm(c_kv);   k_pe roped
    [k_nope_h | v_h]    = c_kv Wkv_b_h               (W_UK_h | W_UV_h)
    score_h(t) = (q_nope_h . k_nope_h(t) + q_pe_h . k_pe(t)) / sqrt(nope + rope)
    out = sum_h (softmax_t score_h) v_h Wo_h

``k_pe`` is one rope key shared by every head. A full sequence (prefill,
forward) builds each head's keys and values from the latent. Decode runs
in the absorbed form over the latent cache: ``W_UK`` is folded into the
query (``q_lat_h = q_nope_h W_UK_h^T``, r wide), the scores read ``c_kv``
and ``k_pe`` as they are stored, and ``W_UV`` is applied after the
weighted sum over positions, so no position's keys or values are rebuilt.

The cache holds ``c_kv`` (r) and ``k_pe`` (rope) a position: in the
model's dtype, or (``kv_cache_bits == 8``) as int8 codes with one float32
scale a position for each, the scales applied to the scores and to the
attention weights rather than to the cache.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.config.types import ModelConfig
from repro.models.init import spec
from repro.models.layers import rope as rope_lib
from repro.models.layers.attention import quantize_kv_row
from repro.models.layers.norms import apply_norm

_NEG_INF = -1e30
_F32 = jnp.float32


def mla_spec(cfg: ModelConfig):
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = cfg.param_dtype
    return {
        "wq": spec((d, h, nope + rope), ("embed", "heads", "head_dim"), dt),
        "wkv_a": spec((d, r + rope), ("embed", None), dt),
        "kv_norm": spec((r,), (None,), dt, init="ones"),
        "wkv_b": spec((r, h, nope + vd), (None, "heads", "head_dim"), dt),
        "wo": spec((h, vd, d), ("heads", "head_dim", "embed"), dt),
    }


def project(params, x: jnp.ndarray, positions: jnp.ndarray,
            cfg: ModelConfig):
    """x (B, S, d) -> q_nope (B,S,H,nope), q_pe (B,S,H,rope), c_kv (B,S,r),
    k_pe (B,S,rope), both pe parts roped at ``positions`` (B, S)."""
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q_pe = rope_lib.apply_rope(q[..., nope:], positions, cfg.rope_theta)
    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    c_kv = apply_norm("rmsnorm", {"scale": params["kv_norm"]}, kv[..., :r])
    k_pe = rope_lib.apply_rope(kv[..., None, r:], positions,
                               cfg.rope_theta)[:, :, 0]
    return q[..., :nope], q_pe, c_kv, k_pe


def _softmax_scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def attend_seq(params, q_nope, q_pe, c_kv, k_pe, cfg: ModelConfig,
               window: int = 0) -> jnp.ndarray:
    """Causal attention over a whole sequence, each head's keys and values
    built from the latent. Returns the block's attention output (B, S, d)."""
    nope = cfg.qk_nope_head_dim
    kvb = jnp.einsum("bsr,rhk->bshk", c_kv, params["wkv_b"])
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scores = (jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope,
                         preferred_element_type=_F32)
              + jnp.einsum("bqhk,bsk->bhqs", q_pe, k_pe,
                           preferred_element_type=_F32))
    scores = scores * _softmax_scale(cfg)
    s = q_nope.shape[1]
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
    return jnp.einsum("bqhk,hkd->bqd", out, params["wo"])


# ---------------------------------------------------------------------------
# The latent cache
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype) -> Dict[str, jnp.ndarray]:
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    if cfg.kv_cache_bits == 8:
        zs = jnp.zeros((batch, cache_len), _F32)
        return {"ckv": jnp.zeros((batch, cache_len, r), jnp.int8),
                "ckv_s": zs,
                "kpe": jnp.zeros((batch, cache_len, rope), jnp.int8),
                "kpe_s": zs}
    return {"ckv": jnp.zeros((batch, cache_len, r), dtype),
            "kpe": jnp.zeros((batch, cache_len, rope), dtype)}


def cache_axes(cfg: ModelConfig = None):
    rows = ("batch", "kv_seq", None)
    if cfg is not None and cfg.kv_cache_bits == 8:
        return {"ckv": rows, "ckv_s": ("batch", "kv_seq"), "kpe": rows,
                "kpe_s": ("batch", "kv_seq")}
    return {"ckv": rows, "kpe": rows}


def _rows(c_kv, k_pe, cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    """Cache rows (B, S, ...) of ``c_kv`` and ``k_pe`` in the cache's
    format."""
    if cfg.kv_cache_bits == 8:
        ckv, ckv_s = quantize_kv_row(c_kv)
        kpe, kpe_s = quantize_kv_row(k_pe)
        return {"ckv": ckv, "ckv_s": ckv_s, "kpe": kpe, "kpe_s": kpe_s}
    return {"ckv": c_kv, "kpe": k_pe}


def build_cache(c_kv, k_pe, cache_len: int, place,
                cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    """The cache of a prefilled sequence: its rows placed by ``place``
    (``blocks._ring_place``: (B, S, ...) -> (B, cache_len, ...))."""
    s = c_kv.shape[1]
    return {k: place(v, s, cache_len)
            for k, v in _rows(c_kv, k_pe, cfg).items()}


def decode(params, x: jnp.ndarray, cache: Dict[str, jnp.ndarray], pos,
           cfg: ModelConfig):
    """One new token ``x`` (B, 1, d) at position ``pos`` against the latent
    cache, absorbed. Returns (attention output (B, 1, d), new cache)."""
    b = x.shape[0]
    positions = jnp.broadcast_to(pos, (b, 1))
    q_nope, q_pe, c_kv, k_pe = project(params, x, positions, cfg)
    s_c = cache["ckv"].shape[1]
    slot = jnp.mod(pos, s_c)
    new = dict(cache)
    for k, v in _rows(c_kv, k_pe, cfg).items():
        start = (0, slot) + (0,) * (v.ndim - 2)
        new[k] = jax.lax.dynamic_update_slice(cache[k],
                                              v.astype(cache[k].dtype), start)
    nope = cfg.qk_nope_head_dim
    w_uk = params["wkv_b"][..., :nope]                    # (r, H, nope)
    w_uv = params["wkv_b"][..., nope:]                    # (r, H, vd)
    q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, w_uk)    # (B, 1, H, r)
    ckv, kpe = new["ckv"], new["kpe"]
    if cfg.kv_cache_bits == 8:
        ckv, kpe = ckv.astype(x.dtype), kpe.astype(x.dtype)
    s_lat = jnp.einsum("bqhr,bsr->bhqs", q_lat, ckv,
                       preferred_element_type=_F32)
    s_pe = jnp.einsum("bqhk,bsk->bhqs", q_pe, kpe,
                      preferred_element_type=_F32)
    if cfg.kv_cache_bits == 8:
        s_lat = s_lat * new["ckv_s"][:, None, None, :]
        s_pe = s_pe * new["kpe_s"][:, None, None, :]
    scores = (s_lat + s_pe) * _softmax_scale(cfg)
    valid = jnp.arange(s_c) < jnp.minimum(pos + 1, s_c)
    scores = jnp.where(valid, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if cfg.kv_cache_bits == 8:
        probs = probs * new["ckv_s"][:, None, None, :]
    out_lat = jnp.einsum("bhqs,bsr->bqhr", probs.astype(x.dtype), ckv)
    out = jnp.einsum("bqhr,rhk->bqhk", out_lat, w_uv)
    return jnp.einsum("bqhk,hkd->bqd", out, params["wo"]), new
