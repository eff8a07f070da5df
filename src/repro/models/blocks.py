"""Block-level composition.

Every architecture is a sequence of *segments*; a segment is a contiguous
run of identical blocks whose stacked parameters are consumed by one
``lax.scan``. Block kinds:

  'd'  dense decoder block   (attn + SwiGLU)           — llama family
  'e'  MoE decoder block     (attn + top-k experts)    — llama4 / grok
  'm'  Mamba2 block                                    — zamba2
  'l'  mLSTM block                                     — xlstm
  's'  sLSTM block                                     — xlstm
  'A'  shared attention block (zamba2; params shared across invocations)
  'E'  encoder block         (bidirectional attn + GELU MLP) — seamless
  'c'  decoder-with-cross-attention block              — seamless

Each kind provides: ``spec`` (ParamSpec tree), ``apply_seq`` (full sequence;
returns (x, aux, cache_entry, routes)) and ``apply_decode`` (one token;
returns (x, new_cache_entry, routes)); ``routes`` are an 'e' block's chosen
expert ids (B, S, k) int32, None for the other kinds. The 'd' and 'e' blocks take multi-head latent
attention (``layers/mla.py``) where the config sets ``kv_lora_rank``; their
cache entry is then the latent (``c_kv`` and ``k_pe`` a position).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.types import ModelConfig
from repro.models.layers import attention as attn_lib
from repro.models.layers import mamba2 as mamba_lib
from repro.models.layers import mla as mla_lib
from repro.models.layers import xlstm as xlstm_lib
from repro.models.layers.mlp import (
    apply_gelu_mlp,
    apply_swiglu,
    gelu_mlp_spec,
    swiglu_spec,
)
from repro.models.layers.moe import apply_moe, apply_moe_decode, moe_spec
from repro.models.layers.norms import apply_norm, norm_spec


class SeqContext(NamedTuple):
    """Everything a block needs for a full-sequence pass."""

    positions: jnp.ndarray                    # (B, S) int32
    positions_3d: Optional[jnp.ndarray]       # (B, S, 3) for M-RoPE or None
    window: int                               # 0 = full attention
    cache_len: int                            # 0 = don't build decode caches
    enc_out: Optional[jnp.ndarray] = None     # encoder output for 'c'


class DecodeContext(NamedTuple):
    pos: jnp.ndarray                          # () int32 — index of new token
    window: int
    positions_3d: Optional[jnp.ndarray] = None  # (B, 1, 3)


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def _mla(kind: str, cfg: ModelConfig) -> bool:
    """Whether a block of this kind attends with latent attention."""
    return kind in ("d", "e") and cfg.is_mla


def block_spec(kind: str, cfg: ModelConfig):
    d, dt_ = cfg.d_model, cfg.param_dtype
    if kind in ("d", "e", "A"):
        p = {
            "ln1": norm_spec(cfg.norm_kind, d, dt_),
            "attn": (mla_lib.mla_spec(cfg) if _mla(kind, cfg)
                     else attn_lib.attention_spec(cfg)),
            "ln2": norm_spec(cfg.norm_kind, d, dt_),
        }
        p["mlp"] = moe_spec(cfg) if kind == "e" else swiglu_spec(d, cfg.d_ff, dt_)
        return p
    if kind == "m":
        return {
            "ln": norm_spec(cfg.norm_kind, d, dt_),
            "mamba": mamba_lib.mamba2_spec(cfg),
        }
    if kind == "l":
        return {"ln": norm_spec(cfg.norm_kind, d, dt_),
                "mlstm": xlstm_lib.mlstm_spec(cfg)}
    if kind == "s":
        return {"ln": norm_spec(cfg.norm_kind, d, dt_),
                "slstm": xlstm_lib.slstm_spec(cfg)}
    if kind == "E":
        return {
            "ln1": norm_spec("layernorm", d, dt_),
            "attn": attn_lib.attention_spec(cfg),
            "ln2": norm_spec("layernorm", d, dt_),
            "mlp": gelu_mlp_spec(d, cfg.d_ff, dt_),
        }
    if kind == "c":
        return {
            "ln1": norm_spec("layernorm", d, dt_),
            "attn": attn_lib.attention_spec(cfg),
            "ln_x": norm_spec("layernorm", d, dt_),
            "xattn": attn_lib.attention_spec(cfg, cross=True),
            "ln2": norm_spec("layernorm", d, dt_),
            "mlp": gelu_mlp_spec(d, cfg.d_ff, dt_),
        }
    raise ValueError(f"unknown block kind {kind!r}")


# ---------------------------------------------------------------------------
# Cache helpers
# ---------------------------------------------------------------------------


def _ring_place(arr: jnp.ndarray, seq_len: int, cache_len: int) -> jnp.ndarray:
    """Place the last ``cache_len`` steps of (B,S,...) into ring-buffer order
    (slot of position p is p % cache_len)."""
    if seq_len <= cache_len:
        pad = [(0, 0), (0, cache_len - seq_len)] + [(0, 0)] * (arr.ndim - 2)
        return jnp.pad(arr, pad)
    tail = arr[:, -cache_len:]
    return jnp.roll(tail, shift=seq_len % cache_len, axis=1)


def _kv_cache_entry(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    kv, hd = cfg.num_kv_heads, cfg.head_dim_
    if cfg.kv_cache_bits == 8:
        z8 = jnp.zeros((batch, cache_len, kv, hd), jnp.int8)
        zs = jnp.zeros((batch, cache_len, kv), jnp.float32)
        return {"k": z8, "ks": zs, "v": z8, "vs": zs}
    z = jnp.zeros((batch, cache_len, kv, hd), dtype)
    return {"k": z, "v": z}


def init_block_cache(kind: str, cfg: ModelConfig, batch: int, cache_len: int,
                     dtype, enc_len: int = 0):
    """Zero cache entry for ONE block of this kind (unstacked)."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim_
    if _mla(kind, cfg):
        return mla_lib.init_cache(cfg, batch, cache_len, dtype)
    if kind in ("d", "e", "A"):
        return _kv_cache_entry(cfg, batch, cache_len, dtype)
    if kind == "m":
        return mamba_lib.init_mamba_state(cfg, batch, dtype)._asdict()
    if kind == "l":
        return xlstm_lib.init_mlstm_state(cfg, batch, dtype)._asdict()
    if kind == "s":
        return xlstm_lib.init_slstm_state(cfg, batch, dtype)._asdict()
    if kind == "c":
        entry = _kv_cache_entry(cfg, batch, cache_len, dtype)
        zx = jnp.zeros((batch, enc_len, kv, hd), dtype)
        entry.update({"xk": zx, "xv": zx})
        return entry
    if kind == "E":
        return {}
    raise ValueError(kind)


def block_cache_axes(kind: str, cfg: ModelConfig = None):
    """Logical axis names for each cache entry of ``init_block_cache``
    (same tree structure; tuples align with array dims). Consumed by the
    sharding resolver for the dry-run / serving in_shardings."""
    kv4 = ("batch", "kv_seq", "kv_heads", "head_dim")
    kv3 = ("batch", "kv_seq", "kv_heads")
    q8 = cfg is not None and cfg.kv_cache_bits == 8
    if cfg is not None and _mla(kind, cfg):
        return mla_lib.cache_axes(cfg)
    if kind in ("d", "e", "A"):
        if q8:
            return {"k": kv4, "ks": kv3, "v": kv4, "vs": kv3}
        return {"k": kv4, "v": kv4}
    if kind == "m":
        return {
            "ssm": ("batch", "heads", "ssm_state", "head_dim"),
            "conv": ("batch", None, "conv_out"),
        }
    if kind == "l":
        return {
            "C": ("batch", "heads", "head_dim", None),
            "n": ("batch", "heads", "head_dim"),
            "m": ("batch", "heads"),
            "conv": ("batch", None, "ssm_in"),
        }
    if kind == "s":
        hd3 = ("batch", "heads", "head_dim")
        return {"c": hd3, "n": hd3, "hid": hd3, "m": hd3,
                "conv": ("batch", None, None)}
    if kind == "c":
        enc4 = ("batch", "enc_seq", "kv_heads", "head_dim")
        if q8:
            return {"k": kv4, "ks": kv3, "v": kv4, "vs": kv3,
                    "xk": enc4, "xv": enc4}
        return {"k": kv4, "v": kv4, "xk": enc4, "xv": enc4}
    if kind == "E":
        return {}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Full-sequence application
# ---------------------------------------------------------------------------


def block_apply_seq(
    kind: str, params, x: jnp.ndarray, ctx: SeqContext, cfg: ModelConfig
) -> Tuple[jnp.ndarray, jnp.ndarray, Any, Any]:
    """Returns (x_new, aux_loss, cache_entry_or_None, routes_or_None)."""
    aux = jnp.zeros((), jnp.float32)
    routes = None
    s = x.shape[1]

    if _mla(kind, cfg):
        h = apply_norm(cfg.norm_kind, params["ln1"], x)
        q_nope, q_pe, c_kv, k_pe = mla_lib.project(params["attn"], h,
                                                   ctx.positions, cfg)
        x = x + mla_lib.attend_seq(params["attn"], q_nope, q_pe, c_kv, k_pe,
                                   cfg, ctx.window)
        h2 = apply_norm(cfg.norm_kind, params["ln2"], x)
        if kind == "e":
            y, aux, routes = apply_moe(params["mlp"], h2, cfg)
        else:
            y = apply_swiglu(params["mlp"], h2)
        cache = None
        if ctx.cache_len:
            cache = mla_lib.build_cache(c_kv, k_pe, ctx.cache_len,
                                        _ring_place, cfg)
        return x + y, aux, cache, routes

    if kind in ("d", "e", "A", "E"):
        h = apply_norm(cfg.norm_kind if kind != "E" else "layernorm",
                       params["ln1"], x)
        q, k, v = attn_lib.project_qkv(
            params["attn"], h, ctx.positions, cfg,
            rope=(kind != "E") or cfg.rope_kind != "none",
            positions_3d=ctx.positions_3d,
        )
        causal = kind != "E"
        out = attn_lib.prefill_attention(
            q, k, v, causal=causal, window=ctx.window if causal else 0
        )
        x = x + attn_lib.attn_output(params["attn"], out)
        h2 = apply_norm(cfg.norm_kind if kind != "E" else "layernorm",
                        params["ln2"], x)
        if kind == "e":
            y, aux, routes = apply_moe(params["mlp"], h2, cfg)
        elif kind == "E":
            y = apply_gelu_mlp(params["mlp"], h2)
        else:
            y = apply_swiglu(params["mlp"], h2)
        x = x + y
        cache = None
        if ctx.cache_len and kind != "E":
            cache = _build_kv_cache(k, v, s, ctx.cache_len, cfg)
        return x, aux, cache, routes

    if kind == "m":
        h = apply_norm(cfg.norm_kind, params["ln"], x)
        # For prefill we need the final SSM/conv state: use the stateful path.
        if ctx.cache_len:
            y, state = _mamba_seq_with_state(params["mamba"], h, cfg)
            return x + y, aux, state._asdict(), None
        y = mamba_lib.apply_mamba2(params["mamba"], h, cfg)
        return x + y, aux, None, None

    if kind == "l":
        h = apply_norm(cfg.norm_kind, params["ln"], x)
        y, state = xlstm_lib.apply_mlstm(params["mlstm"], h, cfg)
        return (x + y, aux, state._asdict() if ctx.cache_len else None,
                None)

    if kind == "s":
        h = apply_norm(cfg.norm_kind, params["ln"], x)
        y, state = xlstm_lib.apply_slstm(params["slstm"], h, cfg)
        return (x + y, aux, state._asdict() if ctx.cache_len else None,
                None)

    if kind == "c":
        h = apply_norm("layernorm", params["ln1"], x)
        q, k, v = attn_lib.project_qkv(params["attn"], h, ctx.positions, cfg)
        out = attn_lib.prefill_attention(q, k, v, causal=True, window=ctx.window)
        x = x + attn_lib.attn_output(params["attn"], out)
        hx = apply_norm("layernorm", params["ln_x"], x)
        xk, xv = attn_lib.cross_attention_kv(params["xattn"], ctx.enc_out)
        x = x + attn_lib.cross_attention(params["xattn"], hx, xk, xv)
        h2 = apply_norm("layernorm", params["ln2"], x)
        x = x + apply_gelu_mlp(params["mlp"], h2)
        cache = None
        if ctx.cache_len:
            cache = _build_kv_cache(k, v, s, ctx.cache_len, cfg)
            cache.update({"xk": xk, "xv": xv})
        return x, aux, cache, routes

    raise ValueError(kind)


def _build_kv_cache(k, v, s, cache_len, cfg: ModelConfig):
    """Ring-ordered KV cache from prefill keys/values, optionally
    JALAD-quantized to int8 (cfg.kv_cache_bits == 8)."""
    kc = _ring_place(k, s, cache_len)
    vc = _ring_place(v, s, cache_len)
    if cfg.kv_cache_bits == 8:
        qk, ks = attn_lib.quantize_kv_row(kc)
        qv, vs = attn_lib.quantize_kv_row(vc)
        return {"k": qk, "ks": ks, "v": qv, "vs": vs}
    return {"k": kc, "v": vc}


def _mamba_seq_with_state(params, h, cfg):
    """Run mamba over a sequence and return the final recurrent state.

    Chunked SSD already produces the final state; we re-derive conv state
    from the raw conv inputs (last width-1 steps)."""
    dims = mamba_lib.mamba_dims(cfg)
    proj = jnp.einsum("bld,de->ble", h, params["in_proj"])
    z, xbc_raw, dt_raw = mamba_lib._split_in_proj(proj, dims)
    conv_tail = xbc_raw[:, -(dims.conv_width - 1):]
    if h.shape[1] < dims.conv_width - 1:
        pad = dims.conv_width - 1 - h.shape[1]
        conv_tail = jnp.pad(conv_tail, ((0, 0), (pad, 0), (0, 0)))

    xbc = jax.nn.silu(
        mamba_lib._causal_depthwise_conv(
            xbc_raw, params["conv_w"], params["conv_b"]
        ).astype(jnp.float32)
    )
    xin = xbc[..., : dims.d_inner]
    Bm = xbc[..., dims.d_inner : dims.d_inner + dims.state]
    Cm = xbc[..., dims.d_inner + dims.state :]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    xh = xin.reshape(*xin.shape[:2], dims.heads, dims.head_dim)
    chunk = 256
    if h.shape[1] % chunk == 0 and h.shape[1] > chunk:
        y, S = mamba_lib.ssd_chunked(xh, dt, A, Bm, Cm, chunk)
    else:
        y, S = mamba_lib.ssd_sequential(xh, dt, A, Bm, Cm)
    y = y + params["D"][None, None, :, None] * xh
    y = y.reshape(*h.shape[:2], dims.d_inner)
    g = jax.nn.silu(z.astype(jnp.float32))
    yn = y * g
    ms = jnp.mean(jnp.square(yn), axis=-1, keepdims=True)
    yn = yn * (ms + 1e-5) ** -0.5 * params["norm_scale"].astype(jnp.float32)
    out = jnp.einsum("ble,ed->bld", yn.astype(h.dtype), params["out_proj"])
    return out, mamba_lib.MambaState(S, conv_tail)


# ---------------------------------------------------------------------------
# Single-token decode
# ---------------------------------------------------------------------------


def block_apply_decode(
    kind: str, params, x: jnp.ndarray, cache, ctx: DecodeContext,
    cfg: ModelConfig
) -> Tuple[jnp.ndarray, Any, Any]:
    """x: (B, 1, d). Returns (x_new, cache_new, routes): an 'e' block's
    chosen expert ids (B, 1, k) int32, None for the other kinds."""
    if _mla(kind, cfg):
        h = apply_norm(cfg.norm_kind, params["ln1"], x)
        out, new_cache = mla_lib.decode(params["attn"], h, cache, ctx.pos,
                                        cfg)
        x = x + out
        h2 = apply_norm(cfg.norm_kind, params["ln2"], x)
        if kind == "e":
            y, routes = apply_moe_decode(params["mlp"], h2, cfg)
            return x + y, new_cache, routes
        return x + apply_swiglu(params["mlp"], h2), new_cache, None

    if kind in ("d", "e", "A", "c"):
        norm_kind = cfg.norm_kind if kind != "c" else "layernorm"
        h = apply_norm(norm_kind, params["ln1"], x)
        positions = jnp.broadcast_to(ctx.pos, (x.shape[0], 1))
        q, k, v = attn_lib.project_qkv(
            params["attn"], h, positions, cfg, positions_3d=ctx.positions_3d
        )
        if cfg.kv_cache_bits == 8:
            qk, ks_new = attn_lib.quantize_kv_row(k)
            qv, vs_new = attn_lib.quantize_kv_row(v)
            k_c, v_c = attn_lib.cache_update(cache["k"], cache["v"], qk, qv,
                                             ctx.pos)
            ks_c = attn_lib.scale_update(cache["ks"], ks_new, ctx.pos)
            vs_c = attn_lib.scale_update(cache["vs"], vs_new, ctx.pos)
            k_use = attn_lib.dequantize_kv(k_c, ks_c, q.dtype)
            v_use = attn_lib.dequantize_kv(v_c, vs_c, q.dtype)
            new_cache = dict(cache, k=k_c, v=v_c, ks=ks_c, vs=vs_c)
        else:
            k_c, v_c = attn_lib.cache_update(cache["k"], cache["v"], k, v,
                                             ctx.pos)
            k_use, v_use = k_c, v_c
            new_cache = dict(cache, k=k_c, v=v_c)
        out = attn_lib.decode_attention(q, k_use, v_use, ctx.pos + 1)
        x = x + attn_lib.attn_output(params["attn"], out)
        if kind == "c":
            hx = apply_norm("layernorm", params["ln_x"], x)
            x = x + attn_lib.cross_attention(
                params["xattn"], hx, cache["xk"], cache["xv"]
            )
        norm2 = apply_norm(norm_kind, params["ln2"], x)
        routes = None
        if kind == "e":
            y, routes = apply_moe_decode(params["mlp"], norm2, cfg)
        elif kind == "c":
            y = apply_gelu_mlp(params["mlp"], norm2)
        else:
            y = apply_swiglu(params["mlp"], norm2)
        return x + y, new_cache, routes

    if kind == "m":
        h = apply_norm(cfg.norm_kind, params["ln"], x)
        state = mamba_lib.MambaState(**cache)
        y, state = mamba_lib.decode_mamba2(params["mamba"], h, state, cfg)
        return x + y, state._asdict(), None

    if kind == "l":
        h = apply_norm(cfg.norm_kind, params["ln"], x)
        state = xlstm_lib.MLSTMState(**cache)
        y, state = xlstm_lib.apply_mlstm(params["mlstm"], h, cfg, state)
        return x + y, state._asdict(), None

    if kind == "s":
        h = apply_norm(cfg.norm_kind, params["ln"], x)
        state = xlstm_lib.SLSTMState(**cache)
        y, state = xlstm_lib.apply_slstm(params["slstm"], h, cfg, state)
        return x + y, state._asdict(), None

    raise ValueError(kind)
