"""Architecture assembly: segment plan, parameter specs, and the three
entry points (train forward, prefill, single-token decode) for every
assigned architecture family (dense / moe / ssm / hybrid / vlm / audio).

The CNN family (paper testbed) lives in ``repro.models.cnn``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.types import ModelConfig
from repro.models import blocks as blk
from repro.models.init import spec, stack_tree
from repro.models.layers.norms import apply_norm, norm_spec
from repro.sharding.activation import constrain

_HID = ("batch", "seq", "embed")   # layer-boundary activation layout


# ---------------------------------------------------------------------------
# Segment plan
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Segment:
    kind: str
    count: int          # layers in this scan (1 for shared 'A')
    shared: bool = False


def default_pattern(cfg: ModelConfig) -> str:
    if cfg.block_pattern:
        return cfg.block_pattern
    if cfg.family == "moe":
        return "e" * cfg.num_layers
    return "d" * cfg.num_layers


def segment_plan(cfg: ModelConfig) -> List[Segment]:
    """Split the block pattern into contiguous same-kind runs; interleave the
    zamba-style shared attention block every ``shared_attention_every``."""
    pattern = default_pattern(cfg)
    if cfg.shared_attention_every:
        out: List[Segment] = []
        period = cfg.shared_attention_every
        i = 0
        while i < len(pattern):
            run = pattern[i : i + period]
            out.append(Segment(run[0], len(run)))
            i += period
            out.append(Segment("A", 1, shared=True))
        return out
    out = []
    i = 0
    while i < len(pattern):
        j = i
        while j < len(pattern) and pattern[j] == pattern[i]:
            j += 1
        out.append(Segment(pattern[i], j - i))
        i = j
    return out


def num_shared_invocations(plan: List[Segment]) -> int:
    return sum(1 for s in plan if s.shared)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    plan = segment_plan(cfg)
    dt_ = cfg.param_dtype
    specs: Dict[str, Any] = {
        "embed": spec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), dt_,
                      init="embed", scale=0.02),
        "final_norm": norm_spec(cfg.norm_kind, cfg.d_model, dt_),
        "segments": [
            stack_tree(blk.block_spec(s.kind, cfg), s.count)
            if not s.shared
            else {}
            for s in plan
        ],
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = spec(
            (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), dt_, scale=0.02
        )
    if cfg.shared_attention_every:
        specs["shared_attn"] = blk.block_spec("A", cfg)
    if cfg.family == "vlm":
        specs["vision_proj"] = spec(
            (cfg.d_model, cfg.d_model), ("embed", "embed_out"), dt_
        )
    if cfg.is_encdec:
        specs["encoder"] = {
            "segments": [
                stack_tree(blk.block_spec("E", cfg), cfg.num_encoder_layers)
            ],
            "final_norm": norm_spec("layernorm", cfg.d_model, dt_),
        }
    return specs


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def effective_window(cfg: ModelConfig, seq_len: int) -> int:
    """Sliding-window size in effect for this sequence length."""
    if not cfg.attention_window:
        return 0
    if cfg.window_only_for_long and seq_len <= 32_768:
        return 0
    return cfg.attention_window


def _logits(specs_params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = apply_norm(cfg.norm_kind, specs_params["final_norm"], x)
    if cfg.tie_embeddings:
        lg = jnp.einsum("bsd,vd->bsv", x, specs_params["embed"])
    else:
        lg = jnp.einsum("bsd,dv->bsv", x, specs_params["lm_head"])
    # Keep the (B,S,V) tensor vocab-sharded through the loss; unsharded it
    # is tens of GiB per device at production shapes.
    return constrain(lg, ("batch", "seq", "vocab"))


def _vision_positions_3d(n_vis: int, text_len: int, batch: int) -> jnp.ndarray:
    """M-RoPE 3-D ids: vision tokens at t=0 on an h*w grid, then text tokens
    t = 1..text_len with h = w = t (Qwen2-VL convention, simplified)."""
    side = max(int(math.ceil(math.sqrt(n_vis))), 1)
    idx = jnp.arange(n_vis)
    vis = jnp.stack([jnp.zeros_like(idx), idx // side, idx % side], axis=-1)
    t = jnp.arange(text_len) + 1
    txt = jnp.stack([t, t, t], axis=-1)
    pos = jnp.concatenate([vis, txt], axis=0)
    return jnp.broadcast_to(pos[None], (batch, n_vis + text_len, 3)).astype(
        jnp.int32
    )


def embed_tokens(params, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    """Token embeddings, times sqrt(d_model) where the config scales them."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def embed_inputs(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    """Token (+ modality-stub) embedding. Returns (x, positions, pos3d)."""
    tokens = batch["tokens"]
    b = tokens.shape[0]
    x = embed_tokens(params, cfg, tokens)
    if cfg.family == "vlm" and "vision_embeds" in batch:
        vis = jnp.einsum("bnd,de->bne", batch["vision_embeds"].astype(x.dtype),
                         params["vision_proj"])
        x = jnp.concatenate([vis, x], axis=1)
        n_vis, text_len = vis.shape[1], tokens.shape[1]
        pos3d = _vision_positions_3d(n_vis, text_len, b)
        positions = jnp.broadcast_to(
            jnp.arange(n_vis + text_len)[None], (b, n_vis + text_len)
        )
        return x, positions, pos3d
    s = tokens.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    pos3d = None
    return x, positions, pos3d


def run_encoder(params, cfg: ModelConfig, src: jnp.ndarray) -> jnp.ndarray:
    """Seamless-style encoder over precomputed (stub) frame embeddings."""
    x = constrain(src.astype(jnp.dtype(cfg.dtype)), _HID)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    ctx = blk.SeqContext(positions, None, 0, 0)

    def body(carry, layer_params):
        h, = carry
        h = blk.block_apply_seq("E", layer_params, h, ctx, cfg)[0]
        return (constrain(h, _HID),), None

    if cfg.block_remat:
        body = jax.checkpoint(body)
    (x,), _ = jax.lax.scan(
        body, (x,), params["encoder"]["segments"][0],
        unroll=cfg.num_encoder_layers if cfg.scan_unroll else 1,
    )
    return apply_norm("layernorm", params["encoder"]["final_norm"], x)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def forward_seq(
    params,
    cfg: ModelConfig,
    batch: Dict[str, jnp.ndarray],
    *,
    cache_len: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[List[Any]]]:
    """Returns (logits, aux_loss, caches). ``cache_len`` > 0 builds decode
    caches (prefill mode)."""
    plan = segment_plan(cfg)
    enc_out = None
    if cfg.is_encdec:
        enc_out = run_encoder(params, cfg, batch["src_frames"])

    x, positions, pos3d = embed_inputs(params, cfg, batch)
    x = constrain(x, _HID)
    window = effective_window(cfg, x.shape[1])
    ctx = blk.SeqContext(positions, pos3d, window, cache_len, enc_out)

    aux_total = jnp.zeros((), jnp.float32)
    caches: List[Any] = []
    for seg, seg_params in zip(plan, params["segments"]):
        if seg.shared:
            x, aux, cache, _ = blk.block_apply_seq(
                "A", params["shared_attn"], x, ctx, cfg
            )
            aux_total += aux
            caches.append(cache)
            continue

        def body(carry, layer_params, kind=seg.kind):
            h, aux_acc = carry
            h, aux, cache, _ = blk.block_apply_seq(kind, layer_params, h, ctx,
                                                   cfg)
            return (constrain(h, _HID), aux_acc + aux), cache

        if cfg.block_remat:
            body = jax.checkpoint(body)
        (x, aux_total), cache_stack = jax.lax.scan(
            body, (x, aux_total), seg_params,
            unroll=seg.count if cfg.scan_unroll else 1,
        )
        caches.append(cache_stack)

    logits = _logits(params, cfg, x)
    return logits, aux_total, (caches if cache_len else None)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_caches(cfg: ModelConfig, batch: int, cache_len: int,
                enc_len: int = 0) -> List[Any]:
    """Zero decode caches; structure mirrors forward_seq's cache output."""
    plan = segment_plan(cfg)
    dtype = jnp.dtype(cfg.dtype)
    caches = []
    for seg in plan:
        one = blk.init_block_cache(seg.kind, cfg, batch, cache_len, dtype,
                                   enc_len)
        if seg.shared:
            caches.append(one)
        else:
            caches.append(
                jax.tree.map(lambda a: jnp.broadcast_to(
                    a[None], (seg.count,) + a.shape
                ).copy() if hasattr(a, "shape") else a, one)
            )
    return caches


def cache_logical_axes(cfg: ModelConfig) -> List[Any]:
    """Logical-axis tree mirroring ``init_caches`` output structure."""
    plan = segment_plan(cfg)
    out = []
    for seg in plan:
        axes = blk.block_cache_axes(seg.kind, cfg)
        if seg.shared:
            out.append(axes)
        else:
            out.append(jax.tree.map(
                lambda a: ("layers",) + a,
                axes,
                is_leaf=lambda x: isinstance(x, tuple) and all(
                    isinstance(e, (str, type(None))) for e in x
                ),
            ))
    return out


def decode_step(
    params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,      # (B, 1)
    pos: jnp.ndarray,         # () int32
    caches: List[Any],
) -> Tuple[jnp.ndarray, List[Any]]:
    """One decode step. Returns (logits (B,1,V), new caches)."""
    plan = segment_plan(cfg)
    x = constrain(embed_tokens(params, cfg, tokens), _HID)
    window = effective_window(cfg, int(_decode_seq_hint(cfg, caches)))
    ctx = _decode_context(cfg, x, pos, window)
    runs = [(sj, 0, seg.count) for sj, seg in enumerate(plan)]
    x, new_caches, _ = decode_blocks(params, cfg, x, ctx, runs, caches)
    return _logits(params, cfg, x), new_caches


def _decode_context(cfg: ModelConfig, x, pos, window: int):
    pos3d = None
    if cfg.rope_kind == "mrope":
        p = jnp.broadcast_to(pos, (x.shape[0], 1))
        pos3d = jnp.stack([p, p, p], axis=-1)
    return blk.DecodeContext(pos, window, pos3d)


def _runs_params(params, seg, sj: int, lo: int, hi: int):
    """Layers ``[lo, hi)`` of segment ``sj``'s stacked weights."""
    seg_params = params["segments"][sj]
    if (lo, hi) != (0, seg.count):
        seg_params = _slice_layers(seg_params, lo, hi)
    return seg_params


def _stack_routes(routes: List[Any]):
    """The expert layers' routes, in layer order, as one (expert layers,
    B, S, k) int32 array; None where the runs hold no expert layer."""
    return jnp.concatenate(routes) if routes else None


def decode_blocks(params, cfg: ModelConfig, x, ctx, runs, caches):
    """One token through layers ``[lo, hi)`` of each segment ``sj`` in
    ``runs`` (a shared block runs whole), each with its cache. Returns (x,
    new caches, routes): each expert layer's chosen expert ids (expert
    layers, B, 1, k) int32, None where the runs hold no expert layer."""
    plan = segment_plan(cfg)
    new_caches: List[Any] = []
    routes: List[Any] = []
    for (sj, lo, hi), cache in zip(runs, caches):
        seg = plan[sj]
        if seg.shared:
            x, new_c, _ = blk.block_apply_decode(
                "A", params["shared_attn"], x, cache, ctx, cfg
            )
            new_caches.append(new_c)
            continue

        def body(h, xs, kind=seg.kind):
            layer_params, layer_cache = xs
            h, new_c, r = blk.block_apply_decode(kind, layer_params, h,
                                                 layer_cache, ctx, cfg)
            return constrain(h, _HID), (new_c, r)

        x, (cache_stack, r) = jax.lax.scan(
            body, x, (_runs_params(params, seg, sj, lo, hi), cache),
            unroll=(hi - lo) if cfg.scan_unroll else 1,
        )
        new_caches.append(cache_stack)
        if r is not None:
            routes.append(r)
    return x, new_caches, _stack_routes(routes)


def seq_blocks(params, cfg: ModelConfig, x, ctx, runs):
    """A sequence through layers ``[lo, hi)`` of each segment ``sj`` in
    ``runs`` (a shared block runs whole), building their caches. Returns
    (x, caches, routes): each expert layer's chosen expert ids (expert
    layers, B, S, k) int32, None where the runs hold no expert layer."""
    plan = segment_plan(cfg)
    caches: List[Any] = []
    routes: List[Any] = []
    for sj, lo, hi in runs:
        seg = plan[sj]
        if seg.shared:
            x, _, cache, _ = blk.block_apply_seq(
                "A", params["shared_attn"], x, ctx, cfg
            )
            caches.append(cache)
            continue

        def body(carry, layer_params, kind=seg.kind):
            h, = carry
            h, _, cache, r = blk.block_apply_seq(kind, layer_params, h, ctx,
                                                 cfg)
            return (constrain(h, _HID),), (cache, r)

        (x,), (cache_stack, r) = jax.lax.scan(
            body, (x,), _runs_params(params, seg, sj, lo, hi),
            unroll=(hi - lo) if cfg.scan_unroll else 1,
        )
        caches.append(cache_stack)
        if r is not None:
            routes.append(r)
    return x, caches, _stack_routes(routes)


def _decode_seq_hint(cfg: ModelConfig, caches) -> int:
    """Recover the nominal sequence length from attention cache shapes (used
    only to pick the window; SSM-only models return 0)."""
    for seg_cache in caches:
        if isinstance(seg_cache, dict) and "k" in seg_cache:
            k = seg_cache["k"]
            return k.shape[-3] if k.ndim >= 4 else 0
    return 0


# ---------------------------------------------------------------------------
# Token-level head/tail split (streaming decode across the JALAD cut)
# ---------------------------------------------------------------------------
#
# The one-shot decoupling in repro.models.api (_transformer_head/_tail) cuts
# a single forward pass. Token streaming cuts the *decode loop*: every step
# the edge runs blocks [0, point], ships the (B, 1, d) boundary row, and the
# cloud resumes at block point+1 — each side holding only its own KV/state
# caches. The functions below mirror forward_seq / decode_step block for
# block so the split loop is bit-identical to the unsplit one up to the
# boundary codec's value transform.


def point_to_segment(cfg: ModelConfig, point: int) -> Tuple[int, int]:
    """Map a global decoupling point to (segment index, offset in segment)."""
    acc = 0
    for si, seg in enumerate(segment_plan(cfg)):
        if point < acc + seg.count:
            return si, point - acc
        acc += seg.count
    raise IndexError(point)


def check_streamable(cfg: ModelConfig) -> None:
    """Families whose decode needs per-token extras beyond the boundary row
    (encoder output, vision positions) cannot stream over the cut."""
    if cfg.is_encdec or cfg.family == "vlm":
        raise ValueError(
            "token streaming ships only the boundary hidden row per token; "
            f"family {cfg.family!r} needs per-token extras (encoder output / "
            "vision positions) that are not part of the streaming wire format"
        )


def _head_segments(cfg: ModelConfig, point: int) -> List[Tuple[int, int]]:
    """(segment index, layer count) pairs the head runs, in order. The cut
    segment runs ``off + 1`` layers (a shared 'A' cut runs whole: count 1)."""
    plan = segment_plan(cfg)
    si, off = point_to_segment(cfg, point)
    return [(sj, plan[sj].count if sj < si else off + 1)
            for sj in range(si + 1)]


def _tail_segments(cfg: ModelConfig, point: int) -> List[Tuple[int, int]]:
    """(segment index, start layer) pairs the tail resumes at. The cut
    segment resumes at ``off + 1``; segments the head consumed entirely
    (including a shared cut block) are skipped."""
    plan = segment_plan(cfg)
    si, off = point_to_segment(cfg, point)
    out: List[Tuple[int, int]] = []
    for sj in range(si, len(plan)):
        seg = plan[sj]
        lo = off + 1 if sj == si else 0
        if (seg.shared and sj == si) or lo >= seg.count:
            continue
        out.append((sj, lo))
    return out


def _sliced_cache_list(cfg: ModelConfig, batch: int, cache_len: int,
                       pairs: List[Tuple[int, int]], head: bool) -> List[Any]:
    plan = segment_plan(cfg)
    dtype = jnp.dtype(cfg.dtype)
    caches: List[Any] = []
    for sj, k in pairs:
        seg = plan[sj]
        count = k if head else seg.count - k
        one = blk.init_block_cache(seg.kind, cfg, batch, cache_len, dtype, 0)
        if seg.shared:
            caches.append(one)
        else:
            caches.append(jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[None], (count,) + a.shape
                ).copy() if hasattr(a, "shape") else a, one))
    return caches


def init_head_caches(cfg: ModelConfig, batch: int, cache_len: int,
                     point: int) -> List[Any]:
    """Zero edge-side caches: blocks [0, point] only."""
    check_streamable(cfg)
    return _sliced_cache_list(cfg, batch, cache_len,
                              _head_segments(cfg, point), head=True)


def init_tail_caches(cfg: ModelConfig, batch: int, cache_len: int,
                     point: int) -> List[Any]:
    """Zero cloud-side caches: blocks [point+1, end). Built from the
    cloud-side config, so ``cfg.kv_cache_bits == 8`` stores int8 codes +
    per-(position, kv-head) float32 scales (see ``blocks._kv_cache_entry``)."""
    check_streamable(cfg)
    return _sliced_cache_list(cfg, batch, cache_len,
                              _tail_segments(cfg, point), head=False)


def _slice_layers(seg_params, lo: int, hi: int):
    return jax.tree.map(lambda a: a[lo:hi], seg_params)


def prefill_head(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
                 cache_len: int, point: int):
    """Edge prefill: run blocks [0, point] over the prompt, building only
    the head's decode caches. Returns (boundary (B, S, d), head_caches,
    routes (``seq_blocks``))."""
    check_streamable(cfg)
    x, positions, pos3d = embed_inputs(params, cfg, batch)
    x = constrain(x, _HID)
    window = effective_window(cfg, x.shape[1])
    ctx = blk.SeqContext(positions, pos3d, window, cache_len, None)
    runs = [(sj, 0, count) for sj, count in _head_segments(cfg, point)]
    return seq_blocks(params, cfg, x, ctx, runs)


def prefill_tail(params, cfg: ModelConfig, boundary: jnp.ndarray,
                 cache_len: int, point: int):
    """Cloud prefill: resume at block point+1 from the decoded boundary,
    building the tail's decode caches. Positions are rebuilt from the
    boundary shape (decoder-only streams: plain arange). Returns
    (logits (B, S, V), tail_caches, routes (``seq_blocks``))."""
    check_streamable(cfg)
    plan = segment_plan(cfg)
    b, s = boundary.shape[0], boundary.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    window = effective_window(cfg, s)
    ctx = blk.SeqContext(positions, None, window, cache_len, None)
    x = constrain(boundary, _HID)
    runs = [(sj, lo, plan[sj].count) for sj, lo in _tail_segments(cfg, point)]
    x, caches, routes = seq_blocks(params, cfg, x, ctx, runs)
    return _logits(params, cfg, x), caches, routes


def decode_head(params, cfg: ModelConfig, tokens: jnp.ndarray,
                pos: jnp.ndarray, head_caches: List[Any], point: int,
                seq_hint: int):
    """Edge half of one decode step: blocks [0, point] on one new token.
    ``seq_hint`` is the nominal sequence length (the shared cache length),
    passed explicitly because the head's caches may not include an
    attention cache to recover it from. Returns (boundary (B, 1, d),
    new head caches, routes (``decode_blocks``))."""
    x = constrain(embed_tokens(params, cfg, tokens), _HID)
    ctx = _decode_context(cfg, x, pos, effective_window(cfg, seq_hint))
    runs = [(sj, 0, count) for sj, count in _head_segments(cfg, point)]
    return decode_blocks(params, cfg, x, ctx, runs, head_caches)


def decode_tail(params, cfg: ModelConfig, boundary: jnp.ndarray,
                pos: jnp.ndarray, tail_caches: List[Any], point: int,
                seq_hint: int):
    """Cloud half of one decode step: resume at block point+1 from the
    decoded (B, 1, d) boundary row. Returns (logits (B, 1, V), new tail
    caches, routes (``decode_blocks``))."""
    plan = segment_plan(cfg)
    x = constrain(boundary, _HID)
    ctx = _decode_context(cfg, x, pos, effective_window(cfg, seq_hint))
    runs = [(sj, lo, plan[sj].count) for sj, lo in _tail_segments(cfg, point)]
    x, new_caches, routes = decode_blocks(params, cfg, x, ctx, runs,
                                          tail_caches)
    return _logits(params, cfg, x), new_caches, routes


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def next_token_loss(logits: jnp.ndarray, tokens: jnp.ndarray,
                    aux: jnp.ndarray, cfg: ModelConfig,
                    text_offset: int = 0) -> jnp.ndarray:
    """Causal LM loss; ``text_offset`` skips modality-prefix positions."""
    lg = logits[:, text_offset:, :]
    pred = lg[:, :-1].astype(jnp.float32)
    tgt = tokens[:, 1:]
    logz = jax.nn.logsumexp(pred, axis=-1)
    gold = jnp.take_along_axis(pred, tgt[..., None], axis=-1)[..., 0]
    nll = (logz - gold).mean()
    return nll + cfg.router_aux_loss * aux
