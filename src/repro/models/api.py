"""Public model API.

``build_model(cfg)`` returns a :class:`Model` with a uniform surface for
training, serving, JALAD decoupling, the multi-pod dry-run and the latency
model — for every architecture family including the paper's CNN testbed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.types import INPUT_SHAPES, ModelConfig, ShapeConfig
from repro.models import cnn as cnn_lib
from repro.models import transformer as tf_lib
from repro.models.init import abstractify, materialize, logical_axes
from repro.utils.tree import tree_param_count


@dataclass
class Model:
    cfg: ModelConfig
    specs: Any                                     # ParamSpec tree

    # ------------------------------------------------------------- params
    def init(self, rng, shardings: Any = None) -> Any:
        """Random parameters from ``rng``. With ``shardings`` (a
        NamedSharding tree, e.g. ``serving.meshed.param_shardings``) every
        leaf is created in its sharded layout, so no single device ever
        holds the whole tree."""
        if shardings is None:
            return materialize(self.specs, rng)
        return jax.jit(lambda r: materialize(self.specs, r),
                       out_shardings=shardings)(rng)

    def head_params(self, params, point: int) -> Any:
        """The part of ``params`` that ``run_head(..., point)`` reads —
        what the edge device holds: the layers up to the cut, without the
        output head."""
        cfg = self.cfg
        if cfg.family == "cnn":
            layers = cnn_lib.build_layers(cfg)[: point + 1]
            return {lyr.name: params[lyr.name] for lyr in layers}
        plan = tf_lib.segment_plan(cfg)
        si, off = _point_to_segment(cfg, point)
        segments = []
        for sj, (seg, seg_params) in enumerate(zip(plan,
                                                   params["segments"])):
            count = seg.count if sj < si else off + 1 if sj == si else 0
            segments.append(seg_params if seg.shared
                            else _slice_seg(seg_params, 0, count))
        out = {k: v for k, v in params.items() if k != "lm_head"}
        out["segments"] = segments
        return out

    def abstract_params(self) -> Any:
        return abstractify(self.specs)

    def param_logical_axes(self) -> Any:
        return logical_axes(self.specs)

    def param_count(self) -> int:
        return tree_param_count(self.abstract_params())

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only routed experts)."""
        cfg = self.cfg
        total = self.param_count()
        if cfg.num_experts:
            per_expert = (
                cfg.d_model * cfg.moe_d_ff_ * 3
            )
            moe_layers = tf_lib.default_pattern(cfg).count("e")
            inactive = (
                moe_layers
                * (cfg.experts_held_ - _routed_held(cfg))
                * per_expert
            )
            return round(total - inactive)
        return total

    # ------------------------------------------------------------ entries
    def loss_fn(self, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.family == "cnn":
            layers = cnn_lib.build_layers(cfg)
            logits = cnn_lib.cnn_forward(layers, params, batch["images"])
            labels = batch["labels"]
            lg = logits.astype(jnp.float32)
            logz = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
            return (logz - gold).mean()
        logits, aux, _ = tf_lib.forward_seq(params, cfg, batch)
        offset = 0
        if cfg.family == "vlm" and "vision_embeds" in batch:
            offset = batch["vision_embeds"].shape[1]
        return tf_lib.next_token_loss(logits, batch["tokens"], aux, cfg,
                                      text_offset=offset)

    def forward(self, params, batch):
        cfg = self.cfg
        if cfg.family == "cnn":
            layers = cnn_lib.build_layers(cfg)
            return cnn_lib.cnn_forward(layers, params, batch["images"])
        logits, _, _ = tf_lib.forward_seq(params, cfg, batch)
        return logits

    def prefill(self, params, batch, cache_len: int):
        logits, aux, caches = tf_lib.forward_seq(
            params, self.cfg, batch, cache_len=cache_len
        )
        return logits, caches

    def decode_step(self, params, tokens, pos, caches):
        return tf_lib.decode_step(params, self.cfg, tokens, pos, caches)

    def init_caches(self, batch: int, cache_len: int, enc_len: int = 0):
        return tf_lib.init_caches(self.cfg, batch, cache_len, enc_len)

    # ------------------------------------------------------- input specs
    def cache_len_for(self, seq_len: int) -> int:
        w = tf_lib.effective_window(self.cfg, seq_len)
        return min(seq_len, w) if w else seq_len

    def enc_len_for(self, seq_len: int) -> int:
        return seq_len // 4 if self.cfg.is_encdec else 0

    def vis_len_for(self, seq_len: int) -> int:
        if self.cfg.family != "vlm":
            return 0
        return min(self.cfg.num_vision_tokens, max(seq_len // 4, 16))

    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for the dry-run (no allocation).

        train/prefill: full batch of sequences (+ modality stubs).
        decode: one new token per sequence + the KV/state caches.
        """
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        f32 = jnp.float32
        i32 = jnp.int32
        act = jnp.dtype(cfg.dtype)

        if cfg.family == "cnn":
            return {
                "images": jax.ShapeDtypeStruct(
                    (b, 3, cfg.image_size, cfg.image_size), f32
                ),
                "labels": jax.ShapeDtypeStruct((b,), i32),
            }

        if shape.mode in ("train", "prefill"):
            batch: Dict[str, Any] = {}
            text_len = s
            if cfg.family == "vlm":
                n_vis = self.vis_len_for(s)
                text_len = s - n_vis
                batch["vision_embeds"] = jax.ShapeDtypeStruct(
                    (b, n_vis, cfg.d_model), act
                )
            batch["tokens"] = jax.ShapeDtypeStruct((b, text_len), i32)
            if cfg.is_encdec:
                batch["src_frames"] = jax.ShapeDtypeStruct(
                    (b, self.enc_len_for(s), cfg.d_model), act
                )
            return batch

        # decode: one token + caches of length cache_len_for(seq).
        cache_len = self.cache_len_for(s)
        caches = jax.eval_shape(
            lambda: self.init_caches(b, cache_len, self.enc_len_for(s))
        )
        return {
            "tokens": jax.ShapeDtypeStruct((b, 1), i32),
            "pos": jax.ShapeDtypeStruct((), i32),
            "caches": caches,
        }

    def batch_logical_axes(self, shape: ShapeConfig) -> Dict[str, Any]:
        """Logical-axis tree matching ``input_specs(shape)`` structure,
        consumed by ``repro.sharding.rules.shardings_for_specs``."""
        cfg = self.cfg
        if cfg.family == "cnn":
            return {
                "images": ("batch", None, None, None),
                "labels": ("batch",),
            }
        if shape.mode in ("train", "prefill"):
            axes: Dict[str, Any] = {"tokens": ("batch", "seq")}
            if cfg.family == "vlm":
                axes["vision_embeds"] = ("batch", "seq", "embed")
            if cfg.is_encdec:
                axes["src_frames"] = ("batch", "enc_seq", "embed")
            return axes
        return {
            "tokens": ("batch", None),
            "pos": (),
            "caches": tf_lib.cache_logical_axes(cfg),
        }

    # ------------------------------------------------ decoupling (JALAD)
    def decoupling_points(self) -> List[str]:
        cfg = self.cfg
        if cfg.family == "cnn":
            return [l.name for l in cnn_lib.build_layers(cfg)]
        plan = tf_lib.segment_plan(cfg)
        names = []
        for si, seg in enumerate(plan):
            for li in range(seg.count):
                names.append(f"seg{si}_{seg.kind}{li}")
        return names

    def run_head(self, params, batch, point: int):
        """Run layers [0, point] and return the boundary activation.

        For CNNs this is the raw layer output; for transformers the hidden
        state after block ``point`` (plus encoder output if the model is
        enc-dec and the cut is inside the decoder)."""
        cfg = self.cfg
        if cfg.family == "cnn":
            layers = cnn_lib.build_layers(cfg)
            return cnn_lib.cnn_forward(layers, params, batch["images"],
                                       upto=point + 1)
        return _transformer_head(self, params, batch, point)

    def run_heads(self, params, batch, points) -> List[Tuple[Any, Any]]:
        """Boundaries at several decoupling points from ONE forward pass,
        as ``(boundary, extras)`` pairs in ``points`` order.

        For CNNs this taps the activation after each requested layer in a
        single sweep — calling ``run_head`` per point re-runs the shared
        prefix, O(N^2) layer executions over a calibration grid. Other
        families fall back to per-point ``run_head`` (normalized to
        pairs); traced inside one jitted program that is still a single
        dispatch. This is the calibration pipeline's head stage."""
        pts = list(points)
        if not pts:
            return []
        cfg = self.cfg
        if cfg.family == "cnn":
            layers = cnn_lib.build_layers(cfg)
            want = set(pts)
            taps: Dict[int, Any] = {}
            x = batch["images"]
            for i, lyr in enumerate(layers[: max(want) + 1]):
                x = lyr.apply(params[lyr.name], x)
                if i in want:
                    taps[i] = x
            return [(taps[p], None) for p in pts]
        outs = [self.run_head(params, batch, p) for p in pts]
        return [o if isinstance(o, tuple) else (o, None) for o in outs]

    def boundary_logical_axes(self, ndim: int):
        """Logical axis names of the boundary activation crossing the cut
        (rank ``ndim``). The meshed cloud worker pins these on entry:
        batch resolves to the "data" mesh axis per the rule table; the
        remaining activation dims (spatial / seq / embed) stay replicated
        so the NamedSharding-annotated params carry the "model" axis."""
        if self.cfg.family == "cnn":
            return ("batch",) + (None,) * (ndim - 1)
        return ("batch", "seq", "embed")[:ndim] + (None,) * max(0, ndim - 3)

    def run_tail(self, params, boundary, point: int, extras=None):
        cfg = self.cfg
        if cfg.family == "cnn":
            layers = cnn_lib.build_layers(cfg)
            return cnn_lib.cnn_forward(layers, params, boundary,
                                       start=point + 1)
        return _transformer_tail(self, params, boundary, point, extras)

    def run_segment(self, params, boundary, from_point: int, to_point: int,
                    extras=None):
        """Run the middle tier of a three-way split: layers
        ``(from_point, to_point]`` on the boundary produced by
        ``run_head(..., from_point)``. The result is the boundary that
        ``run_tail(..., to_point)`` resumes from, so

            run_tail(run_segment(run_head(x, i1), i1, i2), i2)

        equals the full forward pass. ``from_point == to_point`` is the
        degenerate (relay) middle tier and returns ``boundary`` unchanged.
        For transformers the return is ``(boundary2, extras)`` — the same
        extras dict, since positions/encoder output are cut-invariant."""
        if to_point < from_point:
            raise ValueError(f"segment requires from_point <= to_point, got "
                             f"({from_point}, {to_point})")
        cfg = self.cfg
        if cfg.family == "cnn":
            if to_point == from_point:
                return boundary
            layers = cnn_lib.build_layers(cfg)
            return cnn_lib.cnn_forward(layers, params, boundary,
                                       start=from_point + 1,
                                       upto=to_point + 1)
        if to_point == from_point:
            return boundary, extras
        return _transformer_segment(self, params, boundary, from_point,
                                    to_point, extras)

    # -------------------------------------- token streaming (JALAD decode)
    def _check_token_split(self) -> None:
        if self.cfg.family == "cnn":
            raise ValueError("token streaming is autoregressive decode; "
                             "CNNs decouple per request (run_head/run_tail)")
        tf_lib.check_streamable(self.cfg)

    def prefill_head(self, params, batch, cache_len: int, point: int):
        """Edge prefill of blocks [0, point]; returns (boundary, caches,
        routes): its expert layers' chosen expert ids (layers, B, S, k),
        None where it holds none."""
        self._check_token_split()
        return tf_lib.prefill_head(params, self.cfg, batch, cache_len, point)

    def prefill_tail(self, params, boundary, cache_len: int, point: int):
        """Cloud prefill resuming at block point+1 from the decoded
        boundary; returns (logits, caches, routes)."""
        self._check_token_split()
        return tf_lib.prefill_tail(params, self.cfg, boundary, cache_len,
                                   point)

    def decode_head(self, params, tokens, pos, head_caches, point: int,
                    seq_hint: int):
        """Edge half of one decode step; returns (boundary (B,1,d),
        new head caches, routes (layers, B, 1, k) or None)."""
        return tf_lib.decode_head(params, self.cfg, tokens, pos, head_caches,
                                  point, seq_hint)

    def decode_tail(self, params, boundary, pos, tail_caches, point: int,
                    seq_hint: int):
        """Cloud half of one decode step; returns (logits (B,1,V),
        new tail caches, routes (layers, B, 1, k) or None)."""
        return tf_lib.decode_tail(params, self.cfg, boundary, pos,
                                  tail_caches, point, seq_hint)

    def init_head_caches(self, batch: int, cache_len: int, point: int
                         ) -> List[Any]:
        self._check_token_split()
        return tf_lib.init_head_caches(self.cfg, batch, cache_len, point)

    def init_tail_caches(self, batch: int, cache_len: int, point: int
                         ) -> List[Any]:
        self._check_token_split()
        return tf_lib.init_tail_caches(self.cfg, batch, cache_len, point)

    # --------------------------------------------------- latency model IO
    def per_point_fmacs(self, batch: int, seq_len: int = 0) -> List[float]:
        """FMACs of each decoupling segment (layer i's own compute)."""
        cfg = self.cfg
        if cfg.family == "cnn":
            return [f * batch for f in
                    cnn_lib.layer_fmacs(cnn_lib.build_layers(cfg))]
        per_block = _block_fmacs_per_token(cfg)
        tokens = batch * seq_len
        return [f * tokens for f in per_block]

    def boundary_bytes(self, batch: int, seq_len: int = 0,
                       bytes_per_val: int = 4) -> List[int]:
        """Raw boundary feature size after each decoupling point."""
        cfg = self.cfg
        if cfg.family == "cnn":
            return cnn_lib.feature_bytes(cnn_lib.build_layers(cfg), batch,
                                         bytes_per_val)
        n = len(self.decoupling_points())
        return [batch * seq_len * cfg.d_model * bytes_per_val] * n

    def model_flops(self, tokens_or_samples: int) -> float:
        """6·N·D (dense) / 6·N_active·D (MoE); CNN: 2·FMACs."""
        cfg = self.cfg
        if cfg.family == "cnn":
            total = sum(cnn_lib.layer_fmacs(cnn_lib.build_layers(cfg)))
            return 2.0 * total * tokens_or_samples
        return 6.0 * self.active_param_count() * tokens_or_samples

    def analytic_step_flops(self, shape: ShapeConfig,
                            block_remat: bool = False) -> float:
        """Precise matmul FLOPs of one compiled step of this shape (global,
        all chips). Used for the roofline compute term because XLA's
        cost_analysis counts rolled scan bodies once (the attention chunk
        scans stay rolled even in the unrolled dry-run).

        fwd = matmul 2*FMACs + attention quadratic; train = fwd * 3
        (bwd 2x), +1 fwd if per-block remat recomputes the forward."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        if cfg.family == "cnn":
            per = sum(cnn_lib.layer_fmacs(cnn_lib.build_layers(cfg)))
            fwd = 2.0 * per * b
            return fwd * (4.0 if block_remat else 3.0) \
                if shape.mode == "train" else fwd

        if shape.mode in ("train", "prefill"):
            tokens = b * s
            per_block = _block_fmacs_per_token(cfg)
            fwd = 2.0 * sum(per_block) * tokens
            # attention quadratic: QK^T + PV, full scores (XLA computes the
            # masked half too); windowed -> S*W.
            w = tf_lib.effective_window(cfg, s)
            kv_len = min(s, w) if w else s
            n_attn = sum(1 for k in tf_lib.default_pattern(cfg)
                         if k in ("d", "e", "c"))
            if cfg.shared_attention_every:
                n_attn += len(tf_lib.default_pattern(cfg)) \
                    // cfg.shared_attention_every
            fwd += 4.0 * b * cfg.num_heads * s * kv_len * _qkv_width(cfg) \
                * n_attn
            if cfg.is_encdec:
                enc_s = self.enc_len_for(s)
                enc_tokens = b * enc_s
                enc_fmacs = (cfg.d_model * (cfg.num_heads
                                            + 2 * cfg.num_kv_heads)
                             * cfg.head_dim_
                             + cfg.num_heads * cfg.head_dim_ * cfg.d_model
                             + 2 * cfg.d_model * cfg.d_ff)
                fwd += 2.0 * enc_fmacs * enc_tokens * cfg.num_encoder_layers
                fwd += 4.0 * b * cfg.num_heads * enc_s * enc_s \
                    * cfg.head_dim_ * cfg.num_encoder_layers
                # cross attention over encoder keys
                fwd += 4.0 * b * cfg.num_heads * s * enc_s * cfg.head_dim_ \
                    * len(tf_lib.default_pattern(cfg))
            # logits
            fwd += 2.0 * tokens * cfg.d_model * cfg.vocab_size
            if shape.mode == "prefill":
                return fwd
            return fwd * (4.0 if block_remat else 3.0)

        # decode: one token, attention reads the whole cache.
        per_block = _block_fmacs_per_token(cfg)
        fwd = 2.0 * sum(per_block) * b
        cache_len = self.cache_len_for(s)
        n_attn = sum(1 for k in tf_lib.default_pattern(cfg)
                     if k in ("d", "e", "c"))
        if cfg.shared_attention_every:
            n_attn += len(tf_lib.default_pattern(cfg)) \
                // cfg.shared_attention_every
        fwd += 4.0 * b * cfg.num_heads * cache_len * _qkv_width(cfg) \
            * n_attn
        if cfg.is_encdec:
            fwd += 4.0 * b * cfg.num_heads * self.enc_len_for(s) \
                * cfg.head_dim_ * len(tf_lib.default_pattern(cfg))
        fwd += 2.0 * b * cfg.d_model * cfg.vocab_size
        return fwd


# ---------------------------------------------------------------------------
# Transformer head/tail splitting (block-granular, slices scan'd params)
# ---------------------------------------------------------------------------


def _point_to_segment(cfg: ModelConfig, point: int) -> Tuple[int, int]:
    return tf_lib.point_to_segment(cfg, point)


def _slice_seg(seg_params, lo: int, hi: int):
    return jax.tree.map(lambda a: a[lo:hi], seg_params)


def _transformer_head(model: Model, params, batch, point: int):
    cfg = model.cfg
    plan = tf_lib.segment_plan(cfg)
    si, off = _point_to_segment(cfg, point)

    enc_out = None
    if cfg.is_encdec:
        enc_out = tf_lib.run_encoder(params, cfg, batch["src_frames"])
    x, positions, pos3d = tf_lib.embed_inputs(params, cfg, batch)
    ctx = tf_lib.blk.SeqContext(
        positions, pos3d, tf_lib.effective_window(cfg, x.shape[1]), 0, enc_out
    )

    for sj in range(si + 1):
        seg = plan[sj]
        count = seg.count if sj < si else off + 1
        if seg.shared:
            x = tf_lib.blk.block_apply_seq(
                "A", params["shared_attn"], x, ctx, cfg
            )[0]
            continue
        seg_params = _slice_seg(params["segments"][sj], 0, count)

        def body(carry, layer_params, kind=seg.kind):
            h, = carry
            h = tf_lib.blk.block_apply_seq(kind, layer_params, h, ctx,
                                           cfg)[0]
            return (h,), None

        (x,), _ = jax.lax.scan(body, (x,), seg_params)
    extras = {"positions": positions, "enc_out": enc_out, "pos3d": pos3d}
    return x, extras


def _transformer_tail(model: Model, params, boundary, point: int, extras):
    cfg = model.cfg
    plan = tf_lib.segment_plan(cfg)
    si, off = _point_to_segment(cfg, point)
    x = boundary
    ctx = tf_lib.blk.SeqContext(
        extras["positions"], extras.get("pos3d"),
        tf_lib.effective_window(cfg, x.shape[1]), 0, extras.get("enc_out")
    )
    for sj in range(si, len(plan)):
        seg = plan[sj]
        lo = off + 1 if sj == si else 0
        if lo >= seg.count:
            continue
        if seg.shared:
            if sj == si:   # the cut block itself was already run in the head
                continue
            x = tf_lib.blk.block_apply_seq(
                "A", params["shared_attn"], x, ctx, cfg
            )[0]
            continue
        seg_params = _slice_seg(params["segments"][sj], lo, seg.count)

        def body(carry, layer_params, kind=seg.kind):
            h, = carry
            h = tf_lib.blk.block_apply_seq(kind, layer_params, h, ctx,
                                           cfg)[0]
            return (h,), None

        (x,), _ = jax.lax.scan(body, (x,), seg_params)
    return tf_lib._logits(params, cfg, x)


def _transformer_segment(model: Model, params, boundary, from_point: int,
                         to_point: int, extras):
    """Blocks ``(from_point, to_point]`` — ``_transformer_tail`` bounded at
    the second cut instead of running to the logits."""
    cfg = model.cfg
    plan = tf_lib.segment_plan(cfg)
    si, off = _point_to_segment(cfg, from_point)
    si2, off2 = _point_to_segment(cfg, to_point)
    x = boundary
    ctx = tf_lib.blk.SeqContext(
        extras["positions"], extras.get("pos3d"),
        tf_lib.effective_window(cfg, x.shape[1]), 0, extras.get("enc_out")
    )
    for sj in range(si, si2 + 1):
        seg = plan[sj]
        lo = off + 1 if sj == si else 0
        hi = off2 + 1 if sj == si2 else seg.count
        if lo >= hi:
            continue
        if seg.shared:
            if sj == si:   # the cut block itself was already run upstream
                continue
            x = tf_lib.blk.block_apply_seq(
                "A", params["shared_attn"], x, ctx, cfg
            )[0]
            continue
        seg_params = _slice_seg(params["segments"][sj], lo, hi)

        def body(carry, layer_params, kind=seg.kind):
            h, = carry
            h = tf_lib.blk.block_apply_seq(kind, layer_params, h, ctx,
                                           cfg)[0]
            return (h,), None

        (x,), _ = jax.lax.scan(body, (x,), seg_params)
    return x, extras


def _routed_held(cfg: ModelConfig) -> float:
    """Expected experts of the held share a token is routed to: its k
    choices, each held with probability experts_held / num_experts."""
    return cfg.experts_per_token * cfg.experts_held_ / cfg.num_experts


def _qkv_width(cfg: ModelConfig) -> float:
    """Mean of a head's query-key and value widths: the FMACs of one
    head's scores and weighted values at one position, over 2. Latent
    attention counts them in the published (non-absorbed) form."""
    if cfg.is_mla:
        return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                + cfg.v_head_dim) / 2
    return cfg.head_dim_


def _block_fmacs_per_token(cfg: ModelConfig) -> List[float]:
    """Per-token FMACs of each block (weights touched once per token). An
    expert block counts its router over all experts, the routed experts of
    the held share (expected) and the shared experts."""
    d, hd = cfg.d_model, cfg.head_dim_
    h, kv = cfg.num_heads, cfg.num_kv_heads
    out: List[float] = []
    attn = d * (h + 2 * kv) * hd + h * hd * d       # qkv + out proj
    if cfg.is_mla:
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        r, vd = cfg.kv_lora_rank, cfg.v_head_dim
        attn = (d * h * (nope + rope) + d * (r + rope)
                + r * h * (nope + vd) + h * vd * d)
    dense_mlp = 3.0 * d * cfg.d_ff
    f = cfg.moe_d_ff_
    moe_mlp = (3.0 * d * f * (_routed_held(cfg) + cfg.n_shared_experts)
               if cfg.num_experts else 0.0)
    for kind in tf_lib.default_pattern(cfg):
        if kind == "d":
            out.append(attn + dense_mlp)
        elif kind == "e":
            out.append(attn + moe_mlp + d * cfg.num_experts)
        elif kind == "m":
            from repro.models.layers.mamba2 import mamba_dims
            dims = mamba_dims(cfg)
            out.append(
                d * (2 * dims.d_inner + 2 * dims.state + dims.heads)
                + dims.d_inner * d
            )
        elif kind in ("l", "s"):
            di = cfg.ssm_expand * d
            if kind == "l":
                out.append(d * 2 * di + 3 * di * di + di * d)
            else:
                out.append(4 * d * d + 4 * d * (d // max(cfg.num_heads, 1))
                           + 2 * d * int(4 / 3 * d))
        elif kind == "c":
            out.append(2 * attn + 3.0 * d * cfg.d_ff)
        else:
            out.append(attn + dense_mlp)
    if cfg.shared_attention_every:
        # insert shared block cost after every period
        shared_cost = attn + dense_mlp
        merged: List[float] = []
        for i, c in enumerate(out):
            merged.append(c)
            if (i + 1) % cfg.shared_attention_every == 0:
                merged.append(shared_cost)
        out = merged
    return out


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "cnn":
        specs = cnn_lib.cnn_param_specs(cfg)
    else:
        specs = tf_lib.param_specs(cfg)
    return Model(cfg=cfg, specs=specs)
