"""Token-level decoupled serving: the JALAD cut inside the decode loop.

One-shot decoupling (``DecoupledRunner``) ships the boundary once per
request. The commercially real workload is autoregressive generation,
where a small ``(1, 1, d_model)`` boundary row crosses the link *every
token* — a regime where per-token fixed costs (host framing, kernel
launches, scheduler host syncs) dominate end-to-end latency (Auto-Split,
arXiv:2108.13041). :class:`TokenStreamSession` extends the continuous
batching engine so the decode loop itself runs across the cut:

* **Split state.** Each slot carries *head* caches (edge side, first
  ``point + 1`` blocks, full precision) and *tail* caches (cloud side,
  remaining blocks, int8-quantized KV by default — the
  ``kv_cache_bits=8`` machinery wired into serving, with a bytes-halved
  check at session construction).
* **Amortized wire.** Per engine step the head halves of ALL active
  slots run as one vmapped decode, their boundary rows are encoded in
  **one** batched ``encode_batch`` (a single fused Pallas launch for
  the fixed-rate device codecs; two device dispatches — histogram +
  pack — for huffman's device-resident entropy encode, never a
  per-slot loop), decoded in one ``decode_batch``, and the tail halves advance
  in one vmapped decode. Token selection keeps the scheduler's single
  host-sync-per-step property; the wire adds exactly one more host
  round-trip per step, never one per slot.
* **One donated program per side.** Each side of the cut is one jitted
  program over the whole stacked slot state, its caches donated: the
  head program returns the advanced caches and every slot's boundary row
  as its own output (the host picks the live ones with no launch); the
  tail program scatters the decoded rows into their slots, decodes, and
  does the masked cache advance, the position advance and the gather of
  the live logits rows. Both take the slot mask and order as operands,
  so they compile once per session and the step launches the same
  handful of programs whatever the number of live slots.
* **Served routing.** Where the model routes over experts, each side's
  program also returns the experts each token chose in each of its expert
  layers (a join's prompt, a step's one token a slot). They ride in the
  select's fetch (no extra sync, no extra launch) and reach
  ``_record_routes``; a decode step's are counted there, over the live
  slots and the side's expert layers, as the stats of a span
  ``jalad.moe.route(side, routes, experts_hit, rows)``: the routed
  (token, held-expert) pairs, the held experts at least one of them hit,
  and the cache positions the live slots read.
* **Streaming wire format.** A per-session
  :class:`~repro.codec.base.StreamHeader` pins (codec, bits, frame
  shape) once at session open, so every subsequent frame costs
  ``WireBlob.stream_nbytes`` (the per-blob bits tag is amortized away).
* **Bit-identity.** The head/tail split is bitwise-equal to the unsplit
  forward (``tests/test_token_streaming.py``), vmapped slots are
  bitwise-equal to batch-1 (the scheduler contract), and the batched
  codec calls are byte-identical per frame to encoding each row alone —
  so a batched session emits exactly the tokens of serving each
  request's generation loop by itself.

Cross-session batching for the fleet lives in :func:`step_stream_group`:
sessions that agreed on the same (point, bits, codec) plan merge their
per-step boundary rows into ONE encode/decode group — how streaming
slots join the fleet's cloud groups.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import (
    TYPE_CHECKING, Any, List, Optional, Sequence, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.codec import get_codec
from repro.core.decoupler import DecoupledPlan
from repro.models.api import Model
from repro.serving.scheduler import ContinuousBatchingEngine, GenRequest
from repro.utils.trace import mark, span

if TYPE_CHECKING:
    from repro.codec import BoundaryCodec, StreamHeader, WireBlob
    from repro.serving.edge_cloud import EdgeCloudServer, LatencyBreakdown

PlanKey = Tuple[int, int, str]            # (point, bits, codec)


def _tree_nbytes(tree: Any) -> int:
    """Total buffer bytes of a cache tree (works on concrete arrays and
    ``jax.eval_shape`` structs alike)."""
    return sum(
        int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
        for a in jax.tree.leaves(tree) if hasattr(a, "dtype")
    )


@dataclass
class TokenStreamSession(ContinuousBatchingEngine):
    """Continuous batching with the decode loop split at a JALAD cut.

    ``plan`` fixes (point, bits, codec) for the session's lifetime —
    get one from :meth:`JaladEngine.decide_streaming`, which prices the
    per-token steady state. ``cloud_kv_bits=8`` (default) keeps the
    cloud tail's KV cache int8-quantized; ``0`` keeps it full precision.
    """

    plan: Optional[DecoupledPlan] = None
    cloud_kv_bits: int = 8

    def __post_init__(self) -> None:
        if self.plan is None:
            raise ValueError(
                "TokenStreamSession needs a DecoupledPlan (point, bits, "
                "codec) — get one from JaladEngine.decide_streaming")
        if self.plan.is_cloud_only:
            raise ValueError(
                "a cloud-only plan has no boundary stream; serve through "
                "the base ContinuousBatchingEngine instead")
        super().__post_init__()

    # ---------------------------------------------------------- state setup
    def _init_compute(self) -> None:
        model = self.model
        L = self.cfg.max_seq_len
        point = self.plan.point
        cfg_cloud = (model.cfg.replace(kv_cache_bits=self.cloud_kv_bits)
                     if self.cloud_kv_bits else model.cfg)
        # Same weights, different cache handling: the cloud view only
        # changes how tail KV rows are stored (int8 codes + f32 scales).
        self.cloud_model = Model(cfg=cfg_cloud, specs=model.specs)
        self._codec: "BoundaryCodec" = get_codec(self.plan.codec)
        self._cloud_dtype = jnp.dtype(cfg_cloud.dtype)

        # Named, so that the trace reads jit_prefill_head and not a lambda.
        # Routes come out as (slots, expert layers, tokens, k), or None.
        def prefill_head(p, b):
            boundary, caches, routes = model.prefill_head(p, b, L, point)
            return boundary, caches, _one_slot(routes)

        def prefill_tail(p, x):
            logits, caches, routes = self.cloud_model.prefill_tail(
                p, x, L, point)
            return logits, caches, _one_slot(routes)

        slots = (None, 0, 0, 0)
        head = jax.vmap(
            lambda p, t, pos, c: model.decode_head(p, t, pos, c, point, L),
            in_axes=slots)
        tail = jax.vmap(
            lambda p, x, pos, c: self.cloud_model.decode_tail(
                p, x, pos, c, point, L),
            in_axes=slots)
        n = self.cfg.max_batch
        self._frame_shape = (1, 1, int(model.cfg.d_model))

        def decode_head(p, last, pos, caches, mask):
            boundary, new, routes = head(p, last, pos, caches)
            return (self._masked_update(caches, new, mask),
                    tuple(boundary[i] for i in range(n)),
                    _each_slot(routes))

        def decode_tail(p, rows, order, pos, caches, mask):
            x = jnp.zeros((n,) + self._frame_shape, self._cloud_dtype)
            x = x.at[order].set(jnp.stack(rows))
            logits, new, routes = tail(p, x, pos, caches)
            return (self._masked_update(caches, new, mask),
                    jnp.where(mask, pos + 1, pos), logits[order, 0, -1],
                    _each_slot(routes))

        self._prefill_head = jax.jit(prefill_head)
        self._prefill_tail = jax.jit(prefill_tail)
        self._decode_head = jax.jit(decode_head, donate_argnums=3)
        self._decode_tail = jax.jit(decode_tail, donate_argnums=4)
        one_head = model.init_head_caches(1, L, point)
        one_tail = self.cloud_model.init_tail_caches(1, L, point)
        self._head_caches = self._stack_slots(one_head)
        self._tail_caches = self._stack_slots(one_tail)
        # What the tail program reads for a slot with no row this step.
        self._no_row = jnp.zeros(self._frame_shape, self._cloud_dtype)
        # Session-open handshake: (codec, bits, frame shape) ship once,
        # every frame after that costs stream_nbytes.
        self.header: "StreamHeader" = self._codec.open_stream(
            self._frame_shape, self.plan.bits)
        self.bytes_sent: int = self.header.nbytes
        # Routes on the device until the select fetches them: (side,
        # slots, cache rows a decode step reads or None for a join, ids).
        self._routes: List[Tuple[str, List[int], Optional[int],
                                 jnp.ndarray]] = []
        self.encode_groups: List[Tuple[int, List[int]]] = []
        self.tokens_out: int = 0
        self.kv_bytes_ratio: Optional[float] = None
        if self.cloud_kv_bits == 8:
            self.kv_bytes_ratio = self._check_kv_bytes(one_tail, L, point)

    def _check_kv_bytes(self, one_tail: Any, cache_len: int,
                        point: int) -> Optional[float]:
        """The serving-time bytes-halved contract: the int8 tail KV cache
        must cost well under the full-precision bytes (codes shrink 4x
        for f32 models, 2x for bf16; per-row f32 scales add a 1/head_dim
        tax). Returns the measured ratio, or None when the tail holds no
        attention KV to quantize (pure-SSM tails)."""
        if not any(jnp.dtype(a.dtype) == jnp.int8
                   for a in jax.tree.leaves(one_tail)):
            return None
        fp = jax.eval_shape(
            lambda: self.model.init_tail_caches(1, cache_len, point))
        ratio = _tree_nbytes(one_tail) / max(_tree_nbytes(fp), 1)
        if ratio > 0.6:
            raise RuntimeError(
                f"int8 cloud KV cache is {ratio:.2f}x the full-precision "
                "bytes — expected at most 0.6x (bytes-halved contract)")
        return ratio

    # ------------------------------------------------------------ lifecycle
    def _join(self, slot: int, req: GenRequest) -> None:
        """Prefill across the cut: head forward on the edge, the boundary
        sequence through the wire (real encode/decode round trip, counted
        at stream framing cost), tail prefill on the cloud."""
        with span("stream.join", uid=req.uid, prompt_len=len(req.tokens)):
            batch = {"tokens": jnp.asarray(req.tokens[None, :], jnp.int32)}
            with span("stream.prefill_head"):
                boundary, head, head_routes = self._prefill_head(
                    self.params, batch)
            with span("codec.encode", rows=1):
                blob = self._codec.encode(boundary, self.plan.bits)
            self.bytes_sent += blob.stream_nbytes
            with span("codec.decode", rows=1):
                x = self._codec.decode(blob, out_dtype=self._cloud_dtype)
            with span("stream.prefill_tail"):
                logits, tail, tail_routes = self._prefill_tail(self.params,
                                                               x)
            self._keep_routes("head", [slot], head_routes)
            self._keep_routes("tail", [slot], tail_routes)
            self._head_caches = self._write_slot(self._head_caches, head,
                                                 slot)
            self._tail_caches, self._pos = self._write_slot(
                (self._tail_caches, self._pos),
                (tail, np.int32(len(req.tokens))), slot)
            self._seat(slot, req, logits[:, -1])

    def _record_token(self, slot: int, token: int) -> None:
        self.tokens_out += 1
        super()._record_token(slot, token)

    def _evict(self, slot: int) -> None:
        super()._evict(slot)
        # Free the evicted slot's KV rows on BOTH sides of the cut: the
        # buffers are zeroed, and since eviction removes the slot from
        # the active set, the request can never appear in a later
        # batched encode group (asserted in tests).
        self._head_caches = self._zero_slot(self._head_caches, slot)
        self._tail_caches = self._zero_slot(self._tail_caches, slot)

    # --------------------------------------------------------- step phases
    def _head_phase(self, active: List[int]) -> Tuple[
            List[jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]]:
        """Edge half of one step: ONE head program over all slots (vmapped
        decode, masked cache advance). Returns the active slots' boundary
        rows and the step's (mask, order) operands."""
        with span("stream.head", slots=len(active)):
            mask, order = self._slot_order(active)
            self._head_caches, boundary, routes = self._decode_head(
                self.params, self._last, self._pos, self._head_caches, mask)
            self._keep_routes("head", active, routes, decode=True)
            return [boundary[s] for s in active], (mask, order)

    def _keep_routes(self, side: str, slots: List[int],
                     routes: Optional[jnp.ndarray],
                     decode: bool = False) -> None:
        """Hold a side's routes, still on the device, for the select's
        fetch: a join's of its one slot, a decode step's of every slot (to
        be read at the live ``slots``, with the cache positions they
        read)."""
        if routes is None:
            return
        rows = (sum(len(self._slots[s].tokens) + len(self._slots[s].out_tokens)
                    for s in slots) if decode else None)
        self._routes.append((side, slots, rows, routes))

    def _fetch(self, toks: jnp.ndarray) -> np.ndarray:
        """The tokens, and the routes held for this fetch with them."""
        if not self._routes:
            return super()._fetch(toks)
        kept, self._routes = self._routes, []
        toks_np, ids = jax.device_get((toks, [k[-1] for k in kept]))
        for (side, slots, rows, _), a in zip(kept, ids):
            self._record_routes(side, slots,
                                a if rows is None else a[slots], rows)
        return np.asarray(toks_np)

    def _record_routes(self, side: str, slots: List[int], ids: np.ndarray,
                       rows: Optional[int]) -> None:
        """The experts ``slots``' tokens chose, on the host: ids (slots,
        expert layers, tokens, k) of a join's prompt (``rows`` None) or of
        a decode step's one token a slot, whose counts become the stats of
        ``jalad.moe.route``."""
        if rows is None:
            return
        cfg = self.model.cfg
        local = ids - cfg.expert_lo
        held = (local >= 0) & (local < cfg.experts_held_)
        layer = np.broadcast_to(
            np.arange(ids.shape[1])[None, :, None, None], ids.shape)
        hit = np.unique(layer[held] * cfg.experts_held_ + local[held])
        mark("moe.route", side=side, routes=int(held.sum()),
             experts_hit=int(hit.size), rows=rows)

    def _account_encode(self, active: List[int],
                        blobs: Sequence["WireBlob"]) -> List[int]:
        uids = [self._slots[s].uid for s in active]
        self.encode_groups.append((self.step_count, uids))
        self.bytes_sent += sum(b.stream_nbytes for b in blobs)
        return uids

    def _tail_phase(self, active: List[int],
                    sel: Tuple[jnp.ndarray, jnp.ndarray],
                    xs: Sequence[jnp.ndarray]) -> jnp.ndarray:
        """Cloud half: ONE tail program over all slots (the decoded rows
        scattered to their slots, vmapped decode with the int8 KV update
        inside, masked advance). Returns the (k, V) logits rows of the
        active slots."""
        mask, order = sel
        with span("stream.tail", slots=len(active)):
            rows = tuple(xs) + (self._no_row,) * (self.cfg.max_batch
                                                  - len(xs))
            self._tail_caches, self._pos, logits, routes = \
                self._decode_tail(self.params, rows, order, self._pos,
                                  self._tail_caches, mask)
            self._keep_routes("tail", active, routes, decode=True)
            return logits[:len(active)]

    # ------------------------------------------------------------------ step
    def step(self) -> List[GenRequest]:
        """One engine step across the cut: admit, vmapped head decode,
        ONE batched boundary encode (at most two device dispatches for
        any built-in codec — huffman included), ONE batched wire
        decode, vmapped tail decode,
        one batched token select + host sync. Returns the requests that
        finished during this step."""
        with span("stream.step"):
            self.step_count += 1
            done_before = len(self.completed)
            with span("stream.admit"):
                self._admit()
            active = self._active_slots()
            if active:
                rows, sel = self._head_phase(active)
                with span("codec.encode", rows=len(rows)):
                    blobs = self._codec.encode_batch(rows, self.plan.bits)
                self._account_encode(active, blobs)
                with span("codec.decode", rows=len(blobs)):
                    xs = self._codec.decode_batch(
                        blobs, out_dtype=self._cloud_dtype)
                self._finish_step(active, sel[1],
                                  self._tail_phase(active, sel, xs))
            return self.completed[done_before:]

    # ------------------------------------------------------------- protocol
    @property
    def plan_key(self) -> PlanKey:
        return (self.plan.point, self.plan.bits, self.plan.codec)

    def serve(self, server: "EdgeCloudServer",
              bandwidth: float) -> "LatencyBreakdown":
        """One engine step as a bandwidth-trace item — the
        ``EdgeCloudServer.serve_trace`` protocol (see
        :class:`~repro.serving.edge_cloud.Servable`): advance every
        active slot one token, price the step with the planner's
        per-token stage times, and record it on the server's clock."""
        from repro.serving.edge_cloud import LatencyBreakdown

        t0, b0 = self.tokens_out, self.bytes_sent
        self.step()
        k = self.tokens_out - t0
        nbytes = self.bytes_sent - b0
        edge_b, cloud_b = server.engine.plan_space.stage_times(self.plan)
        tpb = server.engine.stream_terms.tokens_per_batch
        bd = LatencyBreakdown(
            edge_b / tpb * k, nbytes / bandwidth, cloud_b / tpb * k,
            int(nbytes), self.plan.point, self.plan.bits, self.plan.codec)
        return server.record(bd)


def _one_slot(routes: Optional[jnp.ndarray]) -> Optional[jnp.ndarray]:
    """A join's routes (layers, 1, S, k) as (1, layers, S, k)."""
    return None if routes is None else jnp.swapaxes(routes, 0, 1)


def _each_slot(routes: Optional[jnp.ndarray]) -> Optional[jnp.ndarray]:
    """The vmapped step's routes (slots, layers, 1, 1, k) as (slots,
    layers, 1, k)."""
    return None if routes is None else routes[:, :, 0]


def step_stream_group(sessions: Sequence[TokenStreamSession]
                      ) -> List[Tuple[TokenStreamSession, List[int]]]:
    """Advance same-plan sessions one engine step each, with the wire
    work of the WHOLE group merged: one cross-session ``encode_batch``
    and one ``decode_batch`` cover every active slot of every session —
    how streaming slots join the fleet's (point, bits, codec) cloud
    groups. Per-session tokens are bit-identical to stepping each
    session alone (the codec's batched byte-identity contract). Returns
    (session, uids-encoded) pairs for the group log."""
    if not sessions:
        return []
    keys = {s.plan_key for s in sessions}
    if len(keys) > 1:
        raise ValueError(f"stream group mixes plans: {sorted(keys)}")
    bits = sessions[0].plan.bits
    codec = sessions[0]._codec
    dtype = sessions[0]._cloud_dtype
    staged = []
    for s in sessions:
        s.step_count += 1
        s._admit()
        active = s._active_slots()
        rows, sel = s._head_phase(active) if active else ([], None)
        staged.append((s, active, rows, sel))
    all_rows = [r for _, _, rows, _ in staged for r in rows]
    all_blobs, all_xs = [], []
    if all_rows:
        with span("codec.encode", rows=len(all_rows)):
            all_blobs = codec.encode_batch(all_rows, bits)
        with span("codec.decode", rows=len(all_blobs)):
            all_xs = codec.decode_batch(all_blobs, out_dtype=dtype)
    out: List[Tuple[TokenStreamSession, List[int]]] = []
    lo = 0
    for s, active, rows, sel in staged:
        hi = lo + len(rows)
        blobs, xs = all_blobs[lo:hi], all_xs[lo:hi]
        lo = hi
        uids: List[int] = []
        if active:
            uids = s._account_encode(active, blobs)
            s._finish_step(active, sel[1], s._tail_phase(active, sel, xs))
        out.append((s, uids))
    return out


__all__ = ["TokenStreamSession", "step_stream_group", "PlanKey"]
