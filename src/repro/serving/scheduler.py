"""Continuous-batching request scheduler (slot-based, vLLM-style).

Replaces the one-shot ``ServeSession.generate`` serving path: instead of
padding a wave of requests to a common prompt length and running them in
lock-step, the engine keeps ``max_batch`` independent *slots*. A request
joins a free slot at any decode step (its prompt is prefilled into that
slot's cache), every active slot advances one token per engine step
through a single batched decode, and a slot is evicted the moment its
request finishes (max tokens or EOS) — so short requests never wait for
long ones and the batch refills continuously.

Per-slot decode positions are handled by ``jax.vmap``-ing the model's
single-sequence ``decode_step`` over a leading slot axis: every slot
carries its own ``pos`` scalar and its own cache tree (batch=1), so the
numerics of each request are *exactly* those of running it alone — the
continuous-batching output is bit-identical to the synchronous batch-1
path (greedy), which the tests assert.

Token selection is batched the same way: greedy argmax and temperature
sampling for **all** active slots run as one device computation per
engine step (vmapped PRNG split + categorical, masked against each
slot's temperature) followed by a single device->host transfer — not one
``int(jnp.argmax(...))`` sync per slot per step. The per-request keys
live on the device as one ``(max_batch,)`` key array that the select
program gathers from and writes back; each sampled slot still consumes
exactly one split of its own key per token, and greedy slots none, so
sampled streams are identical to the per-slot path.

Launches: a step runs a fixed handful of programs, whatever the number of
live slots. The decode program takes the slot mask and the slot order as
operands and does the masked cache advance, the position advance and the
gather of the live logits rows inside, with the caches donated; the
select and the token record are one program each. A join writes its slot
and an eviction zeroes one with one donated program over the cache tree.

Compile behaviour: the batched decode compiles once (fixed slot count and
cache length). Prefill compiles per distinct prompt length, as in
``ServeSession``; the select and the token record per live-slot count.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.types import ServeConfig
from repro.models.api import Model
from repro.utils.trace import span


def _write_slot(bufs: Any, new: Any, slot) -> Any:
    """``bufs`` with slot ``slot`` of every leaf set to ``new``'s leaf."""
    return jax.tree.map(lambda b, x: b.at[slot].set(x), bufs, new)


def _zero_slot(bufs: Any, slot) -> Any:
    """``bufs`` with slot ``slot`` of every leaf zeroed."""
    return jax.tree.map(lambda b: b.at[slot].set(0), bufs)


def _put_tokens(last: jnp.ndarray, idx: jnp.ndarray,
                toks: jnp.ndarray) -> jnp.ndarray:
    """``last`` with the first ``len(toks)`` slots of ``idx`` set to
    ``toks``."""
    return last.at[idx[:toks.shape[0]], 0, 0].set(toks)


@dataclass
class GenRequest:
    """One generation request and (after serving) its result."""

    uid: int
    tokens: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int
    temperature: float = 0.0
    eos_id: Optional[int] = None
    arrival: float = 0.0               # engine step at which it may join
    # Filled by the engine:
    out_tokens: List[int] = field(default_factory=list)
    joined_step: int = -1
    done_step: int = -1
    slot: int = -1

    @property
    def result(self) -> np.ndarray:
        return np.asarray(self.out_tokens, np.int32)


@dataclass
class ContinuousBatchingEngine:
    """Slot-based continuous batching over a shared batched decode."""

    model: Model
    params: Any
    cfg: ServeConfig

    def __post_init__(self):
        if self.model.cfg.family == "cnn":
            raise ValueError("continuous batching serves autoregressive "
                             "families; CNNs go through the edge-cloud "
                             "pipeline (repro.serving.pipeline)")
        n = self.cfg.max_batch
        self._init_compute()
        self._select = jax.jit(self._select_program, donate_argnums=1)
        self._write_slot = jax.jit(_write_slot, donate_argnums=0)
        self._zero_slot = jax.jit(_zero_slot, donate_argnums=0)
        self._put_tokens = jax.jit(_put_tokens, donate_argnums=0)
        self._pos = jnp.zeros((n,), jnp.int32)
        self._last = jnp.zeros((n, 1, 1), jnp.int32)
        self._slots: List[Optional[GenRequest]] = [None] * n
        # Per-request PRNG state, one key a slot; a join writes its own.
        self._keys = jnp.stack([jax.random.key(self.cfg.seed)] * n)
        self.queue: Deque[GenRequest] = deque()
        self.completed: List[GenRequest] = []
        self.events: List[Tuple[str, int, int]] = []   # (kind, step, uid)
        self.step_count = 0

    def _init_compute(self) -> None:
        """Build the jitted forward halves and the stacked per-slot cache
        buffers. The token-streaming session overrides this with split
        head/tail state (see :mod:`repro.serving.streaming`)."""
        L = self.cfg.max_seq_len
        step = jax.vmap(self.model.decode_step, in_axes=(None, 0, 0, 0))

        def prefill(p, b):
            return self.model.prefill(p, b, L)

        def decode(p, last, pos, caches, mask, order):
            logits, new = step(p, last, pos, caches)
            return (self._masked_update(caches, new, mask),
                    jnp.where(mask, pos + 1, pos), logits[order, 0, -1])

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=3)
        self._caches = self._stack_slots(self.model.init_caches(1, L, 0))

    def _stack_slots(self, one: Any) -> Any:
        """Zeros-initialized per-slot stack of a batch-1 cache tree."""
        n = self.cfg.max_batch
        return jax.tree.map(
            lambda a: jnp.zeros((n,) + a.shape, a.dtype), one
        )

    # ------------------------------------------------------------ admission
    def submit(self, req: GenRequest) -> None:
        with span("stream.submit", uid=req.uid):
            self.queue.append(req)

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._slots)

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slots) if r is None]

    def _active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slots) if r is not None]

    def _slot_order(self, active: List[int]
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """A step's operands on the device: the (max_batch,) mask of the
        active slots, and every slot index with the active ones first, in
        order (the rows a step's programs gather and scatter)."""
        mask = np.zeros((self.cfg.max_batch,), bool)
        mask[active] = True
        order = np.concatenate([np.asarray(active, np.int32),
                                np.flatnonzero(~mask).astype(np.int32)])
        return jax.device_put((mask, order))

    def _admit(self) -> None:
        """Admit eligible queued requests into free slots (FIFO; requests
        whose ``arrival`` lies in the future are deferred in order)."""
        free = self._free_slots()
        deferred: List[GenRequest] = []
        while free and self.queue:
            req = self.queue.popleft()
            if req.arrival > self.step_count - 1:
                deferred.append(req)
                continue
            self._join(free.pop(0), req)
        self.queue.extendleft(reversed(deferred))

    # ------------------------------------------------------------- internals
    def _join(self, slot: int, req: GenRequest) -> None:
        batch = {"tokens": jnp.asarray(req.tokens[None, :], jnp.int32)}
        logits, caches = self._prefill(self.params, batch)
        self._caches, self._pos = self._write_slot(
            (self._caches, self._pos),
            (caches, np.int32(len(req.tokens))), slot)
        self._seat(slot, req, logits[:, -1])

    def _seat(self, slot: int, req: GenRequest, rows: jnp.ndarray) -> None:
        """Give ``req`` the slot its prefill was written to, and its first
        token from the prefill's last logits row ``rows`` (1, V)."""
        req.slot = slot
        req.joined_step = self.step_count
        self._slots[slot] = req
        self._keys = self._write_slot(
            self._keys, jax.random.key(self.cfg.seed + req.uid), slot)
        self.events.append(("join", self.step_count, req.uid))
        toks_np, toks = self._select_tokens([slot], rows)
        self._last = self._put_tokens(self._last,
                                      np.asarray([slot], np.int32), toks)
        self._record_token(slot, int(toks_np[0]))

    @staticmethod
    def _batched_select(rows: jnp.ndarray, keys, temps: jnp.ndarray):
        """Next token for a stack of slots in one device computation:
        rows (k, V) logits, keys (k,) per-slot PRNG keys, temps (k,).
        Greedy slots take the argmax; sampled slots split their key once
        (exactly as the per-slot path did) and draw categorically."""
        split = jax.vmap(jax.random.split)(keys)
        new_keys, subs = split[:, 0], split[:, 1]
        greedy = jnp.argmax(rows, axis=-1).astype(jnp.int32)
        safe_t = jnp.where(temps > 0, temps, 1.0)
        sampled = jax.vmap(jax.random.categorical)(
            subs, rows / safe_t[:, None]
        ).astype(jnp.int32)
        return jnp.where(temps > 0, sampled, greedy), new_keys

    def _select_program(self, rows: jnp.ndarray, keys, idx: jnp.ndarray,
                        temps: jnp.ndarray):
        """The select over the slots ``idx`` (k,) of the (max_batch,)
        ``keys``: their tokens, and ``keys`` with each sampled slot's key
        advanced (greedy slots never consume RNG)."""
        mine = keys[idx]
        toks, new = self._batched_select(rows, mine, temps)
        return toks, keys.at[idx].set(jnp.where(temps > 0, new, mine))

    def _select_tokens(self, slots: List[int], rows: jnp.ndarray
                       ) -> Tuple[np.ndarray, jnp.ndarray]:
        """Select the next token for every listed slot: one batched device
        op, one host transfer. Returns (host tokens, device tokens).
        Compiles once per distinct active-slot count (bounded by
        ``max_batch``)."""
        with span("stream.select"):
            temps = np.array([self._slots[s].temperature for s in slots],
                             np.float32)
            toks, self._keys = self._select(
                rows, self._keys, np.asarray(slots, np.int32), temps)
            with span("sync"):
                toks_np = self._fetch(toks)  # the step's single host sync
            return toks_np, toks

    def _fetch(self, toks: jnp.ndarray) -> np.ndarray:
        """The selected tokens on the host."""
        return np.asarray(toks)

    def _finish_step(self, active: List[int], order: jnp.ndarray,
                     rows: jnp.ndarray) -> None:
        """Select the active slots' tokens from their logits ``rows`` (row
        j is ``active[j]``'s), feed them back as the next step's input and
        record them."""
        toks_np, toks = self._select_tokens(active, rows)
        with span("stream.record", tokens=len(active)):
            self._last = self._put_tokens(self._last, order, toks)
            for j, slot in enumerate(active):
                self._record_token(slot, int(toks_np[j]))

    def _record_token(self, slot: int, token: int) -> None:
        req = self._slots[slot]
        req.out_tokens.append(token)
        finished = len(req.out_tokens) >= req.max_new_tokens or (
            req.eos_id is not None and token == req.eos_id
        )
        if finished:
            self._evict(slot)

    @staticmethod
    def _masked_update(old_tree: Any, new_tree: Any, mj: jnp.ndarray) -> Any:
        """Advance only the masked slots of a stacked state tree."""
        return jax.tree.map(
            lambda old, new: jnp.where(
                mj.reshape((-1,) + (1,) * (new.ndim - 1)), new, old
            ),
            old_tree, new_tree,
        )

    def _evict(self, slot: int) -> None:
        req = self._slots[slot]
        req.done_step = self.step_count
        self._slots[slot] = None
        self.completed.append(req)
        self.events.append(("evict", self.step_count, req.uid))

    # ------------------------------------------------------------------ step
    def step(self) -> List[GenRequest]:
        """One engine step: admit eligible requests into free slots, then
        advance every active slot by one decode token. Returns the requests
        that finished during this step."""
        self.step_count += 1
        done_before = len(self.completed)
        self._admit()
        active = self._active_slots()
        if active:
            # Only active slots advance; free slots keep their (ignored)
            # state until a join overwrites it.
            mask, order = self._slot_order(active)
            self._caches, self._pos, rows = self._decode(
                self.params, self._last, self._pos, self._caches, mask,
                order)
            self._finish_step(active, order, rows[:len(active)])
        return self.completed[done_before:]

    def run(self) -> List[GenRequest]:
        """Drain the queue and all active slots; returns completions in
        finish order."""
        while self.queue or self.num_active:
            before = self.step_count
            self.step()
            if self.step_count == before:   # pragma: no cover — safety
                break
        return self.completed
