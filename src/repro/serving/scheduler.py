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
``int(jnp.argmax(...))`` sync per slot per step. Each sampled slot still
consumes exactly one split of its own per-request key per token, so
sampled streams are identical to the per-slot path.

Compile behaviour: the batched decode compiles once (fixed slot count and
cache length). Prefill compiles per distinct prompt length, as in
``ServeSession``.
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
        self._select = jax.jit(self._batched_select)
        self._dummy_key = jax.random.key(self.cfg.seed)
        self._pos = jnp.zeros((n,), jnp.int32)
        self._last = jnp.zeros((n, 1, 1), jnp.int32)
        self._slots: List[Optional[GenRequest]] = [None] * n
        self._keys = [None] * n                     # per-request PRNG state
        self.queue: Deque[GenRequest] = deque()
        self.completed: List[GenRequest] = []
        self.events: List[Tuple[str, int, int]] = []   # (kind, step, uid)
        self.step_count = 0

    def _init_compute(self) -> None:
        """Build the jitted forward halves and the stacked per-slot cache
        buffers. The token-streaming session overrides this with split
        head/tail state (see :mod:`repro.serving.streaming`)."""
        L = self.cfg.max_seq_len

        def prefill(p, b):
            return self.model.prefill(p, b, L)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(
            jax.vmap(self.model.decode_step, in_axes=(None, 0, 0, 0))
        )
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
        self._caches = jax.tree.map(
            lambda buf, new: buf.at[slot].set(new), self._caches, caches
        )
        self._pos = self._pos.at[slot].set(len(req.tokens))
        req.slot = slot
        req.joined_step = self.step_count
        self._slots[slot] = req
        self._keys[slot] = jax.random.key(self.cfg.seed + req.uid)
        self.events.append(("join", self.step_count, req.uid))
        toks_np, toks = self._select_tokens([slot], logits[:, -1])
        self._last = self._last.at[slot, 0, 0].set(toks[0])
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

    def _select_tokens(self, slots: List[int], rows: jnp.ndarray
                       ) -> Tuple[np.ndarray, jnp.ndarray]:
        """Select the next token for every listed slot: one batched device
        op, one host transfer. Returns (host tokens, device tokens).
        Compiles once per distinct active-slot count (bounded by
        ``max_batch``)."""
        with span("stream.select"):
            temps = np.array([self._slots[s].temperature for s in slots],
                             np.float32)
            keys = jnp.stack([
                self._keys[s] if self._keys[s] is not None
                else self._dummy_key for s in slots
            ])
            toks, new_keys = self._select(rows, keys, jnp.asarray(temps))
            with span("sync"):
                toks_np = np.asarray(toks)  # the step's single host sync
            for j, s in enumerate(slots):
                if temps[j] > 0:            # greedy slots never consume RNG
                    self._keys[s] = new_keys[j]
            return toks_np, toks

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
        self._keys[slot] = None
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
            logits, new_caches = self._decode(
                self.params, self._last, self._pos, self._caches
            )
            # Only active slots advance; free slots keep their (ignored)
            # state until a join overwrites it.
            mask = np.zeros((self.cfg.max_batch,), bool)
            mask[active] = True
            mj = jnp.asarray(mask)
            self._caches = self._masked_update(self._caches, new_caches, mj)
            self._pos = jnp.where(mj, self._pos + 1, self._pos)
            # One batched select + one host transfer for all active slots
            # (the old path synced the host once per slot per step).
            rows = logits[jnp.asarray(active), 0, -1]
            toks_np, toks = self._select_tokens(active, rows)
            self._last = self._last.at[jnp.asarray(active), 0, 0].set(toks)
            for j, slot in enumerate(active):
                self._record_token(slot, int(toks_np[j]))
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
