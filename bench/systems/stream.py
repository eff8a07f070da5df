"""Token streaming across the cut: ``TokenStreamSession`` serving a
dense decoder, its head and tail on the chip and every step's boundary
rows through the codec.

The window drives ``submit`` and ``step``. Spans: ``step`` (one engine
step), ``join`` (a request's prefill across the cut, inside a step),
``encode`` / ``decode`` (the codec). Counters: ``prefill_tokens``,
``decode_tokens``, ``model_flops``.

The comparison that decides ``correct``: the wire bytes of a few seeded
encodes (joins and batched steps) against the oracle, and their decodes;
for a seeded sample of the requests the window finished, the longest
among them, every served token's logit in the float32 reference run over
its prompt and served tokens (teacher-forced): the widest gap below the
reference's best logit at that position; and the logits rows the session
selected from in a seeded sample of the window's steps, drawn over the
whole window, every live slot of each kept step compared, against the
reference's (relative L2, per request).
"""
from __future__ import annotations

import gc
from collections import Counter
from typing import Any, Dict, List

import numpy as np

from bench.harness import Recorder
from bench.systems.common import (
    Check, CodecTap, Reservoir, model_config, reference, same_layout,
    seed_key)

WARM_UID = 1 << 30


class StreamSystem:
    def __init__(self, cfg: Dict[str, Any], seed: int, rec: Recorder):
        import jax

        from repro.config import ServeConfig
        from repro.codec import get_codec
        from repro.core.decoupler import DecoupledPlan
        from repro.models.api import build_model
        from repro.serving.streaming import TokenStreamSession

        self.cfg, self.seed, self.rec = cfg, seed, rec
        self.ref = reference(cfg)
        model = build_model(model_config(cfg))
        key = seed_key(seed)
        params = self.ref.make_params(cfg, key)
        bad = same_layout(params, jax.eval_shape(model.init, key))
        if bad:
            raise RuntimeError(f"weights do not fit the program: {bad}")
        plan = cfg["plan"]
        self.bits = int(plan["bits"])
        self.sess = TokenStreamSession(
            model, params,
            ServeConfig(max_batch=int(plan["max_batch"]),
                        max_seq_len=int(plan["max_seq_len"])),
            plan=DecoupledPlan(point=int(plan["point"]), bits=self.bits,
                               predicted_latency=0.0, predicted_acc_drop=0.0,
                               solve_ms=0.0, codec=plan["codec"]),
            cloud_kv_bits=int(plan["cloud_kv_bits"]))
        self.tap = CodecTap(get_codec(plan["codec"]), rec,
                            keep=int(cfg["check"]["encodes"]), seed=seed)
        self.prompts: Dict[int, np.ndarray] = {}
        self.served: Dict[int, List[int]] = {}
        # The select calls kept, a seeded sample over the window: each
        # (logits rows, [(uid, token index, row)]).
        self.calls = Reservoir(int(cfg["check"]["select_calls"]),
                               np.random.default_rng(seed))
        self.recording = False
        self._instrument()

    # ----------------------------------------------------------- spans
    def _instrument(self) -> None:
        sess, rec, ref, cfg = self.sess, self.rec, self.ref, self.cfg
        join, record = sess._join, sess._record_token

        def joined(slot, req):
            with rec.span("join"):
                join(slot, req)

        def recorded(slot, token):
            uid = sess._slots[slot].uid
            if self.recording:
                rec.token(uid)
                n = len(self.served.setdefault(uid, []))
                plen = len(self.prompts[uid])
                if n == 0:
                    rec.count("prefill_tokens", plen)
                    rec.count("model_flops", ref.prefill_flops(cfg, plen))
                else:
                    rec.count("decode_tokens")
                    rec.count("model_flops",
                              ref.flops_per_token(cfg, plen + n))
                self.served[uid].append(int(token))
            record(slot, token)

        select = sess._select_tokens

        def selected(slots, rows):
            slot = self.calls.offer() if self.recording else None
            if slot is not None:
                self.calls.put(slot, (rows, [
                    (sess._slots[s].uid,
                     len(self.served.get(sess._slots[s].uid, [])), j)
                    for j, s in enumerate(slots)]))
            return select(slots, rows)

        sess._join = joined
        sess._record_token = recorded
        sess._select_tokens = selected

    # ---------------------------------------------------------- driving
    def _tokens(self, uid: int, n: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed & (2**63 - 1), uid])
        return rng.integers(0, self.cfg["vocab_size"], n).astype(np.int32)

    def submit(self, req, uid: int) -> None:
        from repro.serving.scheduler import GenRequest

        toks = self._tokens(uid, req.prompt_len)
        self.prompts[uid] = toks
        self.sess.submit(GenRequest(uid=uid, tokens=toks,
                                    max_new_tokens=req.output_len))

    def busy(self) -> bool:
        return bool(self.sess.queue) or self.sess.num_active > 0

    def queued(self) -> int:
        return len(self.sess.queue)

    def pump(self) -> None:
        with self.rec.span("step"):
            done = self.sess.step()
        if self.recording:
            for r in done:
                self.rec.finish(r.uid)

    def warm(self, schedule) -> None:
        """Every shape the window uses: each prompt length of the
        schedule, and each count of active slots (requests of 2, 3, ...
        max_batch + 1 tokens that join together and leave one a step)."""
        from repro.serving.scheduler import GenRequest

        n = self.sess.cfg.max_batch
        lengths = schedule.prompt_lengths
        uid = WARM_UID
        for i in range(0, len(lengths), n):
            group = lengths[i:i + n]
            group = group + [group[-1]] * (n - len(group))
            for j, plen in enumerate(group):
                self.sess.submit(GenRequest(
                    uid=uid, tokens=self._tokens(uid, plen),
                    max_new_tokens=j + 2))
                uid += 1
            self.sess.run()
        self.sess.completed.clear()
        self.sess.events.clear()

    def start(self, trace: bool) -> None:
        self.tap.arm()
        self.calls = Reservoir(self.calls.k, self.calls.rng)
        self.recording = True

    def stop(self) -> None:
        self.recording = False
        self.tap.armed = False

    def describe(self) -> List[str]:
        toks = [t for out in self.served.values() for t in out]
        repeats = sum(a == b for out in self.served.values()
                      for a, b in zip(out, out[1:]))
        live = Counter(len(c[1]) for c in self.calls.items if c)
        return [f"served: {len(toks)} tokens, {len(set(toks))} distinct, "
                f"{repeats} repeat the token before them; "
                f"{self.sess.bytes_sent} B on the wire",
                f"select calls kept for the check: {sum(live.values())} of "
                f"{self.calls.seen}; calls by live slots "
                f"{dict(sorted(live.items()))}"]

    def kept_rows(self) -> Dict[int, list]:
        """The kept select calls' rows by request: uid -> [(token index,
        rows, row)]."""
        out: Dict[int, list] = {}
        for c in self.calls.items:
            if c is None:
                continue
            rows, slots = c
            for uid, idx, j in slots:
                out.setdefault(uid, []).append((idx, rows, j))
        return out

    # ----------------------------------------------------------- checks
    def sample(self) -> List[int]:
        """Seeded sample of the finished requests, the longest first."""
        done = [r.uid for r in self.sess.completed if r.uid < WARM_UID]
        if not done:
            return []
        longest = max(done, key=lambda u: len(self.served.get(u, [])))
        rest = [u for u in done if u != longest]
        rng = np.random.default_rng([self.seed & (2**63 - 1), 1])
        k = min(int(self.cfg["check"]["requests"]) - 1, len(rest))
        pick = rng.choice(len(rest), size=k, replace=False) if k else []
        return [longest] + [rest[i] for i in pick]

    def release(self, uids: List[int]) -> None:
        """Free the program's state before the reference runs."""
        self.tap.restore()
        self.sess = None
        gc.collect()

    def reset(self) -> None:
        """Drop every request, queued or in a slot."""
        sess = self.sess
        sess.queue.clear()
        for s in sess._active_slots():
            sess._evict(s)
        sess.completed.clear()
        sess.events.clear()
        self.prompts.clear()
        self.served.clear()
        self.calls = Reservoir(self.calls.k, self.calls.rng)

    def reseed(self, seed: int) -> None:
        """Serve the same shapes with the weights and inputs of ``seed``."""
        self.reset()
        self.seed = seed
        self.sess.params = None
        gc.collect()
        self.sess.params = self.ref.make_params(self.cfg, seed_key(seed))
        self.tap.rng = np.random.default_rng(seed)
        self.calls.rng = np.random.default_rng(seed)

    def checks(self, uids: List[int]) -> List[Check]:
        lim = self.cfg["limits"]
        return self.tap.wire_checks(self.bits) + [
            Check(k, v, float(lim[k])) for k, v in self.compare(uids).items()]

    def compare(self, uids: List[int], cast=None, shift: int = 0):
        """Against the float32 reference run over each request's prompt
        and served tokens (teacher-forced):

        * the widest gap, over the served tokens of the sample ``uids``,
          between the reference's best logit at a position and its logit
          of the token served there;
        * the worst relative L2 distance, per request, between the logits
          rows the session selected from in the kept select calls and the
          reference's rows at the same positions.

        With ``cast`` (a control), the reference in that lower precision
        stands in for the program: its first token at each position, and
        its rows. ``shift`` plants a fault: each served token read as the
        one ``shift`` ids further on."""
        import jax
        import jax.numpy as jnp

        if not uids:
            return {"logit_gap": float("inf"), "logits_rel_l2": float("inf")}
        cfg, ref = self.cfg, self.ref
        params = ref.make_params(cfg, seed_key(self.seed))
        s_pad = int(cfg["plan"]["max_seq_len"])
        r_max = int(cfg["check"]["select_calls"])
        vocab = int(cfg["vocab_size"])

        @jax.jit
        def measure(params, toks, served, start, n, rows, rpos, rlive):
            lg = ref.forward(cfg, params, toks)
            low = None if cast is None else ref.forward(cfg, params, toks,
                                                        cast)
            tok = ((served + shift) % vocab if low is None
                   else low.argmax(axis=-1))
            got = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
            pos = jnp.arange(toks.shape[0])
            live = (pos >= start) & (pos < start + n)
            gap = jnp.max(jnp.where(live, lg.max(axis=-1) - got, -jnp.inf))
            want = lg[rpos]
            have = rows if low is None else low[rpos]
            m = rlive[:, None]
            return (gap, jnp.sum(jnp.where(m, have - want, 0.0) ** 2),
                    jnp.sum(jnp.where(m, want, 0.0) ** 2))

        gap_worst, rel_worst = 0.0, 0.0
        by_uid = self.kept_rows()
        with_rows = [u for u in by_uid if u in self.prompts]
        for u in sorted(set(uids) | set(with_rows)):
            prompt, out = self.prompts[u], self.served[u]
            seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
            toks = np.zeros(s_pad, np.int32)
            toks[: seq.size] = seq
            # served[p] is the token chosen at position p.
            served = np.zeros(s_pad, np.int32)
            start = prompt.size - 1
            served[start: start + len(out)] = out
            rows = np.zeros((r_max, vocab), np.float32)
            rpos = np.zeros(r_max, np.int32)
            kept = by_uid.get(u, [])
            for i, (idx, arr, j) in enumerate(kept):
                rows[i] = np.asarray(arr[j], np.float32)
                rpos[i] = start + idx
            rlive = np.arange(r_max) < len(kept)
            g, num, den = measure(params, jnp.asarray(toks),
                                  jnp.asarray(served), start, len(out),
                                  jnp.asarray(rows), jnp.asarray(rpos),
                                  jnp.asarray(rlive))
            if u in uids:
                gap_worst = max(gap_worst, float(g))
            if kept:
                rel_worst = max(rel_worst,
                                float(np.sqrt(num / max(float(den), 1e-30))))
        if not with_rows:
            rel_worst = float("inf")
        return {"logit_gap": gap_worst, "logits_rel_l2": rel_worst}


def build(cfg, seed, rec):
    return StreamSystem(cfg, seed, rec)
