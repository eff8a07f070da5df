"""Token streaming of a model that routes over experts: the stream system
(``bench/systems/stream.py``), with the comparison that decides
``correct`` held to the served routing.

The served path returns the experts each token chose in each expert
layer (a join's prompt, each decode step's token). A window keeps them
for every request, by position. The float32 reference then runs each
compared request on its served routing, weighing the served experts by
its own scores, so that a near tie the bf16 program broke the other way
does not send the two down different experts and leave nothing to
compare. The routing itself is checked apart: each served choice
against the reference's own top k at the same place.

Numbers compared (limits in the configuration file), over the same
requests and rows as the stream system:

* ``logit_gap``, ``logits_rel_l2``: as the stream system, against the
  reference on the served routing;
* ``route_deficit``: the widest, over every (token, expert layer) of the
  compared requests, of how far the lowest served expert's score plus
  bias lies below the reference's own k-th best there (0 where the
  served experts are the reference's top k): a flip of a near tie
  reads small, a wrong router large;
* ``route_flips``: the share of those (token, expert layer) choices whose
  served experts are not the reference's top k;
* ``route_missing``: positions of a compared request with no served
  routing (held at 0).

With ``cast`` (a control), the reference in that lower precision stands
in for the program: its own routing is the served one, and its rows and
first tokens the served ones.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.systems.common import seed_key
from bench.systems.stream import StreamSystem

# The shortest padded length of a compared sequence; longer ones pad to
# the next power of two, so the comparison compiles for a few lengths.
MIN_PAD = 512


class StreamMoeSystem(StreamSystem):
    def __init__(self, cfg, seed, rec):
        # uid -> [(side, first position, ids (side's expert layers, S, k))]
        self.routes: Dict[int, list] = {}
        super().__init__(cfg, seed, rec)

    def _instrument(self) -> None:
        super()._instrument()
        sess, record = self.sess, self.sess._record_routes

        def recorded(side, slots, ids, rows):
            if self.recording:
                for j, s in enumerate(slots):
                    uid = sess._slots[s].uid
                    if rows is not None and uid not in self.served:
                        continue        # joined before the window
                    # A decode step routes the token before the one it
                    # serves: position prompt + served - 1.
                    at = 0 if rows is None else (len(self.prompts[uid])
                                                 + len(self.served[uid]) - 1)
                    self.routes.setdefault(uid, []).append(
                        (side, at, ids[j].astype(np.int16)))
            record(side, slots, ids, rows)

        sess._record_routes = recorded

    def reset(self) -> None:
        super().reset()
        self.routes.clear()

    def _routing(self, uid: int, s_pad: int, n: int) -> np.ndarray:
        """The served routing of ``uid`` as (expert layers, s_pad, k),
        the head's expert layers first; -1 where none was served."""
        cfg = self.cfg
        layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
        out = np.full((layers, s_pad, cfg["num_experts_per_tok"]), -1,
                      np.int32)
        for side, at, ids in self.routes.get(uid, []):
            lo = 0 if side == "head" else layers - ids.shape[0]
            span = ids[:, :max(0, min(ids.shape[1], n - at))]
            out[lo:lo + ids.shape[0], at:at + span.shape[1]] = span
        return out

    def compare(self, uids: List[int], cast=None, shift: int = 0):
        """The stream system's numbers on the served routing, and the
        routing's own (module docstring). ``cast`` and ``shift`` as the
        stream system's."""
        import jax
        import jax.numpy as jnp

        names = ("logit_gap", "logits_rel_l2", "route_deficit",
                 "route_flips", "route_missing")
        if not uids:
            return {k: float("inf") for k in names}
        cfg, ref = self.cfg, self.ref
        params = ref.make_params(cfg, seed_key(self.seed))
        s_max = int(cfg["plan"]["max_seq_len"])
        r_max = int(cfg["check"]["select_calls"])
        vocab = int(cfg["vocab_size"])

        @jax.jit
        def measure(params, toks, served, start, n, rows, rpos, rlive,
                    routing, upto):
            if cast is None:
                lg, _, deficit = ref.forward_routed(cfg, params, toks,
                                                    routing)
                tok, have = (served + shift) % vocab, rows
            else:
                low, low_ids, _ = ref.forward_routed(cfg, params, toks,
                                                     None, cast)
                lg, _, deficit = ref.forward_routed(cfg, params, toks,
                                                    low_ids)
                tok, have = low.argmax(axis=-1), low[rpos]
            got = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
            pos = jnp.arange(toks.shape[0])
            live = (pos >= start) & (pos < start + n)
            gap = jnp.max(jnp.where(live, lg.max(axis=-1) - got, -jnp.inf))
            want = lg[rpos]
            m = rlive[:, None]
            seen = (pos < upto)[None, :]
            return (gap, jnp.sum(jnp.where(m, have - want, 0.0) ** 2),
                    jnp.sum(jnp.where(m, want, 0.0) ** 2),
                    jnp.max(jnp.where(seen, deficit, 0.0)),
                    jnp.sum(seen & (deficit > 0)))

        gap_worst = rel_worst = deficit_worst = 0.0
        flips = choices = missing = 0
        by_uid = self.kept_rows()
        with_rows = [u for u in by_uid if u in self.prompts]
        for u in sorted(set(uids) | set(with_rows)):
            prompt, out = self.prompts[u], self.served[u]
            seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
            s_pad = min(s_max, max(MIN_PAD, 1 << (seq.size - 1).bit_length()))
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
            routing = self._routing(u, s_pad, seq.size)
            missing += int((routing[:, :seq.size] < 0).any(-1).sum())
            g, num, den, dmax, nflip = measure(
                params, jnp.asarray(toks), jnp.asarray(served), start,
                len(out), jnp.asarray(rows), jnp.asarray(rpos),
                jnp.asarray(rlive), jnp.asarray(routing), seq.size)
            if u in uids:
                gap_worst = max(gap_worst, float(g))
            if kept:
                rel_worst = max(rel_worst,
                                float(np.sqrt(num / max(float(den), 1e-30))))
            deficit_worst = max(deficit_worst, float(dmax))
            flips += int(nflip)
            choices += seq.size * routing.shape[0]
        if not with_rows:
            rel_worst = float("inf")
        return {"logit_gap": gap_worst, "logits_rel_l2": rel_worst,
                "route_deficit": deficit_worst,
                "route_flips": flips / max(choices, 1),
                "route_missing": float(missing)}


def build(cfg, seed, rec):
    return StreamMoeSystem(cfg, seed, rec)
