#!/usr/bin/env python3
"""Smoke run of the JALAD serving path on a TPU, through its entry points.

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # four chips: the meshed cloud tail

One chip:

* resnet50 at full 224x224 width is served through
  ``build_edge_cloud_server`` -> ``EdgeCloudServer.serve_batch`` and then a
  ``FleetServer``, once with each boundary codec pinned (bitpack,
  perchannel, huffman). Every codec also round-trips a stage-1 boundary
  (8 x (256, 56, 56) f32) at 4 and 8 bits, so each kernel body runs.
* olmo-1b at its published widths (bf16) streams tokens through a
  ``TokenStreamSession`` at a middle decoupling point, once with bitpack
  at 8 bits and once with huffman.

Four chips (``--four-chips``, and nothing else): granite-34b at published
widths, cut in depth, served through ``FleetServer(cloud_mesh=...)`` and
compared with the single-chip fused tail; then, through the same entry
point, a depth whose tail does not fit one chip, its parameters created
sharded over the mesh and the edge heads holding only their own layers.

Checks: each codec's wire bytes equal the host/jnp oracle on a served
boundary, and olmo-1b's streaming session on a prefill and on a
4-row batched decode step; the decoded boundary is within half a
quantization step of the head's output; the decoupled logits (resnet50's
served batches, and every logits row the olmo-1b session picked a token
from, teacher-forced) stay within ``LOGIT_REL_TOL`` (relative L2) of the
unsplit forward; no kernel runs in interpret mode and no Huffman encode
left the device path. Weights are random, from ``--seed``.

Earlier lines report the device, each phase's compile and run time,
bytes sent, and device memory in use at the phase's end (with the
process-wide peak). The last line is one JSON object, and
only when every phase passed; the exit code is non-zero otherwise (no
TPU, a failed check, or any exception).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Relative L2 distance allowed between decoupled and unsplit logits with
# an 8-bit boundary: the quantization noise of half a step of the
# boundary's range (per tensor or per channel) must not move the logits
# by more than a tenth of their norm.
LOGIT_REL_TOL = 0.1
# The decoded boundary may differ from the oracle's dequantization by
# float rounding only (a fused multiply-add in one, not the other).
DECODE_ULPS = 4
CODECS = ("bitpack", "perchannel", "huffman")
CNN_BATCH = 8
CNN_BANDWIDTH = 3e5                # bytes/s: a constrained uplink
# resnet50 decoupling points the planner may choose from: res1_3 (the
# (256, 56, 56) stage-1 output) and res4_3 (the (2048, 7, 7) last stage).
CNN_POINTS = (4, 17)
STAGE1_POINT = 4

# Seconds spent in XLA's backend compiler (JAX reports each compile).
_COMPILE_S = [0.0]
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _COMPILE_S[0] += duration


def _in_use(device) -> int:
    return int((device.memory_stats() or {}).get("bytes_in_use", -1))


def _memory(devices) -> str:
    """Each device's bytes in use now, and its peak over the process."""
    parts = []
    for d in devices:
        s = d.memory_stats() or {}
        parts.append(f"{d.id}: not reported" if "bytes_in_use" not in s
                     else f"{d.id}: {s['bytes_in_use']} B (process peak "
                     f"{s.get('peak_bytes_in_use')} B)")
    return "device memory in use " + ", ".join(parts)


class Phase:
    """Times one phase, splitting XLA compilation from the rest, and
    reports device 0's memory after it."""

    def __init__(self, name: str):
        self.name = name
        self.notes = []

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = _COMPILE_S[0]
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        import jax

        wall = time.perf_counter() - self.t0
        comp = _COMPILE_S[0] - self.c0
        notes = "".join(f"; {n}" for n in self.notes)
        print(f"[{self.name}] wall {wall:.3f} s = XLA compile {comp:.3f} s "
              f"+ rest {wall - comp:.3f} s; "
              f"{_memory(jax.devices()[:1])}{notes}", flush=True)
        return False


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _rel_l2(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# Oracles: the wire format from repro.core (quantize, pack_bits, Huffman)
# ---------------------------------------------------------------------------


def _oracle_blob(codec: str, x, bits: int):
    """(payload, min, max, dequantized) of ``x`` through the jnp/host
    reference of ``codec``: ``core.quantization`` codes, framed as the
    codec's docstring specifies."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.codec.perchannel import channel_axis
    from repro.core import entropy as ent
    from repro.core import quantization as q

    if codec == "perchannel":
        ax = channel_axis(x.ndim)
        qz = q.quantize(x, bits, axis=ax)
        cm = jnp.moveaxis(qz.values, ax, 0).reshape(x.shape[ax], -1)
        words = jax.vmap(lambda row: q.pack_bits(row, bits))(cm)
        payload = np.asarray(words).astype("<u4").tobytes()
        deq = q.dequantize(qz, axis=ax)
        return payload, np.asarray(qz.x_min), np.asarray(qz.x_max), deq
    qz = q.quantize(x, bits)
    codes = np.asarray(qz.values).reshape(-1)
    if codec == "huffman":
        payload = ent.huffman_encode(codes, 1 << bits)
    elif bits <= 4:
        # Pairs of consecutive codes, low nibble first; an odd tail pairs
        # with the tile padding, which repeats the first element.
        c = np.concatenate([codes, codes[:1]])[: codes.size + codes.size % 2]
        c = c.astype(np.uint8)
        payload = (c[0::2] | (c[1::2] << 4)).tobytes()
    elif bits <= 8:
        payload = codes.astype(np.uint8).tobytes()
    else:
        payload = codes.astype("<u2").tobytes()
    return (payload, np.float32(qz.x_min), np.float32(qz.x_max),
            q.dequantize(qz))


def _check_wire(blob, x, codec: str, bits: int, where: str, dec=None):
    """The blob's payload and ranges equal the oracle's; its decode
    (``dec``, else the codec's single-blob decode) is the oracle's
    dequantization up to float rounding, within half a step of ``x``.
    Returns the decoded boundary."""
    import jax.numpy as jnp
    import numpy as np

    from repro.codec import get_codec

    payload, mn, mx, deq = _oracle_blob(codec, x, bits)
    _check(blob.payload == payload,
           f"{where}: {codec}/{bits} wire bytes differ from the oracle")
    _check(np.array_equal(np.asarray(blob.x_min, np.float32),
                          np.asarray(mn, np.float32))
           and np.array_equal(np.asarray(blob.x_max, np.float32),
                              np.asarray(mx, np.float32)),
           f"{where}: {codec}/{bits} ranges differ from the oracle")
    if dec is None:
        dec = get_codec(codec).decode(blob, out_dtype=jnp.float32)
    dec_np = np.asarray(dec, np.float64)
    deq_np = np.asarray(deq, np.float64)
    x_np = np.asarray(x, np.float64)
    scale = float(np.max(np.abs(x_np))) or 1.0
    ulp = float(np.finfo(np.float32).eps) * scale
    _check(float(np.max(np.abs(dec_np - deq_np))) <= DECODE_ULPS * ulp,
           f"{where}: {codec}/{bits} decode differs from the oracle")
    step = (np.asarray(mx, np.float64) - np.asarray(mn, np.float64)) / (
        (1 << bits) - 1)
    if codec == "perchannel":
        shape = [1] * x.ndim
        shape[blob.axis] = -1
        step = step.reshape(shape)
    _check(bool(np.all(np.abs(dec_np - x_np) <= step / 2 + DECODE_ULPS * ulp)),
           f"{where}: {codec}/{bits} decode off by more than half a step")
    return dec


# ---------------------------------------------------------------------------
# One chip: resnet50 request path
# ---------------------------------------------------------------------------


def cnn_phases(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.codec import get_codec
    from repro.config import JaladConfig, get_config
    from repro.config.types import EDGE_TK1, EDGE_TX2
    from repro.data.synthetic import make_batch
    from repro.serving.edge_cloud import build_edge_cloud_server
    from repro.serving.fleet import FleetRequest, FleetServer

    cfg = get_config("resnet50")
    params = None
    batches = [make_batch(cfg, CNN_BATCH, 0, seed=seed + 100 + i)
               for i in range(3)]
    for codec in CODECS:
        with Phase(f"resnet50/{codec}/calibrate"):
            jc = JaladConfig(bits_choices=(8,), codec_choices=(codec,),
                             accuracy_drop_budget=1.0,
                             bandwidth_bytes_per_s=CNN_BANDWIDTH)
            srv, params = build_edge_cloud_server(
                cfg, jc, seed=seed, calib_batches=1,
                calib_batch_size=CNN_BATCH, points=list(CNN_POINTS),
                params=params)
        model = srv.engine.model
        head = jax.jit(model.run_head, static_argnums=2)
        tail = jax.jit(model.run_tail, static_argnums=2)
        full = jax.jit(model.forward)

        with Phase(f"resnet50/{codec}/serve_batch") as ph:
            served = [srv.serve_batch(b, CNN_BANDWIDTH) for b in batches]
            sent = sum(bd.bytes_sent for _, bd in served)
            ph.notes.append(f"{len(served)} batches of {CNN_BATCH}, "
                            f"{sent} B sent")
        for _, bd in served:
            _check(bd.plan_codec == codec and bd.plan_point >= 0,
                   f"serve_batch planned {bd.plan_codec}@{bd.plan_point}, "
                   f"not a {codec} cut")
        logits, bd = served[0]
        point, bits = bd.plan_point, bd.plan_bits
        boundary = head(params, batches[0], point)
        blob = get_codec(codec).encode(boundary, bits)
        _check(blob.nbytes == bd.bytes_sent,
               f"resnet50/{codec}: served {bd.bytes_sent} B, "
               f"the encode gives {blob.nbytes} B")
        dec = _check_wire(blob, boundary, codec, bits, "resnet50 served")
        want = tail(params, dec.astype(jnp.float32), point)
        _check(_rel_l2(logits, want) <= 1e-5,
               f"resnet50/{codec}: served logits differ from the tail of "
               "the decoded boundary")
        err = _rel_l2(logits, full(params, batches[0]))
        _check(err <= LOGIT_REL_TOL,
               f"resnet50/{codec}: decoupled logits {err:.4f} (rel L2) "
               "from the unsplit forward")
        print(f"[resnet50/{codec}] cut {point} at {bits} bits, "
              f"{bd.bytes_sent} B per batch, logits rel L2 {err:.5f} "
              "from unsplit", flush=True)

        with Phase(f"resnet50/{codec}/stage1_roundtrip") as ph:
            x1 = head(params, batches[1], STAGE1_POINT)
            rows = [x1[i:i + 1] for i in range(x1.shape[0])]
            for rb in (4, 8):
                blobs = get_codec(codec).encode_batch(rows, rb)
                decs = get_codec(codec).decode_batch(blobs)
                for i, (b, d) in enumerate(zip(blobs, decs)):
                    _check_wire(b, rows[i], codec, rb,
                                f"resnet50 stage-1 sample {i}", dec=d)
                ph.notes.append(
                    f"{rb} bits: {sum(b.nbytes for b in blobs)} B")

        with Phase(f"resnet50/{codec}/fleet") as ph:
            fleet = FleetServer(srv.engine, params,
                                [EDGE_TX2, EDGE_TK1, EDGE_TX2, EDGE_TK1])
            one = [make_batch(cfg, 1, 0, seed=seed + 200 + i)
                   for i in range(8)]
            reqs = [FleetRequest(uid=i, device_id=i % 4, batch=b,
                                 bandwidth=CNN_BANDWIDTH)
                    for i, b in enumerate(one)]
            done = fleet.serve(reqs)
            ph.notes.append(
                f"{len(done)} requests in {len(fleet.cloud_groups)} cloud "
                f"groups, {sum(r.breakdown.bytes_sent for r in done)} B")
        _check(len(done) == len(reqs), "fleet lost requests")
        for r in done:
            _check(r.plan is not None and not r.plan.is_cloud_only
                   and r.plan.codec == codec,
                   f"fleet request {r.uid} not served through {codec}")
            err = _rel_l2(r.logits, full(params, one[r.uid]))
            _check(err <= LOGIT_REL_TOL,
                   f"fleet request {r.uid}: logits {err:.4f} from unsplit")


# ---------------------------------------------------------------------------
# One chip: olmo-1b token streaming
# ---------------------------------------------------------------------------


class _WireTap:
    """Stands in for a session's codec and keeps what each encode sent,
    as (boundary rows, blobs), so the smoke checks the session's own
    wire bytes."""

    def __init__(self, codec):
        self._codec = codec
        self.sent = []

    def __getattr__(self, name):
        return getattr(self._codec, name)

    def encode(self, x, bits):
        blob = self._codec.encode(x, bits)
        self.sent.append(([x], [blob]))
        return blob

    def encode_batch(self, rows, bits):
        blobs = self._codec.encode_batch(rows, bits)
        self.sent.append((list(rows), blobs))
        return blobs


def _tap_logits(sess):
    """Keep every logits row the session picks a token from, by uid, in
    generation order (the prefill's row first)."""
    import numpy as np

    rows = {}
    select = sess._select_tokens

    def tapped(slots, logits):
        for s, row in zip(slots, np.asarray(logits, np.float32)):
            rows.setdefault(sess._slots[s].uid, []).append(row)
        return select(slots, logits)

    sess._select_tokens = tapped
    return rows


def stream_phases(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import ServeConfig, get_config
    from repro.core.decoupler import DecoupledPlan
    from repro.models.api import build_model
    from repro.serving.scheduler import GenRequest
    from repro.serving.streaming import TokenStreamSession

    cfg = get_config("olmo-1b")
    model = build_model(cfg)
    with Phase("olmo-1b/init"):
        params = jax.block_until_ready(model.init(jax.random.key(seed)))
    point = len(model.decoupling_points()) // 2
    max_len, prompt_len, new_tokens, n_req = 64, 16, 8, 4
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_req)]
    full = jax.jit(model.forward)
    for codec in ("bitpack", "huffman"):
        plan = DecoupledPlan(point=point, bits=8, predicted_latency=0.0,
                             predicted_acc_drop=0.0, solve_ms=0.0,
                             codec=codec)
        with Phase(f"olmo-1b/{codec}/stream") as ph:
            sess = TokenStreamSession(
                model, params,
                ServeConfig(max_batch=n_req, max_seq_len=max_len), plan=plan)
            tap = sess._codec = _WireTap(sess._codec)
            logit_rows = _tap_logits(sess)
            for i, p in enumerate(prompts):
                sess.submit(GenRequest(uid=i, tokens=p,
                                       max_new_tokens=new_tokens))
            done = sess.run()
            ph.notes.append(
                f"{len(done)} requests, {sess.tokens_out} tokens, "
                f"{sess.bytes_sent} B sent, "
                f"{len(sess.encode_groups)} batched encode steps")
        _check(len(done) == n_req
               and all(len(r.out_tokens) == new_tokens for r in done),
               f"olmo-1b/{codec}: a request did not finish")
        if codec == "bitpack":
            frames = sum(len(uids) for _, uids in sess.encode_groups)
            want = (sess.header.nbytes
                    + n_req * (prompt_len * cfg.d_model + 8)
                    + frames * (cfg.d_model + 8))
            _check(sess.bytes_sent == want,
                   f"olmo-1b/bitpack: {sess.bytes_sent} B sent, "
                   f"the frame sizes give {want} B")
        # What the session sent: the first prefill boundary, and the
        # first decode step that encoded every slot in one batch.
        joined = tap.sent[0]
        stepped = next(s for s in tap.sent if len(s[1]) == n_req)
        _check_wire(joined[1][0], joined[0][0], codec, 8,
                    "olmo-1b session prefill")
        for i, (row, blob) in enumerate(zip(*stepped)):
            _check_wire(blob, row, codec, 8,
                        f"olmo-1b session step, row {i} of {n_req}")
        # Every logits row the session chose a token from, against the
        # unsplit forward over the same tokens (teacher-forced).
        worst = 0.0
        for r in done:
            seq = np.concatenate([r.tokens, r.out_tokens[:-1]])
            ref = full(params, {"tokens": jnp.asarray(seq[None, :])})
            got = np.stack(logit_rows[r.uid])
            _check(got.shape[0] == new_tokens,
                   f"olmo-1b/{codec}: {got.shape[0]} logits rows for "
                   f"request {r.uid}")
            worst = max(worst, _rel_l2(got, ref[0, prompt_len - 1:]))
        _check(worst <= LOGIT_REL_TOL,
               f"olmo-1b/{codec}: session logits {worst:.4f} (rel L2) "
               "from the unsplit forward")
        print(f"[olmo-1b/{codec}] cut {point} at 8 bits, session logits "
              f"worst rel L2 {worst:.5f} from unsplit over {n_req} "
              f"requests x {new_tokens} tokens; wire bytes equal the "
              f"oracle for the prefill and a {n_req}-row step", flush=True)


# ---------------------------------------------------------------------------
# Four chips: the meshed cloud tail
# ---------------------------------------------------------------------------


GRANITE_FIT_LAYERS = 4        # ~5.5 GB of bf16 weights: fits one chip
GRANITE_BIG_LAYERS = 20       # tail after block 1: ~20 GB, over one chip
GRANITE_POINT = 1
CHIP_HBM_BYTES = 16e9          # one v5e chip
GRANITE_SEQ = 32
GROUP = 8
# Meshed vs single-chip logits: the same bf16 program with its
# contractions split over four chips and summed across them, so they
# agree to a few bf16 roundings (2^-9 each) per layer.
MESH_REL_TOL = 5e-2
# Room a chip may use beyond the parameters it holds: compiled programs,
# activations and logits that are still live.
WORKING_BYTES = 2 << 30


def _granite(layers: int):
    from repro.config import get_config
    from repro.models.api import build_model

    return build_model(get_config("granite-34b").replace(num_layers=layers))


def _lm_requests(cfg, n: int, seed: int):
    from repro.config.types import EDGE_TK1, EDGE_TX2
    from repro.data.synthetic import make_batch
    from repro.serving.fleet import FleetRequest

    profiles = [EDGE_TX2, EDGE_TK1, EDGE_TX2, EDGE_TK1]
    return profiles, [
        FleetRequest(uid=i, device_id=i % len(profiles),
                     batch=make_batch(cfg, 1, GRANITE_SEQ, seed=seed + i),
                     bandwidth=CNN_BANDWIDTH)
        for i in range(n)]


def _tree_bytes(tree) -> int:
    import jax

    return sum(a.nbytes for a in jax.tree.leaves(tree))


def _share_bytes(tree, device) -> int:
    """Bytes of ``tree``'s shards that live on ``device``."""
    import jax

    return sum(s.data.nbytes for a in jax.tree.leaves(tree)
               for s in a.addressable_shards if s.device == device)


def meshed_phases(seed: int) -> None:
    """granite-34b's cloud tail over a (1 data, 4 model) mesh, through
    ``FleetServer(cloud_mesh=...)``: first against the single-chip fused
    tail at a depth one chip holds, then at a depth whose tail only the
    mesh holds, its parameters created sharded and the edge heads holding
    only their own layers."""
    import gc

    import jax
    import jax.numpy as jnp

    from repro.config import JaladConfig
    from repro.launch.mesh import make_host_mesh
    from repro.serving.edge_cloud import build_edge_cloud_server
    from repro.serving.fleet import FleetServer
    from repro.serving.meshed import param_shardings

    devices = jax.devices()[:4]
    mesh = make_host_mesh(model_axis=4)
    jc = JaladConfig(bits_choices=(8,), codec_choices=("bitpack",),
                     accuracy_drop_budget=1.0,
                     bandwidth_bytes_per_s=CNN_BANDWIDTH)

    model = _granite(GRANITE_FIT_LAYERS)
    with Phase(f"granite-34b x{GRANITE_FIT_LAYERS} layers/calibrate"):
        srv, params = build_edge_cloud_server(
            model.cfg, jc, seed=seed, calib_batches=1, calib_batch_size=2,
            seq_len=GRANITE_SEQ, points=[GRANITE_POINT])
    profiles, reqs = _lm_requests(model.cfg, 2 * GROUP, seed + 300)
    with Phase("granite-34b/single-chip fused tail") as ph:
        ref = FleetServer(srv.engine, params, profiles, fuse_cloud_tail=True)
        done_ref = {r.uid: r for r in ref.serve(reqs)}
        ph.notes.append(f"{len(done_ref)} requests, cloud groups "
                        f"{[len(g.uids) for g in ref.cloud_groups]}")
    profiles, reqs = _lm_requests(model.cfg, 2 * GROUP, seed + 300)
    with Phase("granite-34b/meshed tail, 4 chips") as ph:
        meshed = FleetServer(srv.engine, params, profiles, cloud_mesh=mesh)
        done = meshed.serve(reqs)
        ph.notes.append(f"fused groups {meshed.mesh_worker.group_sizes}; "
                        + _memory(devices))
    _check(meshed.mesh_worker.fused_calls >= 1,
           "no group went through the meshed worker")
    worst = 0.0
    for r in done:
        _check(r.plan is not None and not r.plan.is_cloud_only,
               f"granite request {r.uid} was not decoupled")
        worst = max(worst, _rel_l2(r.logits, done_ref[r.uid].logits))
    _check(worst <= MESH_REL_TOL,
           f"meshed logits {worst:.4f} (rel L2) from the single-chip tail")
    print(f"[granite-34b] meshed vs single-chip logits: worst rel L2 "
          f"{worst:.5f} over {len(done)} requests", flush=True)
    del srv, ref, meshed, params, done, done_ref, r, reqs
    gc.collect()

    model = _granite(GRANITE_BIG_LAYERS)
    before = [_in_use(d) for d in devices]
    name = f"granite-34b x{GRANITE_BIG_LAYERS} layers"
    with Phase(f"{name}/sharded init + calibrate on the mesh"):
        params = jax.block_until_ready(
            model.init(jax.random.key(seed), param_shardings(model, mesh)))
        srv, _ = build_edge_cloud_server(
            model.cfg, jc, seed=seed, calib_batches=1, calib_batch_size=2,
            seq_len=GRANITE_SEQ, points=[GRANITE_POINT], params=params)
    total = _tree_bytes(params)
    head = _tree_bytes(model.head_params(params, GRANITE_POINT))
    _check(total - head > CHIP_HBM_BYTES,
           f"the tail holds {total - head} B: it would fit one chip")
    profiles, reqs = _lm_requests(model.cfg, 3 * GROUP, seed + 400)
    with Phase(f"{name}/meshed fleet") as ph:
        fleet = FleetServer(srv.engine, params, profiles, cloud_mesh=mesh)
        done = fleet.serve(reqs)
        in_use = [_in_use(d) for d in devices]
        ph.notes.append(f"fused groups {fleet.mesh_worker.group_sizes}; "
                        f"params {total} B, edge head {head} B; "
                        + _memory(devices))
    _check(len(done) == len(reqs), "the meshed fleet lost requests")
    _check(fleet.mesh_worker.fused_calls >= 1,
           "no group went through the meshed worker")
    for r in done:
        _check(r.plan is not None and not r.plan.is_cloud_only,
               f"granite request {r.uid} was not decoupled")
        _check(r.logits.shape == (1, GRANITE_SEQ, model.cfg.vocab_size)
               and bool(jnp.all(jnp.isfinite(r.logits))),
               f"granite request {r.uid}: logits not finite or misshapen")
    # What each chip gained over the phase: its shard of the params, plus
    # on chip 0 the edge heads' copy of the head, plus working memory.
    for i, d in enumerate(devices):
        want = _share_bytes(params, d) + (head if i == 0 else 0)
        got = in_use[i] - before[i]
        print(f"[{name}] chip {d.id}: {got} B more in use than before "
              f"the phase; param shard + edge head {want} B", flush=True)
        _check(got <= want + WORKING_BYTES,
               f"chip {d.id} holds {got} B: more than its share {want} B")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the meshed cloud tail over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 2
    from repro.kernels.quantize import count_host_routes, ops

    _check(not ops._should_interpret(), "kernels would run interpreted")
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    _check(len(devices) >= need, f"needs {need} chips, found {len(devices)}")
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    with count_host_routes() as routes:
        if args.four_chips:
            meshed_phases(args.seed)
        else:
            cnn_phases(args.seed)
            stream_phases(args.seed)
    print(f"Huffman encodes that took the host route: {routes.count}",
          flush=True)
    _check(routes.count == 0, "a Huffman encode left the device path")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
