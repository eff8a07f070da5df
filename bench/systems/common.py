"""What both served paths share: the program's model config from a
configuration file, the seed's key, the codec tap, and the result of a
correctness comparison."""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from bench import oracle
from bench.harness import Recorder

# The keys of a configuration file that are the program's ModelConfig
# fields; the rest describe the deployment, the plan and the check.
MODEL_KEYS = (
    "family", "num_layers", "d_model", "num_heads", "num_kv_heads",
    "head_dim", "d_ff", "vocab_size", "norm_kind", "tie_embeddings",
    "rope_theta", "attention_window", "window_only_for_long", "cnn_spec",
    "image_size", "num_classes", "dtype", "param_dtype")


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def model_config(cfg: Dict[str, Any]):
    """The program's ModelConfig for a configuration file: its registered
    config with every model key of the file applied."""
    from repro.config import get_config

    keys = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    return get_config(cfg["arch_id"]).replace(**keys)


def reference(cfg: Dict[str, Any]):
    return importlib.import_module(f"bench.reference.{cfg['reference']}")


def seed_key(seed: int, stream: int = 0):
    """A JAX key from any whole-number seed (wider than 32 bits too)."""
    import jax

    k = jax.random.key(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, stream)


def same_layout(ours, theirs) -> Optional[str]:
    """None when two weight trees agree in structure, shapes and dtypes;
    else what differs."""
    import jax

    a, ta = jax.tree.flatten(ours)
    b, tb = jax.tree.flatten(theirs)
    if ta != tb:
        return f"tree structure differs: {ta} vs {tb}"
    for x, y in zip(a, b):
        if tuple(x.shape) != tuple(y.shape) or x.dtype != y.dtype:
            return f"leaf {x.shape}/{x.dtype} vs {y.shape}/{y.dtype}"
    return None


class Reservoir:
    """A seeded uniform sample of at most ``k`` items from a stream of
    unknown length (Algorithm R): after n offers, each offered item is
    kept with the same probability k / n, so the sample spans the whole
    window and not its opening."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng = k, rng
        self.items: List[Any] = []
        self.seen = 0

    def offer(self) -> Optional[int]:
        """The slot the next item goes to, or None where it is not kept.
        The caller puts the item there (``put``)."""
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(None)
            return len(self.items) - 1
        j = int(self.rng.integers(0, self.seen))
        return j if j < self.k else None

    def put(self, slot: int, item: Any) -> None:
        self.items[slot] = item


class CodecTap:
    """Wraps a codec object's encode/decode calls (instance attributes on
    the codec the served path uses): each call is a span, and a seeded
    sample of the window's encodes, drawn over the whole window, keeps its
    boundary, blobs and decode for the wire check. The tap adds no wait
    for the device: the spans hold the calls' host time, and the trace
    reduction finds the device work each span launched."""

    def __init__(self, codec, rec: Recorder, keep: int, seed: int):
        self.codec = codec
        self.rec = rec
        self.keep = keep
        self.rng = np.random.default_rng(seed)
        self.armed = False
        self.tag = None           # what the served path is encoding for
        self.sample = Reservoir(keep, self.rng)
        self._pending: Dict[int, Dict[str, Any]] = {}
        self._orig = {n: getattr(codec, n) for n in
                      ("encode", "encode_batch", "decode", "decode_batch")}
        codec.encode = self._encode
        codec.encode_batch = self._encode_batch
        codec.decode = self._decode
        codec.decode_batch = self._decode_batch

    @property
    def kept(self) -> List[Dict[str, Any]]:
        return [e for e in self.sample.items if e is not None]

    def restore(self) -> None:
        for n in self._orig:
            self.codec.__dict__.pop(n, None)

    def arm(self) -> None:
        self.armed = True
        self.sample = Reservoir(self.keep, self.rng)
        self._pending = {}

    def _count(self, xs, blobs) -> None:
        """Bytes a call must move at least: the boundary read or written,
        and the payload written or read."""
        if self.armed:
            self.rec.count("codec_bytes", sum(
                x.size * x.dtype.itemsize for x in xs)
                + sum(len(b.payload) for b in blobs))

    def _after_encode(self, rows, blobs) -> None:
        self._count(rows, blobs)
        slot = self.sample.offer() if self.armed else None
        if slot is not None:
            entry = {"rows": list(rows), "blobs": list(blobs),
                     "decs": [None] * len(blobs), "tag": self.tag}
            self.sample.put(slot, entry)
            for i, b in enumerate(blobs):
                self._pending[id(b)] = (entry, i)

    def _encode(self, x, bits):
        with self.rec.span("encode"):
            blob = self._orig["encode"](x, bits)
        self._after_encode([x], [blob])
        return blob

    def _encode_batch(self, xs, bits):
        xs = list(xs)
        with self.rec.span("encode"):
            blobs = self._orig["encode_batch"](xs, bits)
        self._after_encode(xs, blobs)
        return blobs

    def _after_decode(self, blobs, decs) -> None:
        self._count(decs, blobs)
        for b, d in zip(blobs, decs):
            hit = self._pending.pop(id(b), None)
            if hit is not None and hit[0]["blobs"][hit[1]] is b:
                hit[0]["decs"][hit[1]] = d

    def _decode(self, blob, out_dtype=None, **kw):
        if out_dtype is not None:
            kw["out_dtype"] = out_dtype
        with self.rec.span("decode"):
            out = self._orig["decode"](blob, **kw)
        self._after_decode([blob], [out])
        return out

    def _decode_batch(self, blobs, out_dtype=None, **kw):
        if out_dtype is not None:
            kw["out_dtype"] = out_dtype
        with self.rec.span("decode"):
            out = self._orig["decode_batch"](blobs, **kw)
        self._after_decode(list(blobs), out)
        return out

    def wire_checks(self, bits: int) -> List[Check]:
        """Wire bytes and ranges of every kept blob against the oracle,
        and its decode (where the served path decoded it)."""
        wire = dec = rows = decoded = 0
        for e in self.kept:
            for x, blob, d in zip(e["rows"], e["blobs"], e["decs"]):
                w, m = oracle.check_blob(blob, x, bits, d)
                wire += w
                dec += m
                rows += 1
                decoded += d is not None
        # Nothing kept, or nothing decoded, is a check that failed.
        return [Check("wire_mismatches", float(wire if rows else 1), 0.0),
                Check("decode_mismatches", float(dec if decoded else 1),
                      0.0)]
