"""The bitpack wire format, worked out independently of the program, to
hold its wire bytes against.

Per tensor, in float32: ``mn, mx`` are its min and max, ``scale =
(2^bits - 1) / (mx - mn)`` (0 when mx == mn), each code is
``round_half_even((x - mn) * scale)`` clipped to [0, 2^bits - 1]. The
payload is one byte per code for 4 < bits <= 8, little-endian uint16
above 8, and for bits <= 4 two codes a byte, low nibble first, an odd
tail paired with the first code. Decoding gives ``code * ((mx - mn) /
(2^bits - 1)) + mn``.

The arithmetic runs in ``jax.numpy`` on the default device: a TPU's
float32 division is not the host's correctly rounded one, and a scale
one unit in the last place apart moves every copy of a value that sits
next to a rounding edge to the neighbouring code (seen on a v5e against
a numpy oracle). The framing is numpy on the host.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnums=1)
def _codes(x, bits: int):
    xf = x.astype(jnp.float32).reshape(-1)
    mn, mx = jnp.min(xf), jnp.max(xf)
    levels = (1 << bits) - 1
    scale = jnp.where(mx > mn, levels / (mx - mn), 0.0)
    return jnp.clip(jnp.round((xf - mn) * scale), 0, levels), mn, mx


@functools.partial(jax.jit, static_argnums=3)
def _dequantize(q, mn, mx, bits: int):
    step = (mx - mn) / ((1 << bits) - 1)
    return q * step + mn


def codes(x, bits: int) -> Tuple[np.ndarray, np.float32, np.float32]:
    q, mn, mx = _codes(jnp.asarray(x), bits)
    return (np.asarray(q).astype(np.int64), np.float32(mn),
            np.float32(mx))


def payload(q: np.ndarray, bits: int) -> bytes:
    if bits <= 4:
        c = np.concatenate([q, q[:1]])[: q.size + q.size % 2].astype(np.uint8)
        return (c[0::2] | (c[1::2] << 4)).tobytes()
    if bits <= 8:
        return q.astype(np.uint8).tobytes()
    return q.astype("<u2").tobytes()


def unpack(payload: bytes, bits: int, n: int) -> np.ndarray:
    """The ``n`` codes a payload carries."""
    if bits <= 4:
        b = np.frombuffer(payload, np.uint8).astype(np.int64)
        return np.stack([b & 0x0F, b >> 4], axis=-1).reshape(-1)[:n]
    if bits <= 8:
        return np.frombuffer(payload, np.uint8).astype(np.int64)
    return np.frombuffer(payload, "<u2").astype(np.int64)


def dequantize(q: np.ndarray, mn, mx, bits: int) -> np.ndarray:
    return np.asarray(_dequantize(jnp.asarray(q, jnp.float32),
                                  jnp.float32(mn), jnp.float32(mx), bits))


def check_blob(blob, x, bits: int, dec=None) -> Tuple[int, int]:
    """(wire mismatches, decode mismatches) of one served boundary ``x``
    against its blob: 1 if the payload or the range differs from the
    oracle's; the number of decoded values further from the oracle's
    dequantization than one unit in the last place of the decoded dtype
    (the cast) plus 4 float32 units at the tensor's scale (a fused
    multiply-add)."""
    q, mn, mx = codes(x, bits)
    wire = int(blob.payload != payload(q, bits)
               or np.float32(blob.x_min) != mn
               or np.float32(blob.x_max) != mx)
    if dec is None:
        return wire, 0
    deq = dequantize(q, mn, mx, bits)
    got = np.asarray(np.asarray(dec).astype(np.float32), np.float64)
    got = got.reshape(-1)
    deq = deq.astype(np.float64)
    eps = float(jnp.finfo(np.asarray(dec).dtype).eps)
    f32 = float(np.finfo(np.float32).eps)
    tol = eps * np.abs(deq) + 4 * f32 * max(float(np.max(np.abs(deq))), 1e-30)
    return wire, int(np.sum(np.abs(got - deq) > tol))
