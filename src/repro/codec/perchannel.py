"""Per-channel boundary codec: vector range headers + true c-bit packing,
on the same fused device kernels as ``bitpack``.

The ``axis=`` variant of ``repro.core.quantization.quantize`` (tighter
per-channel min/max ranges -> lower error at the same bit width) existed
but never had a wire format — nothing could actually ship it. This codec
gives it one, and since PR 3 the edge half runs **device-side**: one
fused ``perchannel_encode`` pallas_call takes the per-channel (min,
scale) *vectors* as kernel operands and packs the codes to exactly
``bits`` bits each in-kernel (``32 // bits`` codes per uint32 word, codes
never straddling a word) — no host ``pack_bits`` pass. The host only
trims each channel's word row (framing). The cloud half is the symmetric
fused unpack + dequant + cast launch, and both halves are batched
(``encode_batch``/``decode_batch``: one launch per micro-batch of
same-shape boundaries, per-(sample, channel) ranges).

Wire layout: channel-major — each channel's ``ceil(L / (32 // bits))``
uint32 words, channels concatenated, so channels never share a word and
the cloud can decode them independently. The header carries one
(min, max) float32 pair per channel instead of one per tensor, which the
ILP sees as ``8 * C`` extra header bytes traded against the accuracy
gain.

Channel axis convention: dim 1 for 4-D tensors (this repo's CNN layout is
NCHW) and the trailing dim otherwise (transformer ``(B, S, D)`` /
``(B, D)`` boundaries).
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import numpy as np
import jax.numpy as jnp

from repro.codec.base import (
    BoundaryCodec,
    WireBlob,
    register_codec,
    stackable_shapes,
)
from repro.core import quantization as q
from repro.kernels.quantize import (
    perchannel_decode,
    perchannel_decode_batch,
    perchannel_encode,
    perchannel_encode_stack,
    perchannel_words,
)
from repro.utils.trace import span


def channel_axis(ndim: int) -> int:
    return 1 if ndim == 4 else max(ndim - 1, 0)


class PerChannelCodec(BoundaryCodec):
    name = "perchannel"
    value_key = "channel"

    def _frame(self, words: np.ndarray, length: int, bits: int) -> bytes:
        """Trim one sample's (C, W_pad) device words to the wire's
        ceil(L / per_word) words per channel (host framing only)."""
        return np.ascontiguousarray(
            words[:, : perchannel_words(length, bits)]
        ).astype("<u4").tobytes()

    def encode(self, x: jnp.ndarray, bits: int) -> WireBlob:
        shape = tuple(x.shape)
        ax = channel_axis(len(shape))
        if x.size == 0:
            c = shape[ax] if shape else 1
            zeros = np.zeros((c,), np.float32)
            return WireBlob(self.name, b"", shape, bits, zeros, zeros,
                            axis=ax)
        words, mn, mx = perchannel_encode(jnp.asarray(x), bits, ax)
        with span("sync"):
            words, mn, mx = jax.device_get((words, mn, mx))
        with span("codec.frame"):
            payload = self._frame(words, int(x.size) // shape[ax], bits)
        return WireBlob(
            self.name, payload, shape, bits,
            np.asarray(mn, np.float32), np.asarray(mx, np.float32),
            axis=ax,
        )

    def encode_batch(self, xs: Sequence[jnp.ndarray], bits: int
                     ) -> List[WireBlob]:
        xs = list(xs)
        shapes = [tuple(x.shape) for x in xs]
        if not stackable_shapes(shapes):
            return [self.encode(x, bits) for x in xs]
        shape = shapes[0]
        ax = channel_axis(len(shape))
        length = int(np.prod(shape)) // shape[ax]
        words, mn, mx = perchannel_encode_stack(
            tuple(jnp.asarray(x) for x in xs), bits, ax
        )
        with span("sync"):
            words, mn, mx = jax.device_get((words, mn, mx))
        with span("codec.frame"):
            mn = np.asarray(mn, np.float32)
            mx = np.asarray(mx, np.float32)
            return [
                WireBlob(self.name, self._frame(words[i], length, bits),
                         shape, bits, mn[i], mx[i], axis=ax)
                for i in range(len(xs))
            ]

    def _wire_words(self, blob: WireBlob) -> np.ndarray:
        c = blob.shape[blob.axis]
        length = blob.num_elements // c
        return (np.frombuffer(blob.payload, "<u4").astype(np.uint32)
                .reshape(c, perchannel_words(length, blob.bits)))

    def decode(self, blob: WireBlob, out_dtype=jnp.float32) -> jnp.ndarray:
        if blob.num_elements == 0:
            return jnp.zeros(blob.shape, out_dtype)
        with span("codec.unframe"):
            words = jnp.asarray(self._wire_words(blob))
            mn, mx = jnp.asarray(blob.x_min), jnp.asarray(blob.x_max)
        return perchannel_decode(
            words, mn, mx, blob.bits, blob.shape, blob.axis,
            out_dtype=jnp.dtype(out_dtype),
        )

    def decode_batch(self, blobs: Sequence[WireBlob],
                     out_dtype=jnp.float32) -> List[jnp.ndarray]:
        blobs = list(blobs)
        shapes = [b.shape for b in blobs]
        if (not stackable_shapes(shapes)
                or len({b.bits for b in blobs}) != 1):
            return [self.decode(b, out_dtype) for b in blobs]
        first = blobs[0]
        with span("codec.unframe"):
            words = jnp.asarray(
                np.stack([self._wire_words(b) for b in blobs]))
            mn = jnp.asarray(np.stack([b.x_min for b in blobs]))
            mx = jnp.asarray(np.stack([b.x_max for b in blobs]))
        out = perchannel_decode_batch(
            words, mn, mx, first.bits, first.shape, first.axis,
            out_dtype=jnp.dtype(out_dtype),
        )
        return [out[i] for i in range(len(blobs))]

    def wire_size_bytes(self, shape: Tuple[int, ...], bits: int) -> int:
        n = int(np.prod(shape)) if shape else 1
        c = shape[channel_axis(len(shape))] if shape else 1
        if n == 0 or c == 0:
            return 8 * c + 1
        return c * perchannel_words(n // c, bits) * 4 + 8 * c + 1

    def transfer_size_batch(self, x: jnp.ndarray, bits_list: Sequence[int]
                            ) -> List[int]:
        """Fixed-rate: channel-major word count + vector header are both
        shape-only, so calibration records the whole S_i(c) column with
        zero device launches."""
        shape = tuple(x.shape)
        return [self.wire_size_bytes(shape, int(b)) for b in bits_list]

    def simulate(self, x: jnp.ndarray, bits: int) -> jnp.ndarray:
        return q.quantize_dequantize(x, bits, axis=channel_axis(x.ndim))


register_codec(PerChannelCodec())
