"""Device-side boundary codec: fused Pallas quantize+pack, no entropy stage.

The paper runs the whole edge half of the codec (quantize *and* Huffman)
on the host CPU — the side with the least compute. This codec moves the
edge encode onto the accelerator: **one** fused ``quantize_pack``
pallas_call does the hierarchical min/max reduction, the affine quantize
and the nibble packing (bits<=4) in a single two-phase launch — codes
never touch HBM between the affine map and the pack — and the host only
frames the resulting bytes (device->host copy, trim to the exact element
count). The cloud decode is the symmetric single fused launch
(``dequantize_wire``: re-pad to tiles, unpack, dequant, cast).

Both halves are batched: ``encode_batch``/``decode_batch`` stack B
same-shape boundary tensors and run one launch with per-sample (min, max)
scalars, amortizing the dispatch overhead the serving pipeline used to
pay per request. Each sample's bytes are identical to encoding it alone.

Wire format: nibble-packed uint8 for bits<=4 (two codes/byte), one uint8
per element for 4<bits<=8, little-endian uint16 for 8<bits<=16. No
entropy coding means the size is shape-only — the S_i(c) predictor needs
no data pass — and encode latency is independent of the feature
distribution, at the price of a larger payload than Huffman on sparse
feature maps (the ILP weighs exactly that trade).
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
from repro.kernels.quantize import (
    dequantize_wire,
    dequantize_wire_batch,
    quantize_pack,
    quantize_pack_stack,
)
from repro.utils.trace import span


def _payload_bytes(n: int, bits: int) -> int:
    if bits <= 4:
        return (n + 1) // 2
    if bits <= 8:
        return n
    return 2 * n


def _frame(flat: np.ndarray, n: int, bits: int) -> bytes:
    """Host-side framing only: trim the tile padding off one sample's flat
    device codes. The packed stream is pairs of consecutive codes (full
    128-lane rows), so a byte-count trim is exact for every n."""
    if bits <= 4:
        return flat[: (n + 1) // 2].tobytes()
    if bits <= 8:
        return flat[:n].tobytes()
    return flat[:n].astype("<u2").tobytes()


class BitpackCodec(BoundaryCodec):
    name = "bitpack"
    value_key = "tensor"

    def encode(self, x: jnp.ndarray, bits: int) -> WireBlob:
        shape = tuple(x.shape)
        n = int(x.size)
        if n == 0:
            return WireBlob(self.name, b"", shape, bits,
                            np.float32(0.0), np.float32(0.0))
        codes, mn, mx = quantize_pack(jnp.asarray(x), bits)
        with span("sync"):
            codes, mn, mx = jax.device_get((codes, mn, mx))
        with span("codec.frame"):
            payload = _frame(codes.reshape(-1), n, bits)
        return WireBlob(self.name, payload, shape, bits,
                        np.float32(mn), np.float32(mx))

    def encode_batch(self, xs: Sequence[jnp.ndarray], bits: int
                     ) -> List[WireBlob]:
        xs = list(xs)
        shapes = [tuple(x.shape) for x in xs]
        if not stackable_shapes(shapes):
            return [self.encode(x, bits) for x in xs]
        shape = shapes[0]
        n = int(np.prod(shape))
        codes, mn, mx = quantize_pack_stack(
            tuple(jnp.asarray(x) for x in xs), bits
        )
        with span("sync"):
            codes, mn, mx = jax.device_get((codes, mn, mx))
        with span("codec.frame"):
            flat = codes.reshape(len(xs), -1)
            mn = np.asarray(mn, np.float32)
            mx = np.asarray(mx, np.float32)
            return [
                WireBlob(self.name, _frame(flat[i], n, bits), shape, bits,
                         mn[i], mx[i])
                for i in range(len(xs))
            ]

    def _wire_codes(self, blob: WireBlob) -> np.ndarray:
        if blob.bits <= 8:
            return np.frombuffer(blob.payload, np.uint8)
        return np.frombuffer(blob.payload, "<u2").astype(np.uint16)

    def decode(self, blob: WireBlob, out_dtype=jnp.float32) -> jnp.ndarray:
        if blob.num_elements == 0:
            return jnp.zeros(blob.shape, out_dtype)
        with span("codec.unframe"):
            codes = jnp.asarray(self._wire_codes(blob))
        return dequantize_wire(
            codes, blob.x_min, blob.x_max, blob.bits, blob.shape,
            out_dtype=out_dtype,
        )

    def decode_batch(self, blobs: Sequence[WireBlob],
                     out_dtype=jnp.float32) -> List[jnp.ndarray]:
        blobs = list(blobs)
        shapes = [b.shape for b in blobs]
        if (not stackable_shapes(shapes)
                or len({b.bits for b in blobs}) != 1):
            return [self.decode(b, out_dtype) for b in blobs]
        bits = blobs[0].bits
        with span("codec.unframe"):
            flat = jnp.asarray(
                np.stack([self._wire_codes(b) for b in blobs]))
            mn = np.stack([np.float32(b.x_min) for b in blobs])
            mx = np.stack([np.float32(b.x_max) for b in blobs])
        out = dequantize_wire_batch(flat, mn, mx, bits, blobs[0].shape,
                                    out_dtype=out_dtype)
        return [out[i] for i in range(len(blobs))]

    def wire_size_bytes(self, shape: Tuple[int, ...], bits: int) -> int:
        n = int(np.prod(shape)) if shape else 1
        return _payload_bytes(n, bits) + 9

    def transfer_size_batch(self, x: jnp.ndarray, bits_list: Sequence[int]
                            ) -> List[int]:
        """Fixed-rate: the whole S_i(c) column is shape-only — zero device
        launches and zero data passes during calibration."""
        n = int(x.size)
        return [_payload_bytes(n, int(b)) + 9 for b in bits_list]


register_codec(BitpackCodec())
