"""The paper's boundary codec: per-tensor min-max quantize + canonical
Huffman entropy coding (Sec. III-B).

Edge side: the two-phase device-resident batched encode of
``repro.kernels.entropy`` — one histogram dispatch (only the
``(B, 2^bits)`` counts reach the host, where the canonical table is
built) and one fused quantize + LUT-gather + scan + pack XLA program
that emits the packed bitstream words. Quantized codes never touch HBM
or the PCIe link. Pathological deep-tree distributions (any code longer
than ``PACK_MAX_CODE_BITS``) fall back to the host reference encoder in
``repro.core.entropy``, which is the byte-identity oracle the device
path is pinned against either way.

Cloud side: Huffman-decode on the host, then one fused Pallas
dequant+cast launch (``dequantize_codes``; batched stacks share a
single ``dequantize_codes_batch`` launch). Codes wider than 8 bits
travel as uint16 through the same fused kernel — no float fallback.

The payload is byte-identical to the pre-refactor
``repro.core.compression.compress`` wire format (pinned by
``tests/test_codec.py``).
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import numpy as np
import jax.numpy as jnp

from repro.codec.base import (
    BoundaryCodec, WireBlob, register_codec, stackable_shapes,
)
from repro.core import entropy as ent
from repro.core import quantization as q


@functools.partial(jax.jit, static_argnames=("bits_list",))
def _calib_histograms(x: jnp.ndarray, bits_list: Tuple[int, ...]
                      ) -> jnp.ndarray:
    """Symbol histograms of the quantized boundary at every bit width in
    ONE device launch: the quantize is re-traced per width (the min/max
    reductions CSE, and min/max are exactly associative so the codes are
    bitwise-identical to quantizing eagerly per width), and only the
    ``(C, 2^max_bits)`` counts ever reach the host."""
    n_max = 1 << max(bits_list)
    return jnp.stack([
        jnp.bincount(q.quantize(x, bits).values.reshape(-1), length=n_max)
        for bits in bits_list
    ])


class HuffmanCodec(BoundaryCodec):
    name = "huffman"
    value_key = "tensor"

    def _encode_host(self, x: jnp.ndarray, bits: int) -> WireBlob:
        """Host reference path: eager quantize, full code transfer,
        numpy bitstream build. The byte-identity oracle for the device
        path, and the route for deep-tree distributions it rejects."""
        quantized = q.quantize(jnp.asarray(x), bits)
        codes = np.asarray(quantized.values)
        payload = ent.huffman_encode(codes, 1 << bits)
        return WireBlob(
            self.name, payload, tuple(x.shape), bits,
            np.float32(quantized.x_min), np.float32(quantized.x_max),
        )

    def encode(self, x: jnp.ndarray, bits: int) -> WireBlob:
        shape = tuple(x.shape)
        if x.size == 0:
            return WireBlob(self.name, b"", shape, bits,
                            np.float32(0.0), np.float32(0.0))
        from repro.kernels.entropy import huffman_encode_batch_device

        dev = huffman_encode_batch_device(jnp.asarray(x)[None], bits)
        if dev is None:
            return self._encode_host(x, bits)
        payloads, mn, mx = dev
        return WireBlob(self.name, payloads[0], shape, bits,
                        np.float32(mn[0]), np.float32(mx[0]))

    def encode_batch(self, xs: Sequence[jnp.ndarray], bits: int
                     ) -> List[WireBlob]:
        xs = list(xs)
        shapes = [tuple(x.shape) for x in xs]
        if not stackable_shapes(shapes):
            return [self.encode(x, bits) for x in xs]
        from repro.kernels.entropy import huffman_encode_batch_device

        dev = huffman_encode_batch_device(jnp.stack(
            [jnp.asarray(x) for x in xs]), bits)
        if dev is None:
            return [self.encode(x, bits) for x in xs]
        payloads, mn, mx = dev
        return [
            WireBlob(self.name, payloads[i], shapes[i], bits,
                     np.float32(mn[i]), np.float32(mx[i]))
            for i in range(len(xs))
        ]

    def decode(self, blob: WireBlob, out_dtype=jnp.float32) -> jnp.ndarray:
        if blob.num_elements == 0:
            return jnp.zeros(blob.shape, out_dtype)
        from repro.kernels.quantize import dequantize_codes

        # dequantize_codes narrows to the kernel's code dtype (uint8, or
        # uint16 for bits > 8) internally.
        codes = ent.huffman_decode(blob.payload)
        return dequantize_codes(
            jnp.asarray(codes.reshape(blob.shape)),
            blob.x_min, blob.x_max, blob.bits, blob.shape,
            out_dtype=out_dtype,
        )

    def decode_batch(self, blobs: Sequence[WireBlob],
                     out_dtype=jnp.float32) -> List[jnp.ndarray]:
        blobs = list(blobs)
        shapes = [tuple(b.shape) for b in blobs]
        if (not stackable_shapes(shapes)
                or len({b.bits for b in blobs}) != 1):
            return [self.decode(b, out_dtype) for b in blobs]
        from repro.kernels.quantize import dequantize_codes_batch

        # Host entropy decode per payload (data-dependent lengths), then
        # ONE fused batched dequant+cast launch over the stacked codes.
        codes = np.stack([ent.huffman_decode(b.payload) for b in blobs])
        mn = np.stack([np.float32(b.x_min) for b in blobs])
        mx = np.stack([np.float32(b.x_max) for b in blobs])
        out = dequantize_codes_batch(
            jnp.asarray(codes), jnp.asarray(mn), jnp.asarray(mx),
            int(blobs[0].bits), shapes[0], out_dtype=out_dtype,
        )
        return [out[i] for i in range(len(blobs))]

    def wire_size_bytes(self, shape: Tuple[int, ...], bits: int) -> int:
        """Upper bound: Huffman is an optimal prefix code, so its payload
        never exceeds the fixed-width encoding (``bits`` per symbol) plus
        the code-length table header."""
        n = int(np.prod(shape)) if shape else 1
        table = 6 + (1 << bits)
        return table + (n * bits + 7) // 8 + 9

    def transfer_size_bytes(self, x: jnp.ndarray, bits: int) -> int:
        """Exact post-Huffman size from the one-launch device histogram —
        only the ``(2^bits,)`` counts reach the host, same path as
        :meth:`transfer_size_batch` (the full code array never
        transfers)."""
        if x.size == 0:
            return 9
        hist = np.asarray(_calib_histograms(jnp.asarray(x),
                                            (int(bits),)))[0]
        return ent.huffman_size_from_counts(hist[: 1 << bits]) + 9

    def transfer_size_batch(self, x: jnp.ndarray, bits_list: Sequence[int]
                            ) -> List[int]:
        """Exact post-Huffman sizes for every bit width from one batched
        device histogram launch + one small host transfer — instead of C
        host encodes of the full code array (the calibration hot path)."""
        bits_t = tuple(int(b) for b in bits_list)
        if not bits_t:
            return []
        if x.size == 0:
            return [9] * len(bits_t)
        hists = np.asarray(_calib_histograms(jnp.asarray(x), bits_t))
        return [
            ent.huffman_size_from_counts(hists[i, : 1 << bits]) + 9
            for i, bits in enumerate(bits_t)
        ]


register_codec(HuffmanCodec())
