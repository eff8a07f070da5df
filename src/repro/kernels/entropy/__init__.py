"""Device-resident entropy-codec kernels.

The two-phase batched Huffman encode (one histogram dispatch + one
fused quantize/LUT-gather/scan/pack XLA program) behind
``repro.codec.huffman``. See ``docs/kernels.md`` for the stream layout
and the byte-identity contract with ``repro.core.entropy``.
"""
from repro.kernels.entropy.ops import (
    PACK_MAX_CODE_BITS,
    huffman_encode_batch_device,
)

__all__ = [
    "PACK_MAX_CODE_BITS",
    "huffman_encode_batch_device",
]
