"""Pallas boundary-feature codec kernels.

Tiling scheme, SMEM scalar layout, the ``interpret=True`` CPU validation
story, and the fused single-launch encode/decode kernels are documented
in ``docs/kernels.md`` (repo root). ``ref.py`` is the pure-jnp oracle
every kernel must match.
"""
from repro.kernels.quantize.ops import (
    quantize_pack,
    quantize_pack_batch,
    quantize_pack_stack,
    quantize_pack_threelaunch,
    dequantize_unpack,
    dequantize_codes,
    dequantize_codes_batch,
    dequantize_wire,
    dequantize_wire_batch,
    perchannel_encode,
    perchannel_encode_batch,
    perchannel_encode_stack,
    perchannel_decode,
    perchannel_decode_batch,
    perchannel_words,
    quantize_dequantize_kernel,
    count_launches,
    count_host_routes,
)

__all__ = [
    "quantize_pack",
    "quantize_pack_batch",
    "quantize_pack_stack",
    "quantize_pack_threelaunch",
    "dequantize_unpack",
    "dequantize_codes",
    "dequantize_codes_batch",
    "dequantize_wire",
    "dequantize_wire_batch",
    "perchannel_encode",
    "perchannel_encode_batch",
    "perchannel_encode_stack",
    "perchannel_decode",
    "perchannel_decode_batch",
    "perchannel_words",
    "quantize_dequantize_kernel",
    "count_launches",
    "count_host_routes",
]
