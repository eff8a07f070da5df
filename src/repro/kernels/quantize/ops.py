"""Jitted public wrappers around the Pallas quantization kernels.

Handles arbitrary input shapes/dtypes: flattens to 2-D, pads to
(block_m, 128) tiles, launches the kernels, and unpads. Every entry point
has a ``*_batch`` sibling that adds a leading sample axis — one launch
encodes/decodes a stack of B same-shape boundary tensors with per-sample
(min, max) scalars (the serving pipeline's micro-batched edge encode).

``interpret`` defaults to True off-TPU (this container) and False on TPU.

The un-jitted ``*_impl`` functions are exported for
``benchmarks/codec.py``: called eagerly they dispatch each pallas_call
through the module launch counter (``count_launches``), which is how the
benchmark reports launches-per-encode for the fused vs. the PR 2
three-launch path.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.quantize import quantize as k

LANES = k.LANES


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


@contextlib.contextmanager
def _counting(counter: str):
    class _Box:
        count = 0

    box = _Box()
    start = getattr(k, counter)
    try:
        yield box
    finally:
        box.count = getattr(k, counter) - start


def count_launches():
    """Count pallas_call dispatches issued inside the block. Only eager
    (un-jitted ``*_impl``) calls dispatch per invocation — under jit the
    launches happen once at trace time — so measure against the impls."""
    return _counting("LAUNCH_COUNT")


def count_host_routes():
    """Count batched Huffman encodes inside the block that left the
    device path for the per-tensor host encoder (deep trees, overlong
    streams)."""
    return _counting("HOST_ROUTE_COUNT")


def _sublane(bits: int) -> int:
    """Sublane multiple of the (M, 128) tiling for a given code width.

    The row count must satisfy the deepest TPU min-tile among the dtypes
    a launch touches: the f32 input needs (8, 128), uint8/int8 codes need
    (32, 128), and uint16 codes (bits > 8) need only (16, 128) — so
    wide-code tensors (the per-token streaming boundary at high rates)
    pad to half the rows. ``bits == 0`` (callers that don't know the
    width) keeps the conservative 32."""
    return 16 if bits > 8 else 32


def _tile_rows(n_elem: int, block_m: int, bits: int = 0) -> int:
    """Padded row count of the (M, 128) tiling for ``n_elem`` elements:
    a multiple of the sublane requirement of this code width (see
    ``_sublane``), then a multiple of the block that actually launches
    (``min(block_m, rows)``) — so small boundary tensors get a single
    right-sized block instead of padding out to ``block_m`` rows.
    Zero-element inputs still map to one well-formed all-padding block.
    Encode and decode must agree on ``bits`` — the wire payload is
    trimmed to the exact element count, but the re-padded tile grid the
    decoder rebuilds has to match the one the encoder emitted."""
    sub = _sublane(bits)
    rows = max((n_elem + LANES - 1) // LANES, 1)
    rows = (rows + sub - 1) // sub * sub
    bm = min(block_m, rows)
    return (rows + bm - 1) // bm * bm


def _to_tiles(x: jnp.ndarray, block_m: int, bits: int = 0
              ) -> Tuple[jnp.ndarray, int]:
    """Flatten to (M, 128) and pad M to a block multiple. Returns the padded
    2-D array and the original element count."""
    x3d, n_elem = _to_tiles_batch(x.reshape(1, -1), block_m, bits)
    return x3d[0], n_elem


def _pad_rows(flat: jnp.ndarray, cols: int, rows_pad: int,
              fill_first: bool) -> jnp.ndarray:
    """(B, n) -> (B, rows_pad, cols), n > 0. The padding repeats each
    sample's first element when ``fill_first`` (so it never changes the
    sample's min/max), else it is zero.

    The whole ``cols``-wide rows are relaid out on their own, behind an
    ``optimization_barrier``, and the padding is read from that copy:
    fused into the concatenate, or read from the flat view, the relayout
    of a large feature map (e.g. (8, 256, 56, 56) f32) takes the TPU
    compiler minutes."""
    bsz, n = flat.shape
    full = n // cols
    tail = n - full * cols
    parts = []
    if full:
        parts.append(jax.lax.optimization_barrier(
            flat[:, :full * cols].reshape(bsz, full, cols)))
        fill = parts[0][:, 0, :1]
    else:
        fill = flat[:, :1]
    if not fill_first:
        fill = jnp.zeros_like(fill)
    if tail:
        pad = jnp.broadcast_to(fill, (bsz, cols - tail))
        row = jnp.concatenate([flat[:, full * cols:], pad], axis=1)
        parts.append(row[:, None, :])
    used = full + (1 if tail else 0)
    if rows_pad > used:
        parts.append(jnp.broadcast_to(fill[:, :, None],
                                      (bsz, rows_pad - used, cols)))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _to_tiles_batch(xb: jnp.ndarray, block_m: int, bits: int = 0
                    ) -> Tuple[jnp.ndarray, int]:
    """(B, *shape) -> (B, M, 128), padding each sample with its own first
    element so padding never changes its min/max (zeros for an empty
    input, which has no min/max to preserve)."""
    bsz = xb.shape[0]
    n_elem = int(np.prod(xb.shape[1:])) if xb.ndim > 1 else 1
    flat = xb.reshape(bsz, -1)
    rows_pad = _tile_rows(n_elem, block_m, bits)
    if not n_elem:
        return jnp.zeros((bsz, rows_pad, LANES), flat.dtype), n_elem
    return _pad_rows(flat, LANES, rows_pad, True), n_elem


# ---------------------------------------------------------------------------
# Edge encode: fused single-launch (and the PR 2 three-launch reference)
# ---------------------------------------------------------------------------


def quantize_pack_impl(x, bits, block_m=k.DEFAULT_BLOCK_M, interpret=None):
    if interpret is None:
        interpret = _should_interpret()
    x2d, _ = _to_tiles(x, block_m, bits)
    bm = min(block_m, x2d.shape[0])
    codes, mn, mx = k.fused_encode_blocks(x2d[None], bits, bm,
                                          interpret=interpret)
    return codes[0], mn[0], mx[0]


@functools.partial(jax.jit, static_argnames=("bits", "block_m", "interpret"))
def quantize_pack(
    x: jnp.ndarray,
    bits: int,
    block_m: int = k.DEFAULT_BLOCK_M,
    interpret: bool | None = None,
):
    """Fused min/max + affine quantization (+ nibble packing for bits<=4)
    in **one** pallas_call (two-phase grid: hierarchical min/max reduction,
    then quantize+pack against the reduced per-tensor scalars).

    Returns (codes, mn, mx). codes is packed uint8 (two codes/byte) for
    bits<=4, uint8 of x.size elements for 4<bits<=8, and uint16 for
    8<bits<=16 — byte-identical to the PR 2 three-launch path.
    """
    return quantize_pack_impl(x, bits, block_m, interpret)


def quantize_pack_batch_impl(xb, bits, block_m=k.DEFAULT_BLOCK_M,
                             interpret=None):
    if interpret is None:
        interpret = _should_interpret()
    x3d, _ = _to_tiles_batch(xb, block_m, bits)
    bm = min(block_m, x3d.shape[1])
    return k.fused_encode_blocks(x3d, bits, bm, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "block_m", "interpret"))
def quantize_pack_batch(
    xb: jnp.ndarray,
    bits: int,
    block_m: int = k.DEFAULT_BLOCK_M,
    interpret: bool | None = None,
):
    """Batched :func:`quantize_pack`: one launch encodes a (B, *shape)
    stack with per-sample (min, max). Returns (codes (B, M, W), mn (B,),
    mx (B,)); each sample's codes are byte-identical to encoding it
    alone."""
    return quantize_pack_batch_impl(xb, bits, block_m, interpret)


@functools.partial(jax.jit, static_argnames=("bits", "block_m", "interpret"))
def quantize_pack_stack(
    xs,
    bits: int,
    block_m: int = k.DEFAULT_BLOCK_M,
    interpret: bool | None = None,
):
    """:func:`quantize_pack_batch` over a tuple of same-shape tensors —
    the stack happens inside the jitted program, so a micro-batch costs
    one dispatch total (an eager ``jnp.stack`` alone costs more than the
    whole fused kernel for small boundary tensors)."""
    return quantize_pack_batch_impl(jnp.stack(xs), bits, block_m, interpret)


def quantize_pack_threelaunch_impl(x, bits, block_m=k.DEFAULT_BLOCK_M,
                                   interpret=None):
    """The PR 2 edge encode: three pallas_calls (minmax -> quantize ->
    pack4) with the codes round-tripping HBM between quantize and pack.
    Kept as the byte-identity reference and benchmark baseline for the
    fused single-launch path."""
    if interpret is None:
        interpret = _should_interpret()
    x2d, _ = _to_tiles(x, block_m, bits)
    bm = min(block_m, x2d.shape[0])
    mn, mx = k.minmax_blocks(x2d, bm, interpret=interpret)
    codes2d = k.quantize_blocks(x2d, mn, mx, bits, bm, interpret=interpret)
    if bits <= 4:
        packed = k.pack4_blocks(codes2d, bm, interpret=interpret)
        return packed, mn, mx
    return codes2d, mn, mx


@functools.partial(jax.jit, static_argnames=("bits", "block_m", "interpret"))
def quantize_pack_threelaunch(
    x: jnp.ndarray,
    bits: int,
    block_m: int = k.DEFAULT_BLOCK_M,
    interpret: bool | None = None,
):
    return quantize_pack_threelaunch_impl(x, bits, block_m, interpret)


# ---------------------------------------------------------------------------
# Cloud decode: fused (unpack+)dequant+cast, per-tensor and batched
# ---------------------------------------------------------------------------


def dequantize_unpack_impl(codes2d, mn, mx, bits, shape,
                           block_m=k.DEFAULT_BLOCK_M, interpret=None,
                           out_dtype=jnp.float32):
    if interpret is None:
        interpret = _should_interpret()
    bm = min(block_m, codes2d.shape[0])
    x3d = k.fused_decode_blocks(
        codes2d[None],
        jnp.reshape(jnp.asarray(mn, jnp.float32), (1,)),
        jnp.reshape(jnp.asarray(mx, jnp.float32), (1,)),
        bits, bm, out_dtype, packed=bits <= 4, interpret=interpret,
    )
    n_elem = int(np.prod(shape))
    return x3d.reshape(-1)[:n_elem].reshape(shape)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "shape", "block_m", "interpret", "out_dtype"),
)
def dequantize_unpack(
    codes2d: jnp.ndarray,
    mn,
    mx,
    bits: int,
    shape: Tuple[int, ...],
    block_m: int = k.DEFAULT_BLOCK_M,
    interpret: bool | None = None,
    out_dtype=jnp.float32,
):
    """Inverse of quantize_pack; ``shape`` is the original tensor shape.

    One fused ``pallas_call``: int4 nibble unpack (when bits<=4), the
    affine dequant, and the cast to ``out_dtype`` all happen in-kernel.
    """
    return dequantize_unpack_impl(codes2d, mn, mx, bits, shape, block_m,
                                  interpret, out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "shape", "block_m", "interpret", "out_dtype"),
)
def dequantize_codes(
    codes: jnp.ndarray,
    mn,
    mx,
    bits: int,
    shape: Tuple[int, ...],
    block_m: int = k.DEFAULT_BLOCK_M,
    interpret: bool | None = None,
    out_dtype=jnp.float32,
):
    """Cloud-side boundary codec: unpacked integer codes (any shape, e.g.
    straight from the Huffman decoder; uint8, or uint16 when bits > 8) ->
    dequantized ``out_dtype`` tensor of ``shape`` in a single fused
    dequant+cast ``pallas_call``."""
    if interpret is None:
        interpret = _should_interpret()
    q2d, _ = _to_tiles(codes.astype(k.code_dtype(bits)), block_m, bits)
    bm = min(block_m, q2d.shape[0])
    x3d = k.fused_decode_blocks(
        q2d[None],
        jnp.reshape(jnp.asarray(mn, jnp.float32), (1,)),
        jnp.reshape(jnp.asarray(mx, jnp.float32), (1,)),
        bits, bm, out_dtype, packed=False, interpret=interpret,
    )
    n_elem = int(np.prod(shape))
    return x3d.reshape(-1)[:n_elem].reshape(shape)


def dequantize_codes_batch_impl(codes2, mn, mx, bits, shape,
                                block_m=k.DEFAULT_BLOCK_M, interpret=None,
                                out_dtype=jnp.float32):
    if interpret is None:
        interpret = _should_interpret()
    bsz = codes2.shape[0]
    n_elem = int(np.prod(shape))
    if n_elem == 0:
        return jnp.zeros((bsz,) + tuple(shape), out_dtype)
    q3d, _ = _to_tiles_batch(codes2.astype(k.code_dtype(bits)).reshape(
        bsz, -1), block_m, bits)
    bm = min(block_m, q3d.shape[1])
    x3d = k.fused_decode_blocks(
        q3d,
        jnp.asarray(mn, jnp.float32).reshape(bsz),
        jnp.asarray(mx, jnp.float32).reshape(bsz),
        bits, bm, out_dtype, packed=False, interpret=interpret,
    )
    return x3d.reshape(bsz, -1)[:, :n_elem].reshape((bsz,) + tuple(shape))


@functools.partial(
    jax.jit,
    static_argnames=("bits", "shape", "block_m", "interpret", "out_dtype"),
)
def dequantize_codes_batch(
    codes2: jnp.ndarray,
    mn,
    mx,
    bits: int,
    shape: Tuple[int, ...],
    block_m: int = k.DEFAULT_BLOCK_M,
    interpret: bool | None = None,
    out_dtype=jnp.float32,
):
    """Batched :func:`dequantize_codes`: a (B, n) stack of unpacked
    integer codes (e.g. B host-Huffman-decoded payloads) + (B,) ranges
    -> (B, *shape) activations in one fused dequant+cast launch. Unlike
    :func:`dequantize_wire_batch` the codes are one-per-element at every
    bit width — the entropy coder's decode output, not the bitpack wire
    layout."""
    return dequantize_codes_batch_impl(codes2, mn, mx, bits, shape,
                                       block_m, interpret, out_dtype)


def _wire_tiles(codes_flat: jnp.ndarray, n_elem: int, bits: int,
                block_m: int) -> jnp.ndarray:
    """Re-pad flat wire codes (per sample) to the 2-D tile layout
    ``quantize_pack`` emitted."""
    cols = LANES // 2 if bits <= 4 else LANES
    rows_pad = _tile_rows(n_elem, block_m, bits)
    lead = codes_flat.shape[:-1]
    flat = codes_flat.reshape((-1, codes_flat.shape[-1]))
    return _pad_rows(flat, cols, rows_pad, False).reshape(
        lead + (rows_pad, cols))


def dequantize_wire_impl(codes_flat, mn, mx, bits, shape,
                         block_m=k.DEFAULT_BLOCK_M, interpret=None,
                         out_dtype=jnp.float32):
    if interpret is None:
        interpret = _should_interpret()
    n_elem = int(np.prod(shape))
    if n_elem == 0:
        return jnp.zeros(shape, out_dtype)
    q2d = _wire_tiles(codes_flat.reshape(-1), n_elem, bits, block_m)
    return dequantize_unpack_impl(q2d, mn, mx, bits, shape, block_m,
                                  interpret, out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "shape", "block_m", "interpret", "out_dtype"),
)
def dequantize_wire(
    codes_flat: jnp.ndarray,
    mn,
    mx,
    bits: int,
    shape: Tuple[int, ...],
    block_m: int = k.DEFAULT_BLOCK_M,
    interpret: bool | None = None,
    out_dtype=jnp.float32,
):
    """Cloud-side decode of the *bitpack wire format*: the flat device
    codes exactly as ``quantize_pack`` emitted them, trimmed to the
    elements of ``shape`` (nibble-packed uint8 for bits<=4, one uint8 per
    element for 4<bits<=8, uint16 for 8<bits<=16). Re-pads to the tile
    grid and runs the fused (unpack+)dequant+cast kernel in one launch."""
    return dequantize_wire_impl(codes_flat, mn, mx, bits, shape, block_m,
                                interpret, out_dtype)


def dequantize_wire_batch_impl(codes_flat, mn, mx, bits, shape,
                               block_m=k.DEFAULT_BLOCK_M, interpret=None,
                               out_dtype=jnp.float32):
    if interpret is None:
        interpret = _should_interpret()
    bsz = codes_flat.shape[0]
    n_elem = int(np.prod(shape))
    if n_elem == 0:
        return jnp.zeros((bsz,) + tuple(shape), out_dtype)
    q3d = _wire_tiles(codes_flat.reshape(bsz, -1), n_elem, bits, block_m)
    bm = min(block_m, q3d.shape[1])
    x3d = k.fused_decode_blocks(
        q3d,
        jnp.asarray(mn, jnp.float32).reshape(bsz),
        jnp.asarray(mx, jnp.float32).reshape(bsz),
        bits, bm, out_dtype, packed=bits <= 4, interpret=interpret,
    )
    return x3d.reshape(bsz, -1)[:, :n_elem].reshape((bsz,) + tuple(shape))


@functools.partial(
    jax.jit,
    static_argnames=("bits", "shape", "block_m", "interpret", "out_dtype"),
)
def dequantize_wire_batch(
    codes_flat: jnp.ndarray,
    mn,
    mx,
    bits: int,
    shape: Tuple[int, ...],
    block_m: int = k.DEFAULT_BLOCK_M,
    interpret: bool | None = None,
    out_dtype=jnp.float32,
):
    """Batched :func:`dequantize_wire`: (B, n_wire) flat codes + (B,)
    ranges -> (B, *shape) activations, one launch. Each sample decodes
    bit-identically to decoding it alone."""
    return dequantize_wire_batch_impl(codes_flat, mn, mx, bits, shape,
                                      block_m, interpret, out_dtype)


def shard_batch(fn, mesh, batch_axis: str = "data"):
    """``fn(codes, mn, mx)`` — a batched decode whose operands and result
    all lead with the sample axis — run on each device's block of that
    axis. XLA cannot partition a Mosaic kernel, so a caller whose
    operands are sharded hands each device its own block
    (``shard_map``); devices along the other mesh axes decode the same
    block."""
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axis)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)


@functools.lru_cache(maxsize=None)
def _wire_decode_sharded_fn(mesh, batch_axis, bits, shape, block_m,
                            interpret, out_dtype):
    from jax.sharding import NamedSharding, PartitionSpec as P

    row = NamedSharding(mesh, P(batch_axis))          # (B,) scalars
    codes = NamedSharding(mesh, P(batch_axis, None))  # (B, n_wire)
    out = NamedSharding(mesh, P(batch_axis, *([None] * len(shape))))

    def fn(codes_flat, mn, mx):
        return dequantize_wire_batch_impl(codes_flat, mn, mx, bits, shape,
                                          block_m, interpret, out_dtype)

    return jax.jit(shard_batch(fn, mesh, batch_axis),
                   in_shardings=(codes, row, row), out_shardings=out)


def dequantize_wire_batch_sharded(
    codes_flat,
    mn,
    mx,
    bits: int,
    shape: Tuple[int, ...],
    mesh,
    batch_axis: str = "data",
    block_m: int = k.DEFAULT_BLOCK_M,
    interpret: bool | None = None,
    out_dtype=jnp.float32,
):
    """:func:`dequantize_wire_batch` decoding straight into per-device
    batch shards: the (B, n_wire) codes enter sharded over ``batch_axis``
    and the (B, *shape) activations LEAVE sharded the same way — no host
    gather, no replicated intermediate, ready for a sharded tail forward
    (each sample still decodes bit-identically to decoding it alone;
    pinned in ``tests/test_meshed.py``). B must divide the mesh's
    ``batch_axis`` extent — the meshed cloud worker pads the group to a
    multiple before calling. The sharded-jitted callable is cached per
    (mesh, wire format)."""
    if interpret is None:
        interpret = _should_interpret()
    fn = _wire_decode_sharded_fn(
        mesh, str(batch_axis), int(bits), tuple(int(s) for s in shape),
        int(block_m), bool(interpret), jnp.dtype(out_dtype),
    )
    return fn(jnp.asarray(codes_flat), jnp.asarray(mn, jnp.float32),
              jnp.asarray(mx, jnp.float32))


# ---------------------------------------------------------------------------
# Per-channel codec: fused vector-range quantize + in-kernel c-bit pack
# ---------------------------------------------------------------------------


def perchannel_words(n_per_ch: int, bits: int) -> int:
    """uint32 words per channel on the wire (codes never straddle a
    word; channels never share a word)."""
    per_word = 32 // bits
    return (n_per_ch + per_word - 1) // per_word


def _channel_major(xb: jnp.ndarray, axis: int) -> jnp.ndarray:
    """(B, *shape) -> (B, C, L) float32, channel axis of each sample moved
    to the front and the rest flattened."""
    bsz = xb.shape[0]
    c = xb.shape[axis + 1]
    return jnp.moveaxis(xb, axis + 1, 1).reshape(bsz, c, -1).astype(
        jnp.float32
    )


def perchannel_encode_batch_impl(xb, bits, axis, interpret=None):
    if interpret is None:
        interpret = _should_interpret()
    xc = _channel_major(xb, axis)
    mn = jnp.min(xc, axis=2)
    mx = jnp.max(xc, axis=2)
    words = k.pc_encode_blocks(xc, mn, mx, bits, interpret=interpret)
    return words, mn, mx


@functools.partial(jax.jit, static_argnames=("bits", "axis", "interpret"))
def perchannel_encode_batch(
    xb: jnp.ndarray,
    bits: int,
    axis: int,
    interpret: bool | None = None,
):
    """Device-side per-channel edge encode, batched: one fused launch does
    the per-channel affine quantize (vector (min, scale) operands) and the
    in-kernel c-bit pack. Returns (words (B, C, W_pad) uint32, mn (B, C),
    mx (B, C)); the host trims each channel row to
    ``perchannel_words(L, bits)`` words (framing only)."""
    return perchannel_encode_batch_impl(xb, bits, axis, interpret)


@functools.partial(jax.jit, static_argnames=("bits", "axis", "interpret"))
def perchannel_encode_stack(
    xs,
    bits: int,
    axis: int,
    interpret: bool | None = None,
):
    """:func:`perchannel_encode_batch` over a tuple of same-shape tensors
    (in-jit stack, one dispatch per micro-batch)."""
    return perchannel_encode_batch_impl(jnp.stack(xs), bits, axis,
                                        interpret)


def perchannel_encode_impl(x, bits, axis, interpret=None):
    words, mn, mx = perchannel_encode_batch_impl(x[None], bits, axis,
                                                 interpret)
    return words[0], mn[0], mx[0]


@functools.partial(jax.jit, static_argnames=("bits", "axis", "interpret"))
def perchannel_encode(
    x: jnp.ndarray,
    bits: int,
    axis: int,
    interpret: bool | None = None,
):
    """Single-tensor :func:`perchannel_encode_batch` (B = 1 internally)."""
    return perchannel_encode_impl(x, bits, axis, interpret)


def perchannel_decode_batch_impl(words3, mn2, mx2, bits, shape, axis,
                                 out_dtype=jnp.float32, interpret=None):
    if interpret is None:
        interpret = _should_interpret()
    bsz, c, _ = words3.shape
    length = int(np.prod(shape)) // c
    out = k.pc_decode_blocks(words3, mn2, mx2, bits, length, out_dtype,
                             interpret=interpret)
    rest = tuple(s for i, s in enumerate(shape) if i != axis)
    outc = out[:, :, :length].reshape((bsz, c) + rest)
    return jnp.moveaxis(outc, 1, axis + 1)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "shape", "axis", "out_dtype", "interpret"),
)
def perchannel_decode_batch(
    words3: jnp.ndarray,
    mn2,
    mx2,
    bits: int,
    shape: Tuple[int, ...],
    axis: int,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
):
    """Batched cloud half of the per-channel codec: (B, C, W) uint32 wire
    words + (B, C) ranges -> (B, *shape) activations in one fused
    unpack + dequant + cast launch."""
    return perchannel_decode_batch_impl(words3, mn2, mx2, bits, shape,
                                        axis, out_dtype, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "shape", "axis", "out_dtype", "interpret"),
)
def perchannel_decode(
    words2: jnp.ndarray,
    mn,
    mx,
    bits: int,
    shape: Tuple[int, ...],
    axis: int,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
):
    """Single-tensor per-channel decode (B = 1 internally)."""
    out = perchannel_decode_batch_impl(
        words2[None], jnp.asarray(mn)[None], jnp.asarray(mx)[None],
        bits, shape, axis, out_dtype, interpret,
    )
    return out[0]


def quantize_dequantize_kernel(x: jnp.ndarray, bits: int,
                               interpret: bool | None = None) -> jnp.ndarray:
    """One-call straight-through path (edge-side simulation)."""
    codes, mn, mx = quantize_pack(x, bits, interpret=interpret)
    return dequantize_unpack(codes, mn, mx, bits, tuple(x.shape),
                             interpret=interpret, out_dtype=x.dtype)
