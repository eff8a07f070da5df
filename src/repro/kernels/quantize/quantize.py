"""Pallas TPU kernels for JALAD boundary-feature quantization.

The compute hot-spot the paper optimizes is the edge-side feature
compression: global min/max -> affine map -> round -> (optionally) nibble
packing. PR 2 ran it as a three-``pallas_call`` chain (minmax -> quantize
-> pack4) that read the feature map from HBM twice and round-tripped the
codes a third time for packing. The encode is now **one launch**:

  1. ``fused_encode_blocks``   — a single ``pallas_call``. Small stacks
     run as one whole-batch VMEM tile (reduce, then quantize + pack, per
     sample); large stacks run a two-phase ``(2, B, M // block_m)`` grid
     whose phase 0 folds each tile's (min, max) into an SMEM accumulator
     and whose phase 1 re-streams the tiles through the fused affine map
     + round + clip (+ nibble pack for bits <= 4) — codes never touch
     HBM between the affine map and the pack.
  2. ``fused_decode_blocks``   — the symmetric cloud half: (nibble unpack
     +) dequant + cast in one launch, batched over a leading sample axis
     with per-sample ``(min, step)`` scalars.
  3. ``pc_encode_blocks`` / ``pc_decode_blocks`` — the per-channel codec
     on the same fused bodies: per-channel ``(min, scale)`` *vectors* as
     kernel operands and an in-kernel c-bit pack to dense uint32 words
     (``32 // c`` codes per word), batched the same way.

Every kernel carries a leading batch axis, so one launch encodes/decodes
a stack of B boundary tensors (the serving pipeline's micro-batched edge
encode). Per-sample scalars live in SMEM (full-array operands and
outputs indexed by the sample id); per-channel range vectors ride as
``(cb, 1)`` VMEM columns.

What Mosaic accepts shapes the bodies: floats reach unsigned code types
through ``int32``; lanes are never sliced with a stride, so the nibble
pack/unpack is an exact bf16 MXU contraction against a constant 0/1/16
selection matrix, and the per-channel kernels receive their codes
word-slot-major (the wrapper lays ``(B, per_word, C, W)`` out in XLA).

The PR 2 three-launch chain (``minmax_blocks`` -> ``quantize_blocks`` ->
``pack4_blocks``) is kept below as the *reference path*: tests pin the
fused kernel's output byte-for-byte against it, and
``benchmarks/codec.py`` uses it as the baseline.

Tiles are (block_m, 128)-shaped: the trailing 128 matches the VPU lane
width; block_m is a multiple of 32 (the uint8 code tile). Tests run the
kernels with ``interpret=True`` against ``ref.py`` and compile them for
a described v5e chip (``tests/test_tpu_compile.py``).

See ``docs/kernels.md`` for the tiling scheme and validation story.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# (block_m, 128) f32 tiles: 2048 rows = 1 MiB per tile — still comfortable
# in ~16 MB VMEM with double buffering, and 8x fewer grid steps than the
# PR 2 default of 256 (each grid step costs a dispatch on TPU and a full
# buffer pass in interpret mode, so coarse tiles win on both targets).
DEFAULT_BLOCK_M = 2048
# Per-channel kernels tile (cb channels) x (chunk elements); chunk is up
# to PC_CHUNK pack-aligned lane groups long and cb is sized to keep one
# tile under PC_TILE_BYTES of f32.
PC_CHUNK = 8
PC_TILE_BYTES = 1 << 20

# pallas_call sites executed (incremented at trace/eager-dispatch time by
# every launcher below). ``benchmarks/codec.py`` reads it through
# ``ops.count_launches`` to report launches-per-encode for each codec path.
LAUNCH_COUNT = 0
# Batched Huffman encodes that left the device path for the per-tensor
# host encoder (``kernels.entropy.ops.huffman_encode_batch_device``
# returned None); read through ``ops.count_host_routes``.
HOST_ROUTE_COUNT = 0

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _launched() -> None:
    global LAUNCH_COUNT
    LAUNCH_COUNT += 1


def _host_routed() -> None:
    global HOST_ROUTE_COUNT
    HOST_ROUTE_COUNT += 1


def code_dtype(bits: int):
    """Narrowest unsigned integer dtype that holds a c-bit code."""
    return jnp.uint8 if bits <= 8 else jnp.uint16


# The nibble pack and unpack are lane selections written as bf16 matmuls
# against constant 0/1/16 matrices: codes <= 15 and the entries are exact
# in bf16 and every dot sums at most two products <= 255 in f32, so the
# contraction is exact.
def _nibble_pack_matrix(lanes: int) -> jnp.ndarray:
    """(lanes, lanes / 2): lane 2j -> byte j times 1, lane 2j + 1 -> byte
    j times 16."""
    r = jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes // 2), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes // 2), 1)
    sel = jnp.where(r == 2 * c, 1.0, jnp.where(r == 2 * c + 1, 16.0, 0.0))
    return sel.astype(jnp.bfloat16)


def _nibble_unpack_matrix(half: int, odd: bool) -> jnp.ndarray:
    """(half, 2 * half): byte j -> lane 2j, or lane 2j + 1 when ``odd``."""
    r = jax.lax.broadcasted_iota(jnp.int32, (half, 2 * half), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (half, 2 * half), 1)
    return jnp.where(c == 2 * r + int(odd), 1.0, 0.0).astype(jnp.bfloat16)


def _pack_lanes(q: jnp.ndarray, bits: int, out_dtype) -> jnp.ndarray:
    """Fused tail of the encode body: (rows, 128) integer-valued f32 codes
    -> two codes per byte (bits <= 4, lane pairs (2j, 2j + 1) -> byte j,
    low nibble first) or one code per element, cast through int32."""
    if bits <= 4:
        q = jnp.dot(q.astype(jnp.bfloat16), _nibble_pack_matrix(LANES),
                    preferred_element_type=jnp.float32)
    return q.astype(jnp.int32).astype(out_dtype)


def _unpack_nibbles(q: jnp.ndarray) -> jnp.ndarray:
    """(rows, 64) int32 packed bytes -> (rows, 128) f32 codes in lane
    order [lo0, hi0, lo1, hi1, ...] (the inverse of ``_pack_lanes``)."""
    lo = (q & 0x0F).astype(jnp.float32).astype(jnp.bfloat16)
    hi = (q >> 4).astype(jnp.float32).astype(jnp.bfloat16)
    half = q.shape[-1]
    return (jnp.dot(lo, _nibble_unpack_matrix(half, False),
                    preferred_element_type=jnp.float32)
            + jnp.dot(hi, _nibble_unpack_matrix(half, True),
                      preferred_element_type=jnp.float32))


def _encode_tile(blk: jnp.ndarray, mn, mx, bits: int, out_dtype):
    """Affine map + round + clip (+ pack) of one f32 tile against its
    sample's (min, max) — the math of ``core.quantization.quantize``."""
    levels = float((1 << bits) - 1)
    scale = jnp.where(mx > mn, levels / (mx - mn), 0.0)
    q = jnp.clip(jnp.round((blk - mn) * scale), 0.0, levels)
    return _pack_lanes(q, bits, out_dtype)


# ---------------------------------------------------------------------------
# Fused single-launch edge encode (batched): min/max reduction feeding
# quantize (+ pack4) in one pallas_call
# ---------------------------------------------------------------------------


# Whole-batch tile budget, in (sublane) rows: below this the entire
# (B, M, 128) stack is one VMEM tile — f32 4096 x 128 = 2 MiB — and the
# kernel runs as a single grid step.
WHOLE_TILE_ROWS = 4096


def _fused_encode_whole_kernel(x_ref, out_ref, mn_ref, mx_ref, *, bits: int):
    """Whole-batch variant: the (B, M, 128) stack is one VMEM tile; each
    sample is reduced and then quantized from VMEM, its (min, max)
    written to the SMEM range outputs."""

    def sample(b, carry):
        blk = x_ref[b].astype(jnp.float32)
        mn = jnp.min(blk)
        mx = jnp.max(blk)
        mn_ref[b] = mn
        mx_ref[b] = mx
        out_ref[b] = _encode_tile(blk, mn, mx, bits, out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], sample, 0)


def _fused_encode_kernel(x_ref, out_ref, mn_ref, mx_ref, acc_ref,
                         *, bits: int):
    """Blocked variant (large stacks): two-phase grid — p=0 reduces
    min/max into the SMEM accumulator, p=1 quantizes (+ packs) against
    the finished per-sample scalars and publishes them."""
    p = pl.program_id(0)
    b = pl.program_id(1)
    i = pl.program_id(2)
    blk = x_ref[0].astype(jnp.float32)

    @pl.when(p == 0)
    def _reduce():
        bmin = jnp.min(blk)
        bmax = jnp.max(blk)

        @pl.when(i == 0)
        def _init():
            acc_ref[0, b] = bmin
            acc_ref[1, b] = bmax

        @pl.when(i > 0)
        def _fold():
            acc_ref[0, b] = jnp.minimum(acc_ref[0, b], bmin)
            acc_ref[1, b] = jnp.maximum(acc_ref[1, b], bmax)

    @pl.when(p == 1)
    def _quantize():
        mn = acc_ref[0, b]
        mx = acc_ref[1, b]
        mn_ref[b] = mn
        mx_ref[b] = mx
        out_ref[0] = _encode_tile(blk, mn, mx, bits, out_ref.dtype)


def fused_encode_blocks(x3d: jnp.ndarray, bits: int, block_m: int,
                        *, interpret: bool
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One launch: (B, M, 128) tiles -> (codes (B, M, W), mn (B,), mx (B,)).

    W is 64 (two int4 codes per byte) when bits <= 4, else 128. The
    per-sample (min, max) are carried between the reduction and the
    quantize on-chip — codes never touch HBM between the affine map and
    the pack — and leave through SMEM outputs.

    Stacks up to ``WHOLE_TILE_ROWS`` total rows run as one (B, M, 128)
    tile in a single grid step. Larger stacks tile (block_m, 128) per
    sample over a ``(2, B, M // block_m)`` grid whose leading axis is the
    phase (reduce, then quantize); their codes output pins block
    (0, 0, 0) during phase 0 and is rewritten by phase 1's first step,
    so the extra flush is free.
    """
    bsz, m, n = x3d.shape
    pack = bits <= 4
    out_n = n // 2 if pack else n
    out_shape = [
        jax.ShapeDtypeStruct((bsz, m, out_n), code_dtype(bits)),
        jax.ShapeDtypeStruct((bsz,), jnp.float32),
        jax.ShapeDtypeStruct((bsz,), jnp.float32),
    ]
    _launched()
    if bsz * m <= WHOLE_TILE_ROWS:
        return pl.pallas_call(
            functools.partial(_fused_encode_whole_kernel, bits=bits),
            out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM), _SMEM, _SMEM],
            out_shape=out_shape,
            interpret=interpret,
        )(x3d)
    grid = (2, bsz, m // block_m)
    return pl.pallas_call(
        functools.partial(_fused_encode_kernel, bits=bits),
        grid=grid,
        in_specs=[pl.BlockSpec((1, block_m, n), lambda p, b, i: (b, i, 0))],
        out_specs=[
            pl.BlockSpec((1, block_m, out_n),
                         lambda p, b, i: (p * b, p * i, 0)),
            _SMEM,
            _SMEM,
        ],
        out_shape=out_shape,
        scratch_shapes=[pltpu.SMEM((2, bsz), jnp.float32)],
        interpret=interpret,
    )(x3d)


# ---------------------------------------------------------------------------
# Fused cloud-side decode (batched): (unpack) + dequantize + cast
# ---------------------------------------------------------------------------


def _fused_decode_kernel(mn_ref, step_ref, q_ref, out_ref, *, packed: bool):
    b = pl.program_id(0)
    q = q_ref[0].astype(jnp.int32)
    codes = _unpack_nibbles(q) if packed else q.astype(jnp.float32)
    out_ref[0] = (codes * step_ref[b] + mn_ref[b]).astype(out_ref.dtype)


def fused_decode_blocks(q3d: jnp.ndarray, mn, mx, bits: int, block_m: int,
                        out_dtype, *, packed: bool, interpret: bool
                        ) -> jnp.ndarray:
    """One pallas_call for the whole cloud-side boundary codec, batched.

    ``q3d`` is (B, M, W): one uint8/uint16 code per element, or two int4
    codes per byte (pack layout) when ``packed``. ``mn``/``mx`` are (B,)
    per-sample scalars; ``mn`` and ``step`` enter as whole (B,) SMEM
    operands that each grid step indexes by its sample id.
    """
    bsz, m, n = q3d.shape
    levels = float((1 << bits) - 1)
    mn = jnp.reshape(mn.astype(jnp.float32), (bsz,))
    mx = jnp.reshape(mx.astype(jnp.float32), (bsz,))
    step = jnp.where(levels > 0, (mx - mn) / levels, 0.0).astype(jnp.float32)
    out_n = n * 2 if packed else n
    grid = (bsz, m // block_m)
    _launched()
    return pl.pallas_call(
        functools.partial(_fused_decode_kernel, packed=packed),
        grid=grid,
        in_specs=[
            _SMEM,
            _SMEM,
            pl.BlockSpec((1, block_m, n), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, out_n), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, m, out_n), jnp.dtype(out_dtype)),
        interpret=interpret,
    )(mn, step, q3d)


# ---------------------------------------------------------------------------
# Per-channel codec on the same fused bodies: vector (min, scale) operands
# + in-kernel c-bit packing to uint32 words
# ---------------------------------------------------------------------------


def pc_tiling(c: int, length: int, bits: int):
    """Static tile plan for the per-channel kernels: channels pad to a
    16-row multiple (the bf16 tile, so a bf16 decode output tiles too) and
    block ``cb`` at a time; the length axis packs in ``chunk``-element
    blocks (a multiple of ``per_word * LANES`` so a block of codes packs
    to whole 128-lane word rows). ``cb`` is sized to keep one f32 tile
    under ``PC_TILE_BYTES``. Returns (per_word, chunk, l_pad, c_pad, cb)."""
    per_word = 32 // bits
    base = per_word * LANES
    chunk = base * min(PC_CHUNK, max((length + base - 1) // base, 1))
    l_pad = max((length + chunk - 1) // chunk, 1) * chunk
    c_pad = max((c + 15) // 16 * 16, 16)
    cb = min(c_pad, max(16, PC_TILE_BYTES // (chunk * 4) // 16 * 16))
    c_pad = (c_pad + cb - 1) // cb * cb
    return per_word, chunk, l_pad, c_pad, cb


def _pc_encode_kernel(mn_ref, scale_ref, x_ref, out_ref,
                      *, bits: int, per_word: int, n_per_ch: int):
    """One (cb, wchunk) block of words: ``x_ref`` holds the block's codes
    word-slot-major, (1, per_word, cb, wchunk), so slot k of every word
    is one plain (cb, wchunk) tile."""
    i = pl.program_id(2)
    mn = mn_ref[0]                           # (cb, 1) per-channel columns
    scale = scale_ref[0]
    levels = float((1 << bits) - 1)
    cb, wchunk = out_ref.shape[1:]
    word = i * wchunk + jax.lax.broadcasted_iota(jnp.int32, (cb, wchunk), 1)
    w = None
    for k in range(per_word):
        blk = x_ref[0, k].astype(jnp.float32)
        q = jnp.clip(jnp.round((blk - mn) * scale), 0.0, levels)
        # Zero the codes past the channel's true length so the final
        # partial word matches a zero-padded reference pack bit-for-bit.
        q = jnp.where(word * per_word + k < n_per_ch, q, 0.0)
        part = q.astype(jnp.int32).astype(jnp.uint32) << (k * bits)
        w = part if w is None else w | part
    out_ref[0] = w


def pc_encode_blocks(xc: jnp.ndarray, mn2d: jnp.ndarray, mx2d: jnp.ndarray,
                     bits: int, *, interpret: bool) -> jnp.ndarray:
    """Fused per-channel quantize + c-bit pack, one launch.

    ``xc`` is (B, C, L) channel-major features; ``mn2d``/``mx2d`` are the
    (B, C) per-channel range vectors, fed to the kernel as (cb, 1) column
    blocks. Returns (B, C, l_pad // per_word) uint32 words — ``32 //
    bits`` codes per word, codes never straddling a word, channels never
    sharing a word.
    """
    bsz, c, length = xc.shape
    per_word, chunk, l_pad, c_pad, cb = pc_tiling(c, length, bits)
    w_pad = l_pad // per_word
    xc = jnp.pad(xc, ((0, 0), (0, c_pad - c), (0, l_pad - length)))
    # Word-slot-major: code k of word j of channel ch -> [b, k, ch, j].
    xs = xc.reshape(bsz, c_pad, w_pad, per_word).transpose(0, 3, 1, 2)
    levels = float((1 << bits) - 1)
    mn2d = mn2d.astype(jnp.float32)
    scale = jnp.where(mx2d > mn2d, levels / (mx2d - mn2d), 0.0)
    scale = jnp.pad(scale.astype(jnp.float32), ((0, 0), (0, c_pad - c)))
    mn2d = jnp.pad(mn2d, ((0, 0), (0, c_pad - c)))
    wchunk = chunk // per_word
    grid = (bsz, c_pad // cb, w_pad // wchunk)
    kernel = functools.partial(
        _pc_encode_kernel, bits=bits, per_word=per_word, n_per_ch=length,
    )
    col = pl.BlockSpec((1, cb, 1), lambda b, c_, i: (b, c_, 0))
    _launched()
    words = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            col,
            col,
            pl.BlockSpec((1, per_word, cb, wchunk),
                         lambda b, c_, i: (b, 0, c_, i)),
        ],
        out_specs=pl.BlockSpec((1, cb, wchunk), lambda b, c_, i: (b, c_, i)),
        out_shape=jax.ShapeDtypeStruct((bsz, c_pad, w_pad), jnp.uint32),
        interpret=interpret,
    )(mn2d[..., None], scale[..., None], xs)
    return words[:, :c]


def _pc_decode_kernel(mn_ref, step_ref, w_ref, out_ref,
                      *, bits: int, per_word: int):
    mn = mn_ref[0]                           # (cb, 1)
    step = step_ref[0]
    w = w_ref[0]                             # (cb, wchunk) uint32
    mask = jnp.uint32((1 << bits) - 1)
    for k in range(per_word):
        codes = ((w >> (k * bits)) & mask).astype(jnp.int32)
        out_ref[0, k] = (codes.astype(jnp.float32) * step
                         + mn).astype(out_ref.dtype)


def pc_decode_blocks(w3d: jnp.ndarray, mn2d: jnp.ndarray, mx2d: jnp.ndarray,
                     bits: int, length: int, out_dtype, *, interpret: bool
                     ) -> jnp.ndarray:
    """Fused per-channel unpack + dequant + cast, one launch.

    Inverse of :func:`pc_encode_blocks`: (B, C, W) uint32 wire words ->
    (B, C, l_pad) dequantized activations in ``out_dtype`` (trailing axis
    padded to the tile plan; callers trim to ``length``). The kernel
    emits word-slot-major (B, per_word, C, W) tiles; the interleave back
    to element order is an XLA transpose.
    """
    bsz, c, w_true = w3d.shape
    per_word, chunk, l_pad, c_pad, cb = pc_tiling(c, length, bits)
    wchunk = chunk // per_word
    w_pad = l_pad // per_word
    w3d = jnp.pad(w3d, ((0, 0), (0, c_pad - c), (0, w_pad - w_true)))
    levels = float((1 << bits) - 1)
    mn2d = mn2d.astype(jnp.float32)
    mx2d = mx2d.astype(jnp.float32)
    step = jnp.where(levels > 0, (mx2d - mn2d) / levels, 0.0)
    step = jnp.pad(step.astype(jnp.float32), ((0, 0), (0, c_pad - c)))
    mn2d = jnp.pad(mn2d, ((0, 0), (0, c_pad - c)))
    grid = (bsz, c_pad // cb, w_pad // wchunk)
    kernel = functools.partial(_pc_decode_kernel, bits=bits,
                               per_word=per_word)
    col = pl.BlockSpec((1, cb, 1), lambda b, c_, i: (b, c_, 0))
    _launched()
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            col,
            col,
            pl.BlockSpec((1, cb, wchunk), lambda b, c_, i: (b, c_, i)),
        ],
        out_specs=pl.BlockSpec((1, per_word, cb, wchunk),
                               lambda b, c_, i: (b, 0, c_, i)),
        out_shape=jax.ShapeDtypeStruct(
            (bsz, per_word, c_pad, w_pad), jnp.dtype(out_dtype)
        ),
        interpret=interpret,
    )(mn2d[..., None], step[..., None], w3d)
    out = out.transpose(0, 2, 3, 1).reshape(bsz, c_pad, l_pad)
    return out[:, :c]


# ---------------------------------------------------------------------------
# PR 2 three-launch reference path (kept: byte-identity pins + benchmark
# baseline for the fused kernel)
# ---------------------------------------------------------------------------


def _minmax_kernel(x_ref, mn_ref, mx_ref):
    i = pl.program_id(0)
    blk = x_ref[...].astype(jnp.float32)
    mn_ref[i] = jnp.min(blk)
    mx_ref[i] = jnp.max(blk)


def minmax_blocks(x2d: jnp.ndarray, block_m: int, *, interpret: bool
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    m, n = x2d.shape
    grid = (m // block_m,)
    _launched()
    mn, mx = pl.pallas_call(
        _minmax_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, n), lambda i: (i, 0))],
        out_specs=[_SMEM, _SMEM],
        out_shape=[
            jax.ShapeDtypeStruct((grid[0],), jnp.float32),
            jax.ShapeDtypeStruct((grid[0],), jnp.float32),
        ],
        interpret=interpret,
    )(x2d)
    return jnp.min(mn), jnp.max(mx)


def _quantize_kernel(mn_ref, scale_ref, x_ref, out_ref):
    mn = mn_ref[0]
    scale = scale_ref[0]
    levels = scale_ref[1]           # (2^c - 1), passed alongside the scale
    blk = x_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round((blk - mn) * scale), 0.0, levels)
    out_ref[...] = q.astype(jnp.int32).astype(out_ref.dtype)


def quantize_blocks(x2d, mn, mx, bits, block_m, *, interpret):
    m, n = x2d.shape
    levels = float((1 << bits) - 1)
    scale = jnp.where(mx > mn, levels / (mx - mn), 0.0).astype(jnp.float32)
    mn_arr = jnp.reshape(mn.astype(jnp.float32), (1,))
    sc_arr = jnp.stack([scale, jnp.float32(levels)])
    grid = (m // block_m,)
    _launched()
    return pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        in_specs=[
            _SMEM,
            _SMEM,
            pl.BlockSpec((block_m, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), code_dtype(bits)),
        interpret=interpret,
    )(mn_arr, sc_arr, x2d)


def _pack4_kernel(q_ref, out_ref):
    q = q_ref[...].astype(jnp.int32).astype(jnp.float32)
    out_ref[...] = _pack_lanes(q, 4, out_ref.dtype)


def pack4_blocks(q2d: jnp.ndarray, block_m: int, *, interpret: bool
                 ) -> jnp.ndarray:
    m, n = q2d.shape
    grid = (m // block_m,)
    _launched()
    return pl.pallas_call(
        _pack4_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_m, n // 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n // 2), jnp.uint8),
        interpret=interpret,
    )(q2d)
