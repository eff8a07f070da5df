"""Two-phase device-resident batched Huffman encode.

The paper's entropy stage (Sec. III-B) used to run entirely on the host:
quantize on device, ship the full code array over PCIe, then build the
tree and the bitstream in numpy, one tensor at a time. This module keeps
the only genuinely serial part — the O(2^bits) canonical-table build —
on the host and moves everything O(n) onto the device, batched:

* **Phase 1 — histogram dispatch.** One jitted launch quantizes the
  whole (B, *shape) stack and reduces it to per-sample symbol counts
  (the ``_calib_histograms`` shape): only ``(B, 2^bits)`` counts plus
  the (B,) affine ranges reach the host, never the codes.
* **Host interlude.** The existing ``ent._code_lengths`` /
  ``ent._canonical_codes`` machinery turns each histogram into the
  canonical table; it is flattened into per-sample ``(code, length)``
  LUT arrays and each sample's exact ``total_bits`` (known before the
  pack launches, so the output width is static).
* **Phase 2 — pack dispatch.** One jitted XLA program
  (``huffman.huffman_pack``) re-quantizes the stack, gathers
  per-symbol (code, length), prefix-sums the bit lengths per sample,
  and emits the shifted codes into packed u32 words. Serializing those words big-endian and trimming to
  ``ceil(total_bits / 8)`` bytes reproduces ``ent.huffman_encode``'s
  bitstream **byte-identically** (pinned in
  ``tests/test_entropy_kernel.py``).

Total: 2 device dispatches per batch — histogram + pack — counted
through the shared ``kernels.quantize`` launch counter so
``count_launches`` sees both.

Routing: pathological deep-tree distributions (any code length >
``PACK_MAX_CODE_BITS``) and streams too long for the i32 bit offsets
return ``None`` from :func:`huffman_encode_batch_device`; the
codec then falls back to the host reference path, whose output is the
identity the device path is pinned against anyway.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import entropy as ent
from repro.kernels.entropy import huffman as hk
from repro.kernels.quantize import quantize as k

LANES = k.LANES

# A symbol may span at most two u32 words in the pack's two-part
# emission, so any code longer than 32 bits routes to the host reference
# path. Reaching 33 bits needs a Fibonacci-like frequency skew over >
# 5M elements — tests pin the routing by lowering this cap instead.
PACK_MAX_CODE_BITS = 32

# The pack computes stream bit offsets in int32.
_MAX_TOTAL_BITS = (1 << 31) - 1


@functools.partial(jax.jit, static_argnames=("bits",))
def _hist_ranges(xb: jnp.ndarray, bits: int):
    """Phase 1: per-sample symbol histogram + affine range of a (B, N)
    stack in one launch. The quantize is re-traced exactly as
    ``core.quantization.quantize`` writes it (min/max are exactly
    associative), so the counted codes are bitwise the ones the pack
    program re-derives and the host reference would emit."""
    xf = xb.astype(jnp.float32)
    mn = jnp.min(xf, axis=1)
    mx = jnp.max(xf, axis=1)
    levels = (1 << bits) - 1
    scale = jnp.where(mx > mn, levels / (mx - mn), 0.0)
    q = jnp.clip(jnp.round((xf - mn[:, None]) * scale[:, None]),
                 0, levels).astype(jnp.int32)
    if bits <= 8:
        hist = _hist_gemm(q, bits)
    else:
        hist = jax.vmap(lambda row: jnp.bincount(row, length=1 << bits))(q)
    return hist, mn, mx, scale


def _hist_chunk(bits: int) -> int:
    # Measured sweet spots on XLA CPU: small alphabets amortize the scan
    # step overhead over longer chunks before the one-hot operands
    # outgrow cache; at bits >= 6 the operands are 4x wider and 1024
    # wins again.
    return 4096 if bits <= 4 else 1024


def _hist_gemm(q: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Exact symbol histogram as a split-nibble one-hot contraction:
    counts of symbol (h, l) are one_hot(hi)^T @ one_hot(lo), a batched
    GEMM. XLA CPU lowers bincount to a serial scatter loop; this stays
    vectorized, and f32 accumulation is exact below 2^24 counts per bin.
    The contraction runs as a ``lax.scan`` over fixed-size chunks so the
    one-hot operands stay cache-resident — one flat einsum materializes
    ``32 * 2^(bits/2)`` bytes per element in HBM and goes memory-bound
    (measured superlinear past ~25k elements per row)."""
    bsz, n = q.shape
    lo_bits = bits // 2
    hi_sz, lo_sz = 1 << (bits - lo_bits), 1 << lo_bits

    def onehots(qk):
        oh_hi = ((qk >> lo_bits)[..., None] == jnp.arange(hi_sz)
                 ).astype(jnp.float32)
        oh_lo = ((qk & (lo_sz - 1))[..., None] == jnp.arange(lo_sz)
                 ).astype(jnp.float32)
        return oh_hi, oh_lo

    chunk = _hist_chunk(bits)
    nc = n // chunk
    hist = jnp.zeros((bsz, hi_sz, lo_sz), jnp.float32)
    if nc:
        qc = (q[:, : nc * chunk]
              .reshape(bsz, nc, chunk).transpose(1, 0, 2))

        def body(acc, qk):
            oh_hi, oh_lo = onehots(qk)
            return acc + jnp.einsum("bnh,bnl->bhl", oh_hi, oh_lo), None

        hist, _ = jax.lax.scan(body, hist, qc)
    if nc * chunk < n:
        oh_hi, oh_lo = onehots(q[:, nc * chunk:])
        hist = hist + jnp.einsum("bnh,bnl->bhl", oh_hi, oh_lo)
    return hist.reshape(bsz, 1 << bits).astype(jnp.int32)


def _sample_table(freqs: np.ndarray, num_symbols: int):
    """Canonical table of one histogram, flattened for the LUT operand.

    Returns ``(code_of u32 (S,), len_of i32 (S,), lengths (S,),
    total_bits)`` or ``None`` when the sample must route to the host
    reference path (a code longer than ``PACK_MAX_CODE_BITS``, or a
    stream overflowing the pack's i32 bit offsets). The code
    assignment is the numeric canonical form (``ent._canonical_ranges``
    — codes of length l start at first_code[l], ranked by symbol), which
    is exactly the sequential shift-and-increment of
    ``ent._canonical_codes`` but vectorized over the alphabet."""
    lengths = ent._code_lengths(freqs.astype(np.int64))
    max_len = int(lengths.max())
    total_bits = int((freqs.astype(np.int64) * lengths).sum())
    if max_len > PACK_MAX_CODE_BITS or total_bits > _MAX_TOTAL_BITS:
        return None
    first_code, offset, _, rank_sym = ent._canonical_ranges(lengths)
    code_of = np.zeros(num_symbols, np.uint32)
    len_of = np.zeros(num_symbols, np.int32)
    ls = lengths[rank_sym]
    code_of[rank_sym] = (first_code[ls]
                         + np.arange(len(rank_sym)) - offset[ls])
    len_of[rank_sym] = ls
    return code_of, len_of, lengths, total_bits


def fold_factor(max_len: int) -> int:
    """Symbol folding factor for the pack: adjacent codes are
    concatenated into super-symbols as long as the longest folded code
    still fits a u32 word, so every per-element prefix sum runs over
    n / fold entries. Known before launch from the host-built tables;
    capped so the static trace count stays tiny."""
    fold = 1
    while fold < 16 and fold * 2 * max_len <= 32:
        fold *= 2
    return fold


def stream_words(max_bits: int) -> int:
    """Per-sample output width in u32 words. It quantizes coarsely
    (powers of two up to 1024 words, then 1024-word steps) so small
    data-dependent drift in total_bits between calls reuses the pack
    executable's jit cache instead of re-tracing, without ballooning the
    segment scan."""
    need = (max_bits + 31) // 32
    w_words = LANES
    while w_words < need:
        w_words = w_words * 2 if w_words < 1024 else w_words + 1024
    return w_words


def _pad_lanes(n: int) -> int:
    return max((n + LANES - 1) // LANES * LANES, LANES)


def huffman_encode_batch_device(
    xb: jnp.ndarray,
    bits: int,
) -> Optional[Tuple[List[bytes], np.ndarray, np.ndarray]]:
    """Batched device Huffman encode of a (B, *shape) float stack.

    Returns ``(payloads, mn, mx)`` — per-sample wire payloads
    byte-identical to ``ent.huffman_encode`` of that sample's quantized
    codes, plus the (B,) affine ranges for the blob headers — in two
    device dispatches total (histogram + pack). Returns ``None`` when
    the stack is empty, or when any sample needs the host reference
    path (see module docstring; those returns are counted in
    ``quantize.HOST_ROUTE_COUNT``); callers fall back per-tensor.
    """
    xb = jnp.asarray(xb)
    bsz = xb.shape[0]
    n_elem = int(np.prod(xb.shape[1:])) if xb.ndim > 1 else 1
    if bsz == 0 or n_elem == 0:
        return None
    num_symbols = 1 << bits

    # Dispatch 1: the jitted histogram+ranges reduction (one executable
    # per (B, N, bits); counted through the shared launch counter so
    # ``count_launches`` reports dispatches, not pallas_calls only).
    k._launched()
    hist, mn_dev, mx, scale = _hist_ranges(xb.reshape(bsz, -1), bits)
    hist = np.asarray(hist)
    mn = np.asarray(mn_dev)
    mx = np.asarray(mx)

    tables = []
    for b in range(bsz):
        t = _sample_table(hist[b], num_symbols)
        if t is None:
            k._host_routed()
            return None
        tables.append(t)

    s_pad = _pad_lanes(num_symbols)
    max_bits = max(t[3] for t in tables)
    max_len = max(int(t[2].max()) for t in tables)
    # One u32 LUT entry per symbol — (length << 26) | code — whenever
    # every code fits 26 bits, halving the per-element gather traffic;
    # codes wider than that (only possible at fold == 1) keep separate
    # code/length tables.
    split_lut = max_len > 26
    code_lut = np.zeros((bsz, s_pad), np.uint32)
    len_lut = np.zeros((bsz, s_pad), np.uint32)
    for b, (code_of, len_of, _, _) in enumerate(tables):
        if split_lut:
            code_lut[b, :num_symbols] = code_of
            len_lut[b, :num_symbols] = len_of.astype(np.uint32)
        else:
            code_lut[b, :num_symbols] = (
                (len_of.astype(np.uint32) << 26) | code_of)
    if not split_lut:
        len_lut = code_lut
    fold = fold_factor(max_len)
    w_words = stream_words(max_bits)
    # The pack runs the whole batch as one concatenated stream with
    # sample b's bits based at 32 * w_words * b, so the last stream
    # position must also fit the i32 offset arithmetic.
    if 32 * w_words * bsz > _MAX_TOTAL_BITS:
        k._host_routed()
        return None

    # Dispatch 2: the fused quantize + LUT gather + scan + pack program
    # (jitted — counted here, where every call really dispatches it).
    k._launched()
    words = np.asarray(hk.huffman_pack(
        xb.reshape(bsz, -1), mn_dev, scale, jnp.asarray(code_lut),
        jnp.asarray(len_lut), w_words=w_words, bits=bits, fold=fold,
        split_lut=split_lut,
    ))

    # Host framing only: header + big-endian word bytes trimmed to the
    # exact payload length (trailing bits are zero on both paths).
    head = (np.uint32(n_elem).tobytes()
            + np.uint16(num_symbols & 0xFFFF).tobytes())
    payloads = []
    for b, (_, _, lengths, total_bits) in enumerate(tables):
        stream = words[b].astype(">u4").tobytes()[: (total_bits + 7) // 8]
        payloads.append(head + lengths.astype(np.uint8).tobytes() + stream)
    return payloads, mn, mx
