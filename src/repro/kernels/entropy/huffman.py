"""Device half of the batched Huffman encode, as one jitted XLA program.

The entropy codec's phase 2 (see ``ops.py`` for the two-phase layout):
given the per-sample canonical ``(code, length)`` LUTs that phase 1's
histogram produced on the host, ONE device dispatch turns the raw float
boundary stack into the packed Huffman bitstream words:

  quantize -> LUT gather -> symbol folding -> per-sample prefix sum of
  bit lengths -> shifted two-part emission -> sorted-segment sum -> u32

The quantized codes exist only inside the program — what leaves the
device is exactly the wire words.

It is a plain XLA program, not a Pallas kernel: the body is a
per-element table gather, a prefix sum and a binary search over the
whole stream, which Mosaic does not lower (it accepts 2-D gathers only)
and which XLA compiles for the TPU as they are.

Layout invariants the host framing relies on:

* Bit ``k`` of sample ``b``'s stream lives in word ``b * w_words +
  (k >> 5)`` at bit position ``31 - (k & 31)`` — i.e. serializing each
  sample's word row big-endian reproduces the MSB-first ``np.packbits``
  layout of ``ent.huffman_encode`` byte-for-byte.
* Emission is a segment-*sum*, which equals a segment-*or* because the
  prefix sum gives every symbol a disjoint bit range (no carries can
  occur). The batch is one stream whose sample ``b`` starts at bit
  ``32 * w_words * b``, so the word index per part is non-decreasing
  and the reduction is a sorted-segment cumsum diff, never a scatter.
  The u32 cumsums wrap mod 2^32 but the boundary diff recovers each
  segment exactly. A spilling symbol always ends exactly one word after
  its start word (its code is <= 32 bits), so part1's per-word segments
  shift right by one word instead of needing their own boundary search;
  no spill can cross into the next sample's word row (it would
  contradict ``total_bits <= 32 * w_words``).
* Words past a sample's ``total_bits`` stay zero, so truncating the
  big-endian bytes to ``ceil(total_bits / 8)`` matches ``np.packbits``
  padding.

Codes are capped at 32 bits (``ops.PACK_MAX_CODE_BITS``) so a symbol
spans at most two u32 words and all shift arithmetic stays in u32;
deeper trees route to the host reference path before launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(
    jax.jit, static_argnames=("w_words", "bits", "fold", "split_lut"))
def huffman_pack(xb2: jnp.ndarray, mn, scale, code_lut, len_lut, *,
                 w_words: int, bits: int, fold: int = 1,
                 split_lut: bool = False) -> jnp.ndarray:
    """One dispatch: a flat (B, n) float stack + (B, S_pad) canonical
    LUTs -> (B, w_words) packed bitstream words.

    ``mn``/``scale`` are phase 1's per-sample affine scalars; the codes
    are recomputed from them with the very expression of
    ``core.quantization.quantize``, so they are bitwise the codes the
    histogram counted. With ``split_lut`` the code and length tables are
    separate; otherwise every ``code_lut`` entry is ``(length << 26) |
    code`` (one gather per element). ``fold`` adjacent symbols are
    concatenated into one super-symbol before the prefix sum (the host
    checked ``fold * max_len <= 32``).
    """
    bsz, n = xb2.shape
    levels = float((1 << bits) - 1)
    xf = xb2.astype(jnp.float32)
    q = jnp.clip(jnp.round((xf - mn[:, None]) * scale[:, None]), 0, levels)
    s_pad = code_lut.shape[-1]
    idx = q.astype(jnp.int32) + (jnp.arange(bsz, dtype=jnp.int32)
                                 * s_pad)[:, None]
    if split_lut:
        c = code_lut.reshape(-1)[idx]
        length = len_lut.reshape(-1)[idx].astype(jnp.int32)
    else:
        e = code_lut.reshape(-1)[idx]
        c = e & jnp.uint32((1 << 26) - 1)
        length = (e >> 26).astype(jnp.int32)

    # Concatenating Huffman codes is associative: adjacent symbols fold
    # into one super-symbol, so the prefix sums run over n / fold
    # entries. Canonical codes satisfy code < 2^length, so the OR never
    # overlaps bits. The zero-length padding symbols are inert. Strided
    # slices, not a (..., fold) reshape: a minor axis of ``fold``
    # elements pads to 128 lanes in the TPU layout.
    if fold > 1:
        n_pad = -(-n // fold) * fold
        if n_pad > n:
            c = jnp.pad(c, ((0, 0), (0, n_pad - n)))
            length = jnp.pad(length, ((0, 0), (0, n_pad - n)))
        cf, lf = c, length
        c, length = cf[:, 0::fold], lf[:, 0::fold]
        for j in range(1, fold):
            lj = lf[:, j::fold]
            c = (c << lj.astype(jnp.uint32)) | cf[:, j::fold]
            length = length + lj

    # Exclusive per-sample prefix sum of bit lengths, based at each
    # sample's word row.
    base = 32 * w_words * jnp.arange(bsz, dtype=jnp.int32)[:, None]
    starts = (jnp.cumsum(length, axis=1) - length + base).reshape(-1)
    length = length.reshape(-1)
    c = c.reshape(-1)

    # Two-part shifted emission (all in u32): a code starting at bit
    # offset ``o`` in word ``w0`` contributes its top ``32 - o`` bits
    # there and spills the rest into ``w0 + 1``. Shift amounts are
    # clamped into [0, 31] because jnp.where evaluates both branches;
    # spill parts are selected away so clamping never corrupts bits.
    o = starts & 31
    w_tot = bsz * w_words
    w0 = jnp.minimum(starts >> 5, w_tot - 1)     # padding at stream end
    spill = (o + length) > 32
    sh0 = jnp.clip(32 - o - length, 0, 31).astype(jnp.uint32)
    k1 = jnp.clip(o + length - 32, 0, 31).astype(jnp.uint32)
    sh1 = jnp.clip(64 - o - length, 0, 31).astype(jnp.uint32)
    part0 = jnp.where(spill, c >> k1, c << sh0)
    part1 = jnp.where(spill, c << sh1, jnp.uint32(0))

    # w0 is non-decreasing, so each word is a sorted-segment sum of its
    # part0s: a cumsum diff at binary-searched segment ends (``bound[w]``
    # symbols start at or before word w). The unrolled binary search
    # measures 2.5x faster than the default while-loop form on XLA CPU.
    bound = jnp.searchsorted(w0, jnp.arange(w_tot, dtype=jnp.int32),
                             side="right", method="scan_unrolled")
    zero1 = jnp.zeros((1,), jnp.uint32)
    seg = jnp.concatenate([zero1, jnp.cumsum(part0)])[bound]
    words = seg - jnp.concatenate([zero1, seg[:-1]])
    # Only the last symbol that starts in word w can spill, and only into
    # word w + 1, so the spills need a gather per word, not a second
    # stream-length cumsum.
    prev = jnp.concatenate([jnp.zeros((1,), bound.dtype), bound[:-1]])
    spilled = jnp.where(bound > prev, part1[jnp.maximum(bound - 1, 0)],
                        jnp.uint32(0))
    words = words + jnp.concatenate([zero1, spilled[:-1]])
    return words.reshape(bsz, w_words)
