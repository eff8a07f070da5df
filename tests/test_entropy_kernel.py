"""Byte-identity and routing pins for the device-resident batched
Huffman encode (``repro.kernels.entropy``).

The contract: the two-phase device path (histogram dispatch + fused
quantize/LUT-gather/scan/pack program) must reproduce the host reference
``ent.huffman_encode`` byte-for-byte per sample, at every bit width the
codec serves, in at most 2 device dispatches per batch — and must route
to the host path (not emit a wrong stream) for trees it cannot pack.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

from repro.codec import get_codec
from repro.core import entropy as ent
from repro.core import quantization as q
from repro.kernels.entropy import huffman_encode_batch_device
from repro.kernels.entropy import ops as eops
from repro.kernels.quantize import (
    count_host_routes, count_launches, dequantize_codes_batch,
)

BITS_SWEEP = (3, 5, 6, 8, 12, 16)      # uint16 codes included


def _features(shape, seed=0):
    x = np.random.default_rng(seed).standard_normal(shape)
    x[np.abs(x) < 0.8] = 0.0           # post-ReLU-like sparsity
    return x.astype(np.float32)


def _reference(x, bits):
    qz = q.quantize(jnp.asarray(x), bits)
    return (ent.huffman_encode(np.asarray(qz.values), 1 << bits),
            np.float32(qz.x_min), np.float32(qz.x_max))


@pytest.mark.parametrize("bits", BITS_SWEEP)
def test_device_batch_byte_identical_to_host(bits):
    xb = np.stack([_features((2, 7, 11), seed=s) for s in range(3)])
    out = huffman_encode_batch_device(jnp.asarray(xb), bits)
    assert out is not None
    payloads, mn, mx = out
    for b in range(xb.shape[0]):
        ref, rmn, rmx = _reference(xb[b], bits)
        assert payloads[b] == ref
        assert np.float32(mn[b]) == rmn
        assert np.float32(mx[b]) == rmx


def test_multi_block_carry_byte_identical():
    """Long streams (many thousand u32 words per sample, past the
    1024-word step of the output width) keep the per-sample bit offsets
    and the sample rebasing exact."""
    xb = np.stack([
        np.random.default_rng(s).standard_normal(300_000).astype(np.float32)
        for s in range(2)
    ])
    payloads, _, _ = huffman_encode_batch_device(jnp.asarray(xb), 8)
    for b in range(2):
        assert payloads[b] == _reference(xb[b], 8)[0]


def test_codec_encode_uses_device_path_byte_identical():
    codec = get_codec("huffman")
    x = _features((3, 5, 17), seed=7)
    for bits in (4, 8, 12):
        blob = codec.encode(jnp.asarray(x), bits)
        ref, rmn, rmx = _reference(x, bits)
        assert blob.payload == ref
        assert np.float32(blob.x_min) == rmn
        assert np.float32(blob.x_max) == rmx


def test_single_symbol_degenerate_tree():
    """A constant tensor quantizes to one symbol — the one-node tree
    still emits 1 bit per element, identically on both paths."""
    xb = np.full((2, 37), 3.25, np.float32)
    payloads, _, _ = huffman_encode_batch_device(jnp.asarray(xb), 4)
    ref = _reference(xb[0], 4)[0]
    assert payloads[0] == ref and payloads[1] == ref
    assert (ent.huffman_decode(payloads[0]) == 0).all()


def test_empty_and_ragged_inputs_fall_back_cleanly():
    codec = get_codec("huffman")
    empty = jnp.zeros((0, 4), jnp.float32)
    blob = codec.encode(empty, 8)
    assert blob.payload == b"" and blob.num_elements == 0
    assert huffman_encode_batch_device(empty[None], 8) is None
    # Ragged stack: encode_batch must loop, each blob byte-identical to
    # encoding that tensor alone.
    xs = [jnp.asarray(_features(s, seed=i))
          for i, s in enumerate([(2, 9), (3, 5), (0, 4)])]
    blobs = codec.encode_batch(xs, 6)
    for x, blob in zip(xs, blobs):
        assert blob.payload == codec.encode(x, 6).payload


def _fibonacci_values(n: int = 24) -> np.ndarray:
    """Symbol i repeated fib(i) times, shuffled: the skew that drives
    Huffman code lengths deepest for a given element count."""
    fib = [1, 1]
    while len(fib) < n:
        fib.append(fib[-1] + fib[-2])
    vals = np.repeat(np.arange(len(fib)), fib).astype(np.float32)
    np.random.default_rng(3).shuffle(vals)
    return vals


def test_deep_tree_skew_byte_identical():
    """Fibonacci frequencies force >13-bit codes (past the decoder's LUT
    window) — the pack's two-part emission must still match the host
    bitstream exactly."""
    vals = _fibonacci_values()
    xb = np.stack([vals, vals[::-1].copy()])
    codes = np.asarray(q.quantize(jnp.asarray(xb[0]), 8).values)
    lens = ent._code_lengths(np.bincount(codes, minlength=256))
    assert int(lens.max()) > 13          # the regime this test pins
    payloads, _, _ = huffman_encode_batch_device(jnp.asarray(xb), 8)
    for b in range(2):
        assert payloads[b] == _reference(xb[b], 8)[0]


def test_overlong_codes_route_to_host_path(monkeypatch):
    """Any code length > PACK_MAX_CODE_BITS must reject the device path
    (returning None), and the codec must then produce the reference
    bytes via the host encoder. Realistic data cannot reach 33-bit codes
    (it needs Fibonacci skew over >5M elements), so the cap is lowered
    to pin the routing."""
    monkeypatch.setattr(eops, "PACK_MAX_CODE_BITS", 10)
    vals = _fibonacci_values()
    assert huffman_encode_batch_device(jnp.asarray(vals)[None], 8) is None
    blob = get_codec("huffman").encode(jnp.asarray(vals), 8)
    assert blob.payload == _reference(vals, 8)[0]


def test_host_routes_are_counted(monkeypatch):
    """Every encode that leaves the device path for the host encoder is
    counted: a deep-tree batch of three through the codec's batch call
    is one rejected batch encode plus one per tensor, while device-path
    and empty encodes count nothing."""
    vals = jnp.asarray(_fibonacci_values())
    codec = get_codec("huffman")
    with count_host_routes() as c:
        codec.encode_batch([vals, vals], 8)
        huffman_encode_batch_device(jnp.zeros((0, 4)), 8)
    assert c.count == 0
    monkeypatch.setattr(eops, "PACK_MAX_CODE_BITS", 10)
    with count_host_routes() as c:
        blobs = codec.encode_batch([vals, vals, vals], 8)
    assert c.count == 4
    assert all(b.payload == _reference(np.asarray(vals), 8)[0]
               for b in blobs)


def test_launch_accounting_two_dispatches_per_batch():
    """The whole batched encode is histogram + pack: <= 2 device
    dispatches regardless of batch size, and the codec-level batch call
    adds none."""
    xb = jnp.asarray(np.stack([_features((4, 13), seed=s)
                               for s in range(5)]))
    with count_launches() as c:
        huffman_encode_batch_device(xb, 8)
    assert c.count == 2
    codec = get_codec("huffman")
    rows = [xb[i] for i in range(xb.shape[0])]
    with count_launches() as c:
        codec.encode_batch(rows, 8)
    assert c.count == 2


def test_decode_batch_matches_per_blob():
    codec = get_codec("huffman")
    xs = [jnp.asarray(_features((2, 6, 10), seed=s)) for s in range(4)]
    for bits in (4, 12):
        blobs = codec.encode_batch(xs, bits)
        batched = codec.decode_batch(blobs)
        for blob, out in zip(blobs, batched):
            np.testing.assert_array_equal(np.asarray(codec.decode(blob)),
                                          np.asarray(out))


def test_dequantize_codes_batch_matches_single():
    from repro.kernels.quantize import dequantize_codes

    rng = np.random.default_rng(9)
    for bits in (3, 8, 12):
        codes = rng.integers(0, 1 << bits, size=(3, 40))
        mn = rng.standard_normal(3).astype(np.float32)
        mx = mn + np.abs(rng.standard_normal(3)).astype(np.float32)
        out = dequantize_codes_batch(jnp.asarray(codes), mn, mx, bits,
                                     (5, 8))
        for b in range(3):
            one = dequantize_codes(jnp.asarray(codes[b]), mn[b], mx[b],
                                   bits, (5, 8))
            np.testing.assert_array_equal(np.asarray(out[b]),
                                          np.asarray(one))


def test_transfer_size_single_width_is_exact():
    """The single-width size predictor routes through the device
    histogram (no code-array transfer) and must still be byte-exact
    against the actually encoded blob."""
    codec = get_codec("huffman")
    x = jnp.asarray(_features((3, 9, 14), seed=2))
    for bits in (3, 8, 12):
        blob = codec.encode(x, bits)
        assert codec.transfer_size_bytes(x, bits) == blob.nbytes
