"""The host oracle of the bitpack wire against the program's codec (its
kernels interpreted on the CPU), and a fault it must catch."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench.run  # noqa: F401  (puts the program's sources on the path)
from bench import oracle


@pytest.mark.parametrize("shape,dtype,bits", [
    ((1, 1, 256), jnp.bfloat16, 8),      # one token's boundary row
    ((1, 37, 256), jnp.bfloat16, 8),     # a prefill boundary
    ((1, 16, 8, 8), jnp.float32, 8),     # a CNN feature map
    ((1, 16, 8, 8), jnp.float32, 4),
])
def test_oracle_agrees_with_the_codec(shape, dtype, bits):
    from repro.codec import get_codec

    codec = get_codec("bitpack")
    x = (jax.random.normal(jax.random.key(1), shape) * 3).astype(dtype)
    blob = codec.encode(x, bits)
    dec = codec.decode(blob, out_dtype=dtype)
    assert oracle.check_blob(blob, x, bits, dec) == (0, 0)
    blobs = codec.encode_batch([x, x * 2], bits)
    decs = codec.decode_batch(blobs, out_dtype=dtype)
    for xi, b, d in zip([x, x * 2], blobs, decs):
        assert oracle.check_blob(b, xi, bits, d) == (0, 0)


def test_oracle_catches_a_changed_byte_and_a_wrong_decode():
    from repro.codec import get_codec

    codec = get_codec("bitpack")
    x = jax.random.normal(jax.random.key(2), (1, 1, 256), jnp.float32)
    blob = codec.encode(x, 8)
    raw = bytearray(blob.payload)
    raw[5] ^= 1
    bad = dataclasses.replace(blob, payload=bytes(raw))
    assert oracle.check_blob(bad, x, 8)[0] == 1
    step = (float(blob.x_max) - float(blob.x_min)) / 255
    dec = codec.decode(blob, out_dtype=jnp.float32) + step
    assert oracle.check_blob(blob, x, 8, dec)[1] == x.size


def test_codes_round_half_to_even():
    q, mn, mx = oracle.codes(np.array([0.0, 0.5, 1.5, 255.0]), 8)
    assert mn == 0 and mx == 255
    assert q.tolist() == [0, 0, 2, 255]
