"""The served path's boundary-codec kernels compile for a TPU v5e.

Every other test runs the Pallas kernels in interpret mode on the CPU,
which accepts bodies the chip's compiler (Mosaic) refuses. Here each
kernel is lowered and compiled for a *described* v5e chip — libtpu's
compiler, no chip attached — at real boundary widths: a micro-batch of
8 olmo-1b token rows (1, 1, 2048) bf16 and of 8 resnet50 stage-1 feature
maps (256, 56, 56) f32, at every bit width the planner may pick.

A case that is still a Pallas kernel asserts that the compiled program
holds the Mosaic custom call; the Huffman pack is a plain XLA program
and only has to compile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config.types import JaladConfig
from repro.kernels.entropy import huffman as hk
from repro.kernels.entropy import ops as eops
from repro.kernels.quantize import ops
from repro.kernels.quantize import quantize as k

BITS = JaladConfig().bits_choices
SHAPES = {
    "olmo1b_token_rows": ((8, 1, 1, 2048), jnp.bfloat16),
    "resnet50_stage1": ((8, 256, 56, 56), jnp.float32),
}
MOSAIC = "tpu_custom_call"


@pytest.fixture(scope="module")
def v5e_slice():
    """The devices of a described v5e:2x2 slice, with the persistent
    compilation cache off for the module."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it refused
        pytest.skip(f"cannot describe a TPU v5e here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def v5e(v5e_slice):
    """One chip of the described slice."""
    return SingleDeviceSharding(v5e_slice[0])


def _compiled_text(sharding, fn, *specs) -> str:
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]
    return jax.jit(fn).lower(*args).compile().as_text()


def _n_elem(shape) -> int:
    return int(np.prod(shape[1:]))


@pytest.mark.parametrize("bits", [4, 8])
def test_batch_sharded_wire_decode_compiles(v5e_slice, bits):
    """The meshed cloud worker's decode: the batch enters sharded over
    the mesh's "data" axis, and each chip decodes its block — XLA cannot
    partition a Mosaic kernel, so the decode runs in a ``shard_map``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(v5e_slice).reshape(2, 2), ("data", "model"))
    shape, dtype = SHAPES["olmo1b_token_rows"]
    n = _n_elem(shape)
    n_wire = (n + 1) // 2 if bits <= 4 else n
    decode = ops.shard_batch(
        lambda c, a, b: ops.dequantize_wire_batch_impl(
            c, a, b, bits, shape[1:], interpret=False, out_dtype=dtype),
        mesh)
    rows = NamedSharding(mesh, P("data"))
    text = jax.jit(decode).lower(
        jax.ShapeDtypeStruct((shape[0], n_wire), k.code_dtype(bits),
                             sharding=rows),
        jax.ShapeDtypeStruct((shape[0],), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((shape[0],), jnp.float32, sharding=rows),
    ).compile().as_text()
    assert MOSAIC in text


@pytest.mark.parametrize("shape_name", sorted(SHAPES))
@pytest.mark.parametrize("bits", BITS)
def test_fused_encode_compiles(v5e, bits, shape_name):
    """Edge encode: the whole-batch tile (token rows) and the two-phase
    blocked grid (feature maps)."""
    shape, dtype = SHAPES[shape_name]
    text = _compiled_text(
        v5e, lambda x: ops.quantize_pack_batch_impl(x, bits,
                                                    interpret=False),
        (shape, dtype))
    assert MOSAIC in text


@pytest.mark.parametrize("shape_name", sorted(SHAPES))
@pytest.mark.parametrize("bits", BITS)
def test_wire_decode_compiles(v5e, bits, shape_name):
    """Cloud decode of the bitpack wire (nibble unpack at bits <= 4) and
    of the one-code-per-element stacks the Huffman decoder emits."""
    shape, dtype = SHAPES[shape_name]
    n = _n_elem(shape)
    n_wire = (n + 1) // 2 if bits <= 4 else n
    cdt = k.code_dtype(bits)
    ranges = ((shape[0],), jnp.float32)
    wire = _compiled_text(
        v5e, lambda c, a, b: ops.dequantize_wire_batch_impl(
            c, a, b, bits, shape[1:], interpret=False, out_dtype=dtype),
        ((shape[0], n_wire), cdt), ranges, ranges)
    codes = _compiled_text(
        v5e, lambda c, a, b: ops.dequantize_codes_batch_impl(
            c, a, b, bits, shape[1:], interpret=False, out_dtype=dtype),
        ((shape[0], n), cdt), ranges, ranges)
    assert MOSAIC in wire and MOSAIC in codes


@pytest.mark.parametrize("shape_name", sorted(SHAPES))
@pytest.mark.parametrize("bits", BITS)
def test_perchannel_encode_decode_compiles(v5e, bits, shape_name):
    shape, dtype = SHAPES[shape_name]
    axis = 0 if shape[1] > 1 else len(shape) - 2     # codec channel axis
    c = shape[axis + 1]
    words = ops.perchannel_words(_n_elem(shape) // c, bits)
    enc = _compiled_text(
        v5e, lambda x: ops.perchannel_encode_batch_impl(x, bits, axis,
                                                        interpret=False),
        (shape, dtype))
    ranges = ((shape[0], c), jnp.float32)
    dec = _compiled_text(
        v5e, lambda w, a, b: ops.perchannel_decode_batch_impl(
            w, a, b, bits, shape[1:], axis, out_dtype=dtype,
            interpret=False),
        ((shape[0], c, words), jnp.uint32), ranges, ranges)
    assert MOSAIC in enc and MOSAIC in dec


@pytest.mark.parametrize("shape_name", sorted(SHAPES))
@pytest.mark.parametrize("bits", BITS)
def test_huffman_device_encode_compiles(v5e, bits, shape_name):
    """Both dispatches of the device Huffman encode — the histogram and
    the pack — with the folding and output width the host picks for a
    table whose longest code is ``bits + 2`` bits."""
    shape, dtype = SHAPES[shape_name]
    bsz, n = shape[0], _n_elem(shape)
    flat = ((bsz, n), dtype)
    _compiled_text(v5e, lambda x: eops._hist_ranges(x, bits), flat)
    s_pad = eops._pad_lanes(1 << bits)
    lut = ((bsz, s_pad), jnp.uint32)
    vec = ((bsz,), jnp.float32)
    _compiled_text(
        v5e, lambda x, mn, sc, a, b: hk.huffman_pack(
            x, mn, sc, a, b, w_words=eops.stream_words(n * (bits + 2)),
            bits=bits, fold=eops.fold_factor(bits + 2),
            split_lut=bits + 2 > 26),
        flat, vec, vec, lut, lut)


@pytest.mark.parametrize("shape_name", sorted(SHAPES))
@pytest.mark.parametrize("bits", BITS)
def test_threelaunch_chain_compiles(v5e, bits, shape_name):
    """The benchmark baseline (minmax -> quantize -> pack4) on one
    sample of the stack."""
    shape, dtype = SHAPES[shape_name]
    text = _compiled_text(
        v5e, lambda x: ops.quantize_pack_threelaunch_impl(
            x, bits, interpret=False),
        (shape[1:], dtype))
    assert MOSAIC in text


@pytest.mark.parametrize("bits", [4, 16])
def test_default_tiles_fit_vmem(v5e, bits):
    """The largest single tile each encode variant takes by default: a
    whole-batch stack of exactly ``WHOLE_TILE_ROWS`` f32 rows, and the
    blocked grid's ``DEFAULT_BLOCK_M`` rows — Mosaic refuses a kernel
    whose double-buffered tiles exceed its VMEM budget."""
    whole = ((1, k.WHOLE_TILE_ROWS, k.LANES), jnp.float32)
    blocked = ((2, k.WHOLE_TILE_ROWS, k.LANES), jnp.float32)
    for spec in (whole, blocked):
        text = _compiled_text(
            v5e, lambda x: k.fused_encode_blocks(
                x, bits, k.DEFAULT_BLOCK_M, interpret=False), spec)
        assert MOSAIC in text
