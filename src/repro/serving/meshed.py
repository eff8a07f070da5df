"""Meshed cloud worker: the shared cloud tail, sharded over a device mesh.

The serving stack's cloud side was single-device — fine for the paper's
1080Ti testbed, but the large configs (granite-34b and up) cannot even
hold their tail params in one accelerator's HBM. This module turns the
shared cloud worker of :class:`~repro.serving.fleet.FleetServer` into a
MaxText-style SPMD runner:

* **Sharded param tree.** Parameter PartitionSpecs are resolved ONCE per
  (config, mesh) through :func:`repro.sharding.rules.resolve_spec` (the
  priority-ordered, divisibility-checked rule table) and cached by config
  hash — like PR 5's calibration tables. ``params`` are ``device_put``
  into those NamedShardings at worker construction (nothing moves when
  they were created there, ``Model.init(rng, param_shardings(...))``),
  so every tail launch reads weights already distributed over the mesh.

* **Batch-sharded boundary entry.** A `(point, bits, codec)` group's wire
  blobs decode in ONE launch whose output is already sharded over the
  "data" mesh axis (``kernels.quantize.ops.dequantize_wire_batch`` in a
  ``shard_map`` over that axis, since XLA cannot partition a Mosaic
  kernel — no host gather, no replicated intermediate), and the
  decoded boundary is pinned via
  :func:`repro.sharding.activation.constrain` (batch on "data"; the rule
  table leaves seq/embed/spatial dims replicated so the params carry the
  "model" axis).

* **One fused forward.** For the bitpack wire format decode + tail run
  under ONE ``jax.jit`` per (point, bits, boundary shape); huffman
  groups ride the same fused jit after the host entropy decode stacks
  their codes (the ``wire="codes"`` flavor) — no more per-blob
  single-device fallback. Remaining codecs decode through their
  existing batch path and reshard only the stacked boundary. Results
  are float-level equivalent to the single-device tail
  (XLA re-blocks reductions per partitioning — pinned by tolerance in
  ``tests/test_meshed.py``), which is the same contract as
  ``fuse_tail=True``.

Groups whose size does not divide the "data" axis extent are padded by
tiling (and the padding sliced off the logits), so a flash crowd of any
size serves in one launch. Runs on CPU CI under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.api import Model
from repro.sharding.activation import constrain
from repro.sharding.rules import shardings_for_specs

_UNSTACKABLE = object()

# (config hash, mesh) -> NamedSharding param tree. The rule-table resolve
# walks every param leaf; one worker per (config, mesh) pays it once and
# every later worker (tests, benchmarks, re-built fleets) reuses it.
_SHARDING_CACHE: Dict[Tuple[str, Mesh], Any] = {}


def _config_hash(cfg) -> str:
    # Same idiom as PredictorTables.cache_key: the full config repr keys
    # the cache (reduced() variants must never share an entry).
    return hashlib.sha1(repr(cfg).encode()).hexdigest()[:16]


def param_shardings(model: Model, mesh: Mesh):
    """The model's NamedSharding param tree on ``mesh`` — resolved via
    ``rules.resolve_spec`` once per (config, mesh) and cached."""
    key = (_config_hash(model.cfg), mesh)
    got = _SHARDING_CACHE.get(key)
    if got is None:
        got = shardings_for_specs(model.abstract_params(),
                                  model.param_logical_axes(), mesh)
        _SHARDING_CACHE[key] = got
    return got


def _tile_to(arr, b_pad: int):
    """Tile ``arr`` along axis 0 to length ``b_pad`` (b_pad >= len)."""
    b = int(arr.shape[0])
    if b == b_pad:
        return arr
    idx = np.arange(b_pad) % b
    if isinstance(arr, np.ndarray):
        return np.take(arr, idx, axis=0)
    return jnp.take(arr, jnp.asarray(idx), axis=0)


class MeshedCloudWorker:
    """Owns the mesh + sharded param tree and serves batched cloud steps.

    ``try_cloud_step_batch`` is the hook :meth:`DecoupledRunner.
    cloud_step_batch` calls when a mesh worker is wired in: it returns the
    per-request logits list for groups it can serve fused, or ``None`` to
    fall back to the single-device path (mixed codecs, non-stackable
    extras, empty boundaries)."""

    def __init__(self, model: Model, params: Any, mesh: Mesh):
        self.model = model
        self.mesh = mesh
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.data_size = int(axis_sizes.get("data", 1))
        self.param_shardings = param_shardings(model, mesh)
        self.params = jax.device_put(params, self.param_shardings)
        self._fused: Dict[Tuple, Any] = {}
        self._tails: Dict[int, Any] = {}
        # Serving stats the benchmarks/tests assert on.
        self.fused_calls = 0
        self.group_sizes: List[int] = []

    # ------------------------------------------------------------ helpers
    def _batch_sharding(self, ndim: int) -> NamedSharding:
        return NamedSharding(self.mesh, P("data", *([None] * (ndim - 1))))

    def _put_batched(self, tree):
        """Commit every leaf batch-sharded along its leading axis."""
        return jax.tree.map(
            lambda a: jax.device_put(a, self._batch_sharding(a.ndim)), tree)

    def _stack_extras(self, extras_list: Sequence[Any],
                      counts: Sequence[int]):
        """Concatenate per-request extras trees along the batch axis.
        Returns None (no extras), the stacked tree, or ``_UNSTACKABLE``
        when any leaf's leading dim is not that request's batch (e.g.
        mrope's (3, b, s) position grid)."""
        if all(e is None for e in extras_list):
            return None
        if any(e is None for e in extras_list):
            return _UNSTACKABLE
        treedef = jax.tree.structure(extras_list[0])
        if any(jax.tree.structure(e) != treedef for e in extras_list[1:]):
            return _UNSTACKABLE
        cols = list(zip(*(jax.tree.leaves(e) for e in extras_list)))
        for leaves in cols:
            for leaf, cnt in zip(leaves, counts):
                if leaf.ndim == 0 or int(leaf.shape[0]) != int(cnt):
                    return _UNSTACKABLE
            if len({leaf.shape[1:] for leaf in leaves}) != 1:
                return _UNSTACKABLE
        stacked = [jnp.concatenate(leaves, axis=0) for leaves in cols]
        return jax.tree.unflatten(treedef, stacked)

    # ---------------------------------------------------------- jit cache
    def _fused_fn(self, point: int, bits: int, blob_shape: Tuple[int, ...],
                  dtype, wire: str = "bitpack"):
        """ONE jit: sharded wire decode -> constrain -> sharded tail.

        ``wire`` picks the decode flavor: "bitpack" feeds the flat
        bitpack wire codes through ``dequantize_wire_batch_impl``;
        "codes" feeds one-code-per-element stacks (the host Huffman
        decoder's output) through ``dequantize_codes_batch_impl``."""
        key = (point, bits, blob_shape, dtype, wire)
        fn = self._fused.get(key)
        if fn is None:
            from repro.kernels.quantize import ops

            model = self.model
            decode = (ops.dequantize_wire_batch_impl if wire == "bitpack"
                      else ops.dequantize_codes_batch_impl)

            def decode_block(codes, mn, mx):
                return decode(codes, mn, mx, bits, blob_shape,
                              out_dtype=dtype)

            decode_sharded = ops.shard_batch(decode_block, self.mesh)

            def fused(params, codes, mn, mx, extras):
                x = decode_sharded(codes, mn, mx)
                # Merge (n_blobs, b, ...) -> (n_blobs * b, ...): one tail
                # forward over the whole group's samples.
                x = x.reshape((-1,) + tuple(blob_shape[1:]))
                x = constrain(x, model.boundary_logical_axes(x.ndim))
                return model.run_tail(params, x, point, extras)

            fn = jax.jit(fused)
            self._fused[key] = fn
        return fn

    def _tail_fn(self, point: int):
        """Sharded tail for pre-decoded boundaries (non-bitpack codecs)."""
        fn = self._tails.get(point)
        if fn is None:
            model = self.model

            def tail(params, x, extras):
                x = constrain(x, model.boundary_logical_axes(x.ndim))
                return model.run_tail(params, x, point, extras)

            fn = jax.jit(tail)
            self._tails[point] = fn
        return fn

    # ------------------------------------------------------------ serving
    def try_cloud_step_batch(self, blobs: Sequence["Any"],
                             extras_list: Optional[Sequence[Any]],
                             plan) -> Optional[List[Any]]:
        """Serve one (point, bits, codec) group through the mesh. Returns
        the per-request logits (float-equivalent to the single-device
        fused tail) or None when the group cannot batch-shard."""
        from repro.codec import get_codec
        from repro.codec.bitpack import BitpackCodec
        from repro.codec.huffman import HuffmanCodec
        from repro.core import entropy as ent

        blobs = list(blobs)
        if not blobs or plan.is_cloud_only:
            return None
        if extras_list is None:
            extras_list = [None] * len(blobs)
        if len({b.codec for b in blobs}) != 1:
            return None
        if len({b.shape[1:] for b in blobs}) != 1:
            return None
        if any(len(b.shape) < 1 or b.num_elements == 0 for b in blobs):
            return None
        counts = [int(b.shape[0]) for b in blobs]
        extras = self._stack_extras(extras_list, counts)
        if extras is _UNSTACKABLE:
            return None
        point = int(plan.point)
        dtype = jnp.dtype(self.model.cfg.dtype)
        codec = get_codec(blobs[0].codec)
        ds = self.data_size
        total = sum(counts)

        wire = None
        if (len({b.shape for b in blobs}) == 1
                and len({b.bits for b in blobs}) == 1):
            if isinstance(codec, BitpackCodec):
                wire = "bitpack"
            elif isinstance(codec, HuffmanCodec):
                wire = "codes"
        if wire is not None:
            # Host side does framing only for bitpack (exactly like
            # codec.decode) and the per-payload entropy decode for
            # huffman (data-dependent lengths are inherently host work);
            # the dequant itself happens inside the fused sharded jit,
            # directly into the per-device batch shards — huffman groups
            # no longer fall back to the per-blob single-device path.
            nb = len(blobs)
            nb_pad = -(-nb // ds) * ds
            per = counts[0]
            if wire == "bitpack":
                stacked = np.stack([codec._wire_codes(b) for b in blobs])
            else:
                from repro.kernels.quantize.quantize import code_dtype

                cdt = np.dtype(code_dtype(int(blobs[0].bits)))
                stacked = np.stack([
                    ent.huffman_decode(b.payload).astype(cdt)
                    for b in blobs
                ])
            codes = _tile_to(stacked, nb_pad)
            mn = _tile_to(
                np.stack([np.float32(b.x_min) for b in blobs]), nb_pad)
            mx = _tile_to(
                np.stack([np.float32(b.x_max) for b in blobs]), nb_pad)
            if extras is not None:
                extras = jax.tree.map(
                    lambda a: _tile_to(a, nb_pad * per), extras)
            fn = self._fused_fn(point, int(blobs[0].bits),
                                tuple(blobs[0].shape), dtype, wire)
            args = self._put_batched((codes, mn, mx))
            extras = self._put_batched(extras)
            with jax.set_mesh(self.mesh):
                logits = fn(self.params, *args, extras)
        else:
            boundaries = codec.decode_batch(blobs, out_dtype=dtype)
            stacked = jnp.concatenate(boundaries, axis=0)
            b_pad = -(-total // ds) * ds
            stacked = _tile_to(stacked, b_pad)
            if extras is not None:
                extras = jax.tree.map(lambda a: _tile_to(a, b_pad), extras)
            stacked = self._put_batched(stacked)
            extras = self._put_batched(extras)
            fn = self._tail_fn(point)
            with jax.set_mesh(self.mesh):
                logits = fn(self.params, stacked, extras)
        self.fused_calls += 1
        self.group_sizes.append(total)
        logits = logits[:total]
        if len(counts) == 1:
            return [logits]
        return list(jnp.split(logits, np.cumsum(counts)[:-1], axis=0))

    def cloud_step(self, blob: Any, extras: Any, plan) -> Any:
        """One blob through the sharded tail, its boundary and extras
        replicated over the mesh: the path for requests that
        ``try_cloud_step_batch`` declines, since the tail's params live
        only here."""
        from repro.codec import get_codec

        dtype = jnp.dtype(self.model.cfg.dtype)
        x = get_codec(blob.codec).decode(blob, out_dtype=dtype)
        x, extras = jax.device_put((x, extras), NamedSharding(self.mesh, P()))
        with jax.set_mesh(self.mesh):
            return self._tail_fn(int(plan.point))(self.params, x, extras)


# ---------------------------------------------------------------------------
# AOT compile-only analysis (no params materialized)
# ---------------------------------------------------------------------------


def aot_tail_report(model: Model, point: int, *, batch: int = 8,
                    seq_len: int = 64, mesh: Optional[Mesh] = None
                    ) -> Dict[str, float]:
    """Compile the cloud tail at ``point`` ahead-of-time — abstract params
    only, so this works for configs whose weights cannot fit in host RAM
    (granite-34b is ~68 GB bf16) — and read XLA's per-device cost/memory
    analysis. With a mesh, params are NamedSharding-annotated through the
    rule table and the boundary enters batch-sharded, exactly the serving
    worker's layout; without one it is the replicated single-device tail.

    ``flops`` from ``cost_analysis`` is per-device AFTER SPMD
    partitioning, so ``single.flops / sharded.flops`` is the achieved
    parallel fraction — a deterministic stand-in for wall-clock speedup on
    fake CPU mesh devices. ``argument_bytes_per_device`` is the per-device
    HBM needed just to hold the inputs (params + boundary), the footprint
    gate ``benchmarks/meshed_tail.py`` checks against real HBM sizes."""
    from repro.data.synthetic import make_batch
    from repro.launch.hlo_analysis import cost_analysis_dict

    specs = model.abstract_params()
    raw = make_batch(model.cfg, batch, seq_len, seed=0)
    batch_spec = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        raw)
    head = jax.eval_shape(lambda p, b: model.run_head(p, b, point),
                          specs, batch_spec)
    boundary, extras = head if isinstance(head, tuple) else (head, None)

    def tail(p, x, e):
        x = constrain(x, model.boundary_logical_axes(x.ndim))
        return model.run_tail(p, x, point, e)

    if mesh is None:
        lowered = jax.jit(tail).lower(specs, boundary, extras)
    else:
        pshard = param_shardings(model, mesh)
        bshard = NamedSharding(
            mesh, P("data", *([None] * (len(boundary.shape) - 1))))
        eshard = jax.tree.map(lambda a: NamedSharding(mesh, P()), extras)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                tail, in_shardings=(pshard, bshard, eshard),
            ).lower(specs, boundary, extras)
    compiled = lowered.compile()
    cost = cost_analysis_dict(compiled)
    mem = compiled.memory_analysis()
    return {
        "n_devices": 1 if mesh is None else int(mesh.size),
        "flops_per_device": float(cost.get("flops", 0.0)),
        "argument_bytes_per_device": float(
            getattr(mem, "argument_size_in_bytes", 0)),
        "temp_bytes_per_device": float(
            getattr(mem, "temp_size_in_bytes", 0)),
        "output_bytes_per_device": float(
            getattr(mem, "output_size_in_bytes", 0)),
    }
