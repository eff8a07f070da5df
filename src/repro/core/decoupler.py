"""Deep-structure decoupling: split a model at point i*, quantize the
boundary to c bits, and run head (edge) / tail (cloud) as separate jitted
functions — plus the engine that glues predictors + latency model + ILP
into the paper's decision procedure.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:
    # Runtime import would cycle: the codec package depends on
    # repro.core.quantization. get_codec is imported lazily where needed.
    from repro.codec import BoundaryCodec, WireBlob

from repro.config.types import JaladConfig
from repro.core.ilp import ILPProblem, solve
from repro.core.latency import LatencyModel
from repro.core.planner import PlanSpace, StreamPlanTerms
from repro.core.predictor import PredictorTables
from repro.core.tri_planner import TriPlanSpace
from repro.core.quantization import quantize_dequantize
from repro.models.api import Model
from repro.utils.trace import span


@dataclass
class DecoupledPlan:
    """The outcome of one ILP solve: where to cut, at what bit width, and
    through which boundary codec.

    A three-tier solve (``repro.core.tri_planner``) fills the second cut:
    the device runs ``[0, point]``, an edge server runs ``(point, point2]``
    and the cloud runs the rest, with the second boundary quantized to
    ``bits2`` through ``codec2``. Two-tier plans keep the defaults
    (``point2 = -1``), so every existing consumer of the single-cut
    contract is untouched. A degenerate middle tier (``point2 == point``)
    relays the first blob through the edge server unchanged — the planner
    only emits such cells with ``bits2 == bits`` and ``codec2 == codec``.
    """

    point: int
    bits: int
    predicted_latency: float
    predicted_acc_drop: float
    solve_ms: float
    codec: str = "huffman"
    # --- three-tier extension (second ordered cut; -1 = no middle tier) ---
    point2: int = -1
    bits2: int = 0
    codec2: str = ""

    @property
    def is_cloud_only(self) -> bool:
        return self.point < 0

    @property
    def has_second_cut(self) -> bool:
        return self.point2 >= 0


@dataclass
class DecoupledRunner:
    """Executable split model. ``edge_step`` runs on the edge device and
    returns the encoded boundary; ``cloud_step`` finishes the inference.
    Both delegate the wire format entirely to the plan's
    :class:`BoundaryCodec` — the runner knows nothing about bit widths,
    entropy stages or code dtypes. ``run`` wires them together (with exact
    wire-size accounting)."""

    model: Model
    params: Any
    plan: DecoupledPlan
    # Optional repro.serving.meshed.MeshedCloudWorker: when set, every
    # cloud step runs the worker's sharded tail (see cloud_step_batch).
    mesh_worker: Optional[Any] = None
    # What the edge half reads; ``None`` means ``params``. A runner of a
    # meshed cloud gets only the head's slice (``Model.head_params``).
    edge_params: Any = None

    def __post_init__(self):
        from repro.codec import get_codec

        if self.edge_params is None:
            self.edge_params = self.params
        self._head = jax.jit(self.model.run_head, static_argnums=2)
        self._tail = jax.jit(self.model.run_tail, static_argnums=2)
        self._codec: "BoundaryCodec" = get_codec(self.plan.codec)

    def edge_step(self, batch) -> Tuple["WireBlob", Any]:
        out = self._head(self.edge_params, batch, self.plan.point)
        boundary, extras = out if isinstance(out, tuple) else (out, None)
        with span("codec.encode", rows=1):
            blob = self._codec.encode(boundary, self.plan.bits)
        return blob, extras

    def edge_step_batch(self, batches) -> List[Tuple["WireBlob", Any]]:
        """Micro-batched edge step: run the head per request, then encode
        every boundary in **one** batched codec launch (same-shape
        boundaries stack; the codec falls back to a loop otherwise). Each
        blob is byte-identical to the per-request ``edge_step``."""
        outs = [self._head(self.edge_params, b, self.plan.point)
                for b in batches]
        pairs = [o if isinstance(o, tuple) else (o, None) for o in outs]
        with span("codec.encode", rows=len(pairs)):
            blobs = self._codec.encode_batch([p[0] for p in pairs],
                                             self.plan.bits)
        return [(blob, extras) for blob, (_, extras) in zip(blobs, pairs)]

    def cloud_step(self, blob: "WireBlob", extras=None):
        from repro.codec import get_codec

        if self.mesh_worker is not None:
            return self.mesh_worker.cloud_step(blob, extras, self.plan)
        dtype = jnp.dtype(self.model.cfg.dtype)
        with span("codec.decode", rows=1):
            boundary = get_codec(blob.codec).decode(blob, out_dtype=dtype)
        if extras is not None:
            return self._tail(self.params, boundary, self.plan.point, extras)
        return self._tail(self.params, boundary, self.plan.point)

    def cloud_step_batch(self, blobs: List["WireBlob"],
                         extras_list: Optional[List[Any]] = None,
                         fuse_tail: bool = False) -> List[Any]:
        """Batched cloud half, mirroring ``edge_step_batch``: one batched
        wire decode (``BoundaryCodec.decode_batch``, bit-identical per blob
        by the codec contract) feeding the tail forwards.

        ``fuse_tail=False`` (default) runs the tails through the SAME
        jitted per-request callable as ``cloud_step``, so each result is
        byte-identical to serving the blob alone — the decode batching
        still collapses B dequant launches into one. ``fuse_tail=True``
        additionally concatenates the group along the batch axis into ONE
        tail forward; that is the fastest path but only float-level
        equivalent (XLA re-blocks matmul/conv reductions per batch size,
        so bitwise equality across batch shapes is impossible on CPU —
        measured ~1e-6 relative; the contract is tolerance-pinned in
        ``tests/test_meshed.py::test_fused_tail_float_contract``).
        Requests carrying ``extras`` or boundaries whose trailing dims
        differ fall back to the per-request loop.

        With a ``mesh_worker`` wired in, the group goes down the
        mesh-aware path first: one sharded wire decode straight into
        per-device batch shards, ``sharding.activation.constrain`` on the
        boundary, and ONE tail forward with NamedSharding-annotated
        params across the whole mesh. That path is inherently fused —
        same float-equivalence contract as ``fuse_tail=True`` — and can
        additionally batch same-structure ``extras`` (transformer
        position/encoder trees). Groups the worker cannot batch-shard
        (mixed codecs, non-stackable extras) go through it one blob at a
        time (``cloud_step``): the tail params live only on the mesh."""
        with span("cloud", rows=len(blobs)):
            return self._cloud_step_batch(blobs, extras_list, fuse_tail)

    def _cloud_step_batch(self, blobs, extras_list, fuse_tail):
        from repro.codec import get_codec

        if extras_list is None:
            extras_list = [None] * len(blobs)
        if not blobs:
            return []
        if self.mesh_worker is not None:
            out = self.mesh_worker.try_cloud_step_batch(
                blobs, extras_list, self.plan)
            if out is not None:
                return out
        batchable = (
            self.mesh_worker is None
            and len(blobs) > 1
            and all(e is None for e in extras_list)
            and len({b.codec for b in blobs}) == 1
            and len({b.shape[1:] for b in blobs}) == 1
            and all(len(b.shape) >= 1 for b in blobs)
        )
        if not batchable:
            return [self.cloud_step(b, e)
                    for b, e in zip(blobs, extras_list)]
        dtype = jnp.dtype(self.model.cfg.dtype)
        with span("codec.decode", rows=len(blobs)):
            boundaries = get_codec(blobs[0].codec).decode_batch(
                blobs, out_dtype=dtype)
        if not fuse_tail:
            return [self._tail(self.params, x, self.plan.point)
                    for x in boundaries]
        stacked = jnp.concatenate(boundaries, axis=0)
        logits = self._tail(self.params, stacked, self.plan.point)
        splits = np.cumsum([b.shape[0] for b in blobs])[:-1]
        return list(jnp.split(logits, splits, axis=0))

    def run(self, batch):
        """Full decoupled inference; returns (logits, transfer_bytes)."""
        blob, extras = self.edge_step(batch)
        logits = self.cloud_step(blob, extras)
        return logits, blob.nbytes

    def stream_session(self, serve_cfg, cloud_kv_bits: int = 8):
        """Token-level serving under this runner's plan: a
        :class:`~repro.serving.streaming.TokenStreamSession` whose decode
        loop runs head-on-edge / boundary-through-this-codec /
        tail-on-cloud every token (with int8 cloud KV by default)."""
        from repro.serving.streaming import TokenStreamSession

        return TokenStreamSession(self.model, self.params, serve_cfg,
                                  plan=self.plan,
                                  cloud_kv_bits=cloud_kv_bits)

    def run_simulated(self, batch):
        """jit-friendly end-to-end path: the codec's value transform
        in-graph (no host serialization round trip). Numerically identical
        boundary values."""
        out = self._head(self.params, batch, self.plan.point)
        boundary, extras = out if isinstance(out, tuple) else (out, None)
        xq = self._codec.simulate(boundary, self.plan.bits)
        xq = xq.astype(jnp.dtype(self.model.cfg.dtype))
        if extras is not None:
            return self._tail(self.params, xq, self.plan.point, extras)
        return self._tail(self.params, xq, self.plan.point)


@dataclass
class TriDecoupledRunner:
    """Executable three-way split (device → edge server → cloud) for a plan
    carrying a second cut. Three steps mirror the three tiers:
    ``device_step`` runs ``[0, point]`` and encodes the first boundary;
    ``edge_server_step`` decodes it, runs ``(point, point2]`` and encodes
    the second boundary; ``cloud_step`` finishes from ``point2``. A
    degenerate middle tier (``point2 == point``) relays the device blob
    through unchanged — no decode/re-encode, byte-identical wire blob on
    both links, exactly how the planner prices diagonal cells."""

    model: Model
    params: Any
    plan: DecoupledPlan

    def __post_init__(self):
        from repro.codec import get_codec

        if not self.plan.has_second_cut:
            raise ValueError("TriDecoupledRunner needs a plan with a second "
                             "cut (point2 >= 0); use DecoupledRunner for "
                             "two-tier plans")
        if self.plan.point2 < self.plan.point:
            raise ValueError(f"cuts must be ordered, got "
                             f"({self.plan.point}, {self.plan.point2})")
        self._head = jax.jit(self.model.run_head, static_argnums=2)
        self._seg = jax.jit(self.model.run_segment, static_argnums=(2, 3))
        self._tail = jax.jit(self.model.run_tail, static_argnums=2)
        self._codec1: "BoundaryCodec" = get_codec(self.plan.codec)
        self._codec2: "BoundaryCodec" = get_codec(self.plan.codec2)

    @property
    def is_relay(self) -> bool:
        return self.plan.point2 == self.plan.point

    def device_step(self, batch) -> Tuple["WireBlob", Any]:
        out = self._head(self.params, batch, self.plan.point)
        boundary, extras = out if isinstance(out, tuple) else (out, None)
        blob = self._codec1.encode(boundary, self.plan.bits)
        return blob, extras

    def edge_server_step(self, blob: "WireBlob",
                         extras=None) -> Tuple["WireBlob", Any]:
        """Middle tier: first-link blob in, second-link blob out."""
        from repro.codec import get_codec

        if self.is_relay:
            return blob, extras
        dtype = jnp.dtype(self.model.cfg.dtype)
        boundary = get_codec(blob.codec).decode(blob, out_dtype=dtype)
        out = self._seg(self.params, boundary, self.plan.point,
                        self.plan.point2, extras)
        boundary2, extras = out if isinstance(out, tuple) else (out, extras)
        blob2 = self._codec2.encode(boundary2, self.plan.bits2)
        return blob2, extras

    def cloud_step(self, blob: "WireBlob", extras=None):
        from repro.codec import get_codec

        dtype = jnp.dtype(self.model.cfg.dtype)
        boundary = get_codec(blob.codec).decode(blob, out_dtype=dtype)
        if extras is not None:
            return self._tail(self.params, boundary, self.plan.point2,
                              extras)
        return self._tail(self.params, boundary, self.plan.point2)

    def run(self, batch):
        """Full three-hop inference; returns
        ``(logits, link1_bytes, link2_bytes)``."""
        blob1, extras = self.device_step(batch)
        blob2, extras = self.edge_server_step(blob1, extras)
        logits = self.cloud_step(blob2, extras)
        return logits, blob1.nbytes, blob2.nbytes


# ---------------------------------------------------------------------------
# Recurrent-state compression (SSM/hybrid decode across the cut)
# ---------------------------------------------------------------------------


def compress_state(caches, bits: int):
    """JALAD extension for SSM decode: the recurrent state that crosses the
    cut is itself quantized (per-leaf min-max)."""
    return jax.tree.map(
        lambda a: quantize_dequantize(a, bits).astype(a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating)
        else a,
        caches,
    )


# ---------------------------------------------------------------------------
# Decision engine
# ---------------------------------------------------------------------------


@dataclass
class JaladEngine:
    """Holds the predictor tables + latency model and answers "where do we
    cut right now?" for the current bandwidth (paper Sec. III-E).

    All cost math is delegated to one :class:`PlanSpace` (built lazily,
    cached): the bandwidth-independent parts of the objective are
    precomputed once, so a re-decision under a new bandwidth is a single
    fused argmin instead of an ILPProblem rebuild."""

    model: Model
    tables: PredictorTables
    latency: LatencyModel
    cfg: JaladConfig
    point_indices: Optional[List[int]] = None   # tables row -> model point
    # Cloud mesh applied to lazily-built spaces (set by with_cloud_mesh).
    cloud_mesh: Optional[Any] = None
    _plan_space: Optional[PlanSpace] = field(
        default=None, repr=False, compare=False)
    _stream_terms: Optional[StreamPlanTerms] = field(
        default=None, repr=False, compare=False)
    _tri_space: Optional[TriPlanSpace] = field(
        default=None, repr=False, compare=False)

    @property
    def plan_space(self) -> PlanSpace:
        if self._plan_space is None:
            self._plan_space = PlanSpace.build(
                self.tables, self.latency, self.cfg.accuracy_drop_budget,
                self.point_indices,
            )
        return self._plan_space

    @property
    def tri_space(self) -> TriPlanSpace:
        """The three-tier (device → edge server → cloud) generalization of
        :attr:`plan_space`, built lazily from the same tables/latency with
        the config's middle-tier device and power model. Degenerate at
        ``BW1 = inf`` it reproduces ``plan_space.decide`` bitwise."""
        if self._tri_space is None:
            tri = TriPlanSpace.build(
                self.tables, self.latency, self.cfg.accuracy_drop_budget,
                edge_server=self.cfg.edge_server,
                power=self.cfg.power,
                energy_weight=self.cfg.energy_weight,
                point_indices=self.point_indices,
            )
            if self.cloud_mesh is not None:
                tri = tri.with_cloud_mesh(self.cloud_mesh)
            self._tri_space = tri
        return self._tri_space

    def decide_tri(self, bandwidth1: Optional[float] = None,
                   bandwidth2: Optional[float] = None,
                   energy_budget: Optional[float] = None) -> DecoupledPlan:
        """Three-tier decision at the two link bandwidths (defaults from
        the config), honouring the config's energy budget unless
        overridden."""
        bw1 = bandwidth1 if bandwidth1 is not None else \
            self.cfg.bandwidth_bytes_per_s
        bw2 = bandwidth2 if bandwidth2 is not None else \
            self.cfg.bandwidth2_bytes_per_s
        budget = energy_budget if energy_budget is not None else \
            self.cfg.energy_budget_j
        return self.tri_space.decide(bw1, bw2, energy_budget=budget)

    def ilp_problem(self, bandwidth: float) -> ILPProblem:
        """The selection problem over the joint choice axis: the (C, K)
        bits x codec grid flattens to one column per (c, k) pair, so the
        ILP picks the wire format along with the cut (Auto-Split style:
        the compression scheme is a decision variable). Materialized from
        the PlanSpace for the oracle solvers."""
        return self.plan_space.ilp_problem(bandwidth)

    def decide(self, bandwidth: Optional[float] = None,
               method: str = "planner") -> DecoupledPlan:
        """Decide (point, bits, codec) at a bandwidth. ``method="planner"``
        is the fused-argmin fast path; ``"enumeration"``/``"bnb"`` run the
        cross-checked ILP oracles over the identical cost tables."""
        bw = bandwidth if bandwidth is not None else \
            self.cfg.bandwidth_bytes_per_s
        space = self.plan_space
        if method == "planner":
            return space.decide(bw)
        sol = solve(space.ilp_problem(bw), method)
        if sol is None:
            # Infeasible => fall back to cloud-only (paper's worst case is
            # x_{NC} = 1, i.e. effectively no decoupling).
            return space.cloud_only_plan(bw)
        return space.plan_from_solution(sol)

    @property
    def stream_terms(self) -> StreamPlanTerms:
        """The per-token steady-state extension of this engine's
        PlanSpace (built lazily, cached). The calibration unit is one
        batch of ``input_bytes / 4`` tokens (LM inputs are int32 token
        ids, so ``input_bytes = B * S * 4``), which converts the
        per-batch FMAC time vectors into per-token stage times."""
        if self._stream_terms is None:
            if self.model.cfg.family == "cnn":
                raise ValueError(
                    "token streaming is autoregressive decode; CNNs "
                    "decouple per request (use decide/make_runner)")
            self._stream_terms = self.plan_space.with_streaming(
                self.model.cfg.d_model,
                self.latency.input_bytes / 4.0,
            )
        return self._stream_terms

    def decide_streaming(self, bandwidth: Optional[float] = None,
                         expected_tokens: float = 128.0,
                         method: str = "planner") -> DecoupledPlan:
        """Decide (point, bits, codec) for token-level streaming: the
        one-shot objective plus ``expected_tokens`` times the per-token
        steady-state term (edge step + stream-frame bytes / BW + cloud
        step). ``method`` mirrors :meth:`decide` — ``"planner"`` is the
        fused argmin, ``"enumeration"``/``"bnb"`` the ILP oracles over
        bitwise-identical streaming costs."""
        bw = bandwidth if bandwidth is not None else \
            self.cfg.bandwidth_bytes_per_s
        terms = self.stream_terms
        if method == "planner":
            return terms.decide(bw, expected_tokens)
        sol = solve(terms.ilp_problem(bw, expected_tokens), method)
        if sol is None:
            return terms.cloud_only_plan(bw, expected_tokens)
        return terms.plan_from_solution(sol)

    def for_edge(self, edge_profile) -> "JaladEngine":
        """A per-device engine sharing this engine's tables, cloud profile
        and PlanSpace precomputation — only the edge-time vector differs.
        The fleet server builds one of these per heterogeneous device."""
        import dataclasses as _dc

        lat = LatencyModel(self.latency.fmacs_per_point, edge_profile,
                           self.latency.cloud, self.latency.input_bytes)
        return _dc.replace(self, latency=lat,
                           _plan_space=self.plan_space.with_edge(edge_profile),
                           _stream_terms=None, _tri_space=None)

    def with_cloud_mesh(self, mesh_model) -> "JaladEngine":
        """An engine whose PlanSpace prices the cloud side under a
        :class:`~repro.core.latency.CloudMeshModel` (T_C / M + per-layer
        collectives) — the planner-side half of the meshed cloud worker.
        Identity at mesh size 1; ``for_edge`` views derived from this
        engine keep the meshed cloud vector."""
        import dataclasses as _dc

        tri = (self._tri_space.with_cloud_mesh(mesh_model)
               if self._tri_space is not None else None)
        return _dc.replace(
            self, _plan_space=self.plan_space.with_cloud_mesh(mesh_model),
            _stream_terms=None, _tri_space=tri, cloud_mesh=mesh_model)

    def make_runner(self, params, plan: DecoupledPlan,
                    mesh_worker: Optional[Any] = None,
                    edge_params: Any = None) -> DecoupledRunner:
        return DecoupledRunner(self.model, params, plan,
                               mesh_worker=mesh_worker,
                               edge_params=edge_params)

    def make_tri_runner(self, params,
                        plan: DecoupledPlan) -> TriDecoupledRunner:
        return TriDecoupledRunner(self.model, params, plan)
