"""Edge-cloud JALAD serving runtime (the paper's deployment, Fig. 1).

A simulated-clock execution of decoupled inference:

  edge compute (T = w*Q_edge/F_edge)  ->  encode (real wire bytes from the
  plan's boundary codec)
  ->  channel transfer (bytes / BW, with a bandwidth trace)
  ->  cloud compute (T = w*Q_cloud/F_cloud)

The numerical result is produced by actually running the decoupled model
(head -> codec encode -> codec decode -> tail); the latency is accounted with the
paper's FMAC model so experiments are device-independent and reproducible.
The AdaptationController re-solves the ILP as the bandwidth trace drifts —
reproducing the paper's Fig. 8 behaviour ("JALAD remains a stable low
latency by adaptively changing the decoupling strategy").
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import (
    Any, Callable, Dict, Iterable, List, Optional, Protocol, Tuple,
    runtime_checkable,
)

from repro.config.types import JaladConfig
from repro.core.adaptation import AdaptationController
from repro.core.decoupler import DecoupledPlan, DecoupledRunner, JaladEngine
from repro.core.latency import LatencyModel, PNG_RATIO


@dataclass
class LatencyBreakdown:
    edge_s: float
    transfer_s: float
    cloud_s: float
    bytes_sent: int
    plan_point: int
    plan_bits: int
    plan_codec: str = ""
    # --- three-tier extension (zeros for two-tier breakdowns, so their
    # ``total_s`` is untouched): middle-tier compute + second link ---
    edge_server_s: float = 0.0
    transfer2_s: float = 0.0
    bytes_sent2: int = 0
    plan_point2: int = -1
    plan_bits2: int = 0
    plan_codec2: str = ""

    @property
    def total_s(self) -> float:
        return (self.edge_s + self.transfer_s + self.edge_server_s
                + self.transfer2_s + self.cloud_s)


@runtime_checkable
class Servable(Protocol):
    """Anything ``serve_trace`` can advance under one trace step: the
    item prices and executes itself against the server. Streaming
    sessions (:class:`~repro.serving.streaming.TokenStreamSession`)
    implement this; plain batches don't and go through ``serve_batch``.
    Structural — no registration, no isinstance chains on concrete
    session types."""

    def serve(self, server: "EdgeCloudServer",
              bandwidth: float) -> "LatencyBreakdown":
        ...


@dataclass
class RunnerCache:
    """(point, bits, codec) -> DecoupledRunner, shared by the synchronous
    and the pipelined servers. Thread-safe: the pipelined server warms it
    from an adaptation listener while the edge stage reads it.

    ``mesh_worker`` (a :class:`~repro.serving.meshed.MeshedCloudWorker`)
    is threaded into every runner built here, so all cached plans share
    ONE mesh + sharded param tree for their cloud steps. The edge half
    of such a runner reads only the head's slice of ``params``
    (``Model.head_params``), copied once per cut to the default device:
    ``params`` may then be created sharded over the mesh
    (``Model.init(rng, param_shardings(model, mesh))``) and no device
    ever holds the whole tree."""

    engine: JaladEngine
    params: Any
    mesh_worker: Optional[Any] = None
    _cache: Dict[Tuple[int, int, str], DecoupledRunner] = field(
        default_factory=dict
    )
    _heads: Dict[int, Any] = field(default_factory=dict, repr=False)
    _lock: Any = None
    _full_forward: Any = field(default=None, repr=False, compare=False)

    def __post_init__(self):
        import threading

        self._lock = threading.Lock()

    def full_forward(self):
        """The jitted whole-model forward every server falls back to on a
        cloud-only plan — jitted once and shared, like the split runners.
        (A benign race can double-jit; last writer wins, same as get().)"""
        if self._full_forward is None:
            import jax

            self._full_forward = jax.jit(self.engine.model.forward)
        return self._full_forward

    def get(self, plan: DecoupledPlan) -> DecoupledRunner:
        key = (plan.point, plan.bits, plan.codec)
        with self._lock:
            runner = self._cache.get(key)
        if runner is None:
            # Build outside the lock: a miss (e.g. the adaptation listener
            # pre-registering a new plan) must not stall hits from the
            # other pipeline stages.
            runner = self.engine.make_runner(
                self.params, plan, mesh_worker=self.mesh_worker,
                edge_params=self._edge_params(plan.point))
            with self._lock:
                runner = self._cache.setdefault(key, runner)
        return runner

    def _edge_params(self, point: int) -> Any:
        if self.mesh_worker is None:
            return None
        head = self._heads.get(point)
        if head is None:
            import jax

            head = jax.device_put(
                self.engine.model.head_params(self.params, point),
                jax.devices()[0])
            with self._lock:
                head = self._heads.setdefault(point, head)
        return head


@dataclass
class EdgeCloudServer:
    """Serves batches through the current JALAD decoupling, one request at
    a time (edge, transfer and cloud strictly in sequence). The pipelined
    variant that overlaps the three stages lives in
    ``repro.serving.pipeline``."""

    engine: JaladEngine
    params: Any
    controller: Optional[AdaptationController] = None
    clock: float = 0.0
    log: List[LatencyBreakdown] = field(default_factory=list)
    runners: Optional[RunnerCache] = None

    def __post_init__(self):
        if self.controller is None:
            self.controller = AdaptationController(self.engine)
        if self.runners is None:
            self.runners = RunnerCache(self.engine, self.params)

    def _runner(self, plan: DecoupledPlan) -> DecoupledRunner:
        return self.runners.get(plan)

    def record(self, bd: LatencyBreakdown) -> LatencyBreakdown:
        """Account one served unit: feed the controller's bandwidth
        estimator with the transfer observation, advance the simulated
        clock, append to the log. Every serving path — including
        :class:`Servable` items pricing themselves — funnels through
        here."""
        self.controller.observe_transfer(max(bd.bytes_sent, 1),
                                         max(bd.transfer_s, 1e-9))
        self.clock += bd.total_s
        self.log.append(bd)
        return bd

    def serve_batch(self, batch, bandwidth: float) -> Tuple[Any, LatencyBreakdown]:
        """Run one batch at the given true bandwidth; returns (logits,
        latency breakdown). Advances the simulated clock."""
        plan = self.controller.current_plan(bandwidth)
        space = self.engine.plan_space
        edge_t, cloud_t = space.stage_times(plan)
        if plan.is_cloud_only:
            # numerics: full model on the "cloud" (jitted once, cached)
            logits = self.runners.full_forward()(self.params, batch)
            nbytes = int(space.input_bytes * PNG_RATIO)
            # The fallback ships a PNG-compressed input image, not an
            # empty-string non-codec — the log must say which wire format
            # the transfer term was charged for.
            bd = LatencyBreakdown(edge_t, nbytes / bandwidth, cloud_t,
                                  nbytes, -1, 0, "png")
        else:
            runner = self._runner(plan)
            blob, extras = runner.edge_step(batch)
            logits = runner.cloud_step(blob, extras)
            transfer_t = blob.nbytes / bandwidth
            bd = LatencyBreakdown(edge_t, transfer_t, cloud_t, blob.nbytes,
                                  plan.point, plan.bits, plan.codec)
        self.record(bd)
        return logits, bd

    def serve_microbatch(self, batches: List[Any], bandwidth: float
                         ) -> List[Tuple[Any, LatencyBreakdown]]:
        """Serve several requests under one plan decision with a single
        batched edge-encode launch (``DecoupledRunner.edge_step_batch``).
        Latency accounting stays strictly sequential per request — the
        micro-batch amortizes real kernel-dispatch overhead, not modeled
        stage time. Falls back to per-request serving on a cloud-only
        plan."""
        plan = self.controller.current_plan(bandwidth)
        if plan.is_cloud_only:
            return [self.serve_batch(b, bandwidth) for b in batches]
        runner = self._runner(plan)
        edge_t, cloud_t = self.engine.plan_space.stage_times(plan)
        out = []
        for blob, extras in runner.edge_step_batch(batches):
            logits = runner.cloud_step(blob, extras)
            bd = LatencyBreakdown(edge_t, blob.nbytes / bandwidth, cloud_t,
                                  blob.nbytes, plan.point, plan.bits,
                                  plan.codec)
            self.record(bd)
            out.append((logits, bd))
        return out

    def serve_trace(self, items: Iterable[Any],
                    bandwidth_trace: Iterable[float]
                    ) -> List[LatencyBreakdown]:
        """Serve a stream of trace items under a bandwidth trace
        (Fig. 8). An item that implements the :class:`Servable` protocol
        (e.g. a token-streaming session) advances itself for one trace
        step; anything else is treated as a one-shot batch. Mixed
        streams interleave freely — both paths record through
        :meth:`record`, so the clock, the log and the bandwidth
        estimator see one consistent sequence."""
        out: List[LatencyBreakdown] = []
        for item, bw in zip(items, bandwidth_trace):
            serve = getattr(item, "serve", None)
            if callable(serve):
                out.append(serve(self, bw))
            else:
                out.append(self.serve_batch(item, bw)[1])
        return out


def build_edge_cloud_server(
    cfg,
    jalad_cfg: JaladConfig,
    *,
    seed: int = 0,
    calib_batches: int = 2,
    calib_batch_size: int = 8,
    seq_len: int = 64,
    params: Any = None,
    points: Optional[List[int]] = None,
    tables_cache_dir: Optional[str] = None,
) -> Tuple[EdgeCloudServer, Any]:
    """End-to-end factory: model -> calibration -> predictors -> latency
    model -> ILP engine -> server. The calibration measures accuracy drop
    against the un-quantized model's own predictions when no labels exist
    (prediction fidelity), exactly how A_i(c) behaves for a deployed
    pre-trained model.

    Every latency term the engine compares is per *calibration batch*:
    the S_i(c, k) tables (exact batch-blob wire bytes), ``input_bytes``
    (raw batch input) and the FMAC vectors (batch included) — so
    decoupled plans, the cloud-only fallback and the serving clock all
    agree on units.

    ``tables_cache_dir`` enables config-hashed table persistence: when a
    ``tables-<cache_key>.npz`` for this exact (arch, bits, codecs,
    points, calibration recipe, seed) exists there, startup loads it and
    skips recalibration entirely. Ignored when ``params`` is supplied by
    the caller (the tables depend on weights we cannot hash cheaply)."""
    import jax

    from repro.core.predictor import (
        PredictorTables,
        build_tables,
        load_or_build_tables,
    )
    from repro.data.synthetic import make_batch
    from repro.models.api import build_model

    model = build_model(cfg)
    caller_params = params is not None
    if params is None:
        params = model.init(jax.random.key(seed))
    n_points = len(model.decoupling_points())
    if points is None and n_points > 24:
        # Subsample decoupling points for deep models (keeps calibration
        # tractable; the ILP operates on the sampled rows).
        step = max(n_points // 16, 1)
        points = list(range(0, n_points, step))

    def calibrate() -> PredictorTables:
        batches = [
            make_batch(cfg, calib_batch_size, seq_len, seed=seed + 10 + i)
            for i in range(calib_batches)
        ]
        return build_tables(model, params, batches,
                            list(jalad_cfg.bits_choices),
                            codecs=list(jalad_cfg.codec_choices),
                            points=points)

    cache_dir = None if caller_params else tables_cache_dir
    key = PredictorTables.cache_key(
        cfg.arch_id, jalad_cfg.bits_choices, jalad_cfg.codec_choices,
        points=points, seed=seed, calib_batches=calib_batches,
        calib_batch_size=calib_batch_size, seq_len=seq_len,
        # The full config repr: reduced() variants share the arch_id but
        # must never share a table file.
        config=repr(cfg),
    )
    tables, _ = load_or_build_tables(cache_dir, key, calibrate)
    if cfg.family == "cnn":
        input_bytes = calib_batch_size * 3 * cfg.image_size * cfg.image_size
    else:
        input_bytes = calib_batch_size * seq_len * 4
    fmacs = model.per_point_fmacs(calib_batch_size, seq_len)
    lat = LatencyModel(fmacs, jalad_cfg.edge, jalad_cfg.cloud,
                       float(input_bytes))
    engine = JaladEngine(model, tables, lat, jalad_cfg,
                         point_indices=points)
    return EdgeCloudServer(engine, params), params
