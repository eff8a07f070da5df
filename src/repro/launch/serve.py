"""Serving launcher: batched generation with the JALAD edge-cloud runtime.

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --reduced \
      --tokens 16                       # one-shot batched generation
  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --reduced \
      --continuous --requests 6         # continuous-batching scheduler
  PYTHONPATH=src python -m repro.launch.serve --arch resnet50 --jalad \
      --bandwidth 300e3                 # synchronous edge-cloud serving
  PYTHONPATH=src python -m repro.launch.serve --arch resnet50 --jalad \
      --pipeline --requests 16          # overlapped 3-stage pipeline
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.config import JaladConfig, ServeConfig, get_config
from repro.data.synthetic import make_batch
from repro.models.api import build_model
from repro.serving.engine import ServeSession
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.log import get_logger

log = get_logger("repro.launch.serve")


def serve_lm(args) -> int:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed))
    sc = ServeConfig(max_batch=args.batch,
                     max_seq_len=args.prompt + args.tokens, seed=args.seed)
    if args.continuous:
        return _serve_lm_continuous(args, cfg, model, params, sc)
    session = ServeSession(model, params, sc)
    batch = make_batch(cfg, args.batch, args.prompt, seed=args.seed)
    batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
    out = session.generate(batch, args.tokens, temperature=args.temperature,
                          seed=args.seed)
    log.info("generated %s tokens for %d requests", out.shape, args.batch)
    print(out[:, :16])
    return 0


def _serve_lm_continuous(args, cfg, model, params, sc) -> int:
    """Continuous batching: staggered arrivals, per-request lengths."""
    from repro.serving.scheduler import ContinuousBatchingEngine, GenRequest

    engine = ContinuousBatchingEngine(model, params, sc)
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        plen = int(rng.integers(min(4, args.prompt), args.prompt + 1))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).astype(np.int32)
        engine.submit(GenRequest(
            uid=i, tokens=prompt,
            max_new_tokens=int(
                rng.integers(min(2, args.tokens), args.tokens + 1)
            ),
            temperature=args.temperature, arrival=i // 2,
        ))
    for req in engine.run():
        log.info("req %d: joined@%d done@%d slot=%d tokens=%s", req.uid,
                 req.joined_step, req.done_step, req.slot,
                 req.result[:8].tolist())
    log.info("%d requests in %d engine steps (%d joins/evictions logged)",
             len(engine.completed), engine.step_count, len(engine.events))
    return 0


def serve_jalad(args) -> int:
    """Edge-cloud decoupled serving of the CNN testbed (the paper's mode)."""
    from repro.codec import get_codec, list_codecs
    from repro.serving.edge_cloud import build_edge_cloud_server

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    codecs = tuple(list_codecs()) if args.codec == "auto" else (args.codec,)
    for name in codecs:
        get_codec(name)     # fail fast on a typo, before model/calibration
    jc = JaladConfig(bandwidth_bytes_per_s=args.bandwidth,
                     accuracy_drop_budget=args.acc_drop,
                     codec_choices=codecs)
    t0 = time.perf_counter()
    server, params = build_edge_cloud_server(
        cfg, jc, seed=args.seed, calib_batches=args.calib,
        calib_batch_size=args.batch,
        tables_cache_dir=args.tables_cache or None)
    log.info("server ready in %.2fs (tables cache: %s)",
             time.perf_counter() - t0,
             args.tables_cache or "disabled")
    if args.pipeline:
        return _serve_jalad_pipelined(args, server, params)
    batch = make_batch(cfg, args.batch, 64, seed=args.seed + 1)
    for i in range(args.requests):
        result, lat = server.serve_batch(batch, bandwidth=args.bandwidth)
        log.info(
            "req %d: point=%d bits=%d codec=%s edge=%.1fms xfer=%.1fms "
            "cloud=%.1fms sent=%dB", i, lat.plan_point, lat.plan_bits,
            lat.plan_codec, lat.edge_s * 1e3,
            lat.transfer_s * 1e3, lat.cloud_s * 1e3, lat.bytes_sent,
        )
    return 0


def _serve_jalad_pipelined(args, server, params) -> int:
    """Overlapped edge/link/cloud serving of the same request stream."""
    from repro.serving.pipeline import PipelinedEdgeCloudServer, \
        PipelineRequest

    pipe = PipelinedEdgeCloudServer(server.engine, params,
                                    controller=server.controller)
    cfg = server.engine.model.cfg
    reqs = [
        PipelineRequest(uid=i,
                        batch=make_batch(cfg, args.batch, 64,
                                         seed=args.seed + 1 + i),
                        bandwidth=args.bandwidth)
        for i in range(args.requests)
    ]
    for req in pipe.serve(reqs):
        tl = req.timeline
        log.info(
            "req %d: point=%d bits=%d codec=%s edge=[%.1f,%.1f]ms "
            "xfer=[%.1f,%.1f]ms cloud=[%.1f,%.1f]ms lat=%.1fms", req.uid,
            tl.plan_point, tl.plan_bits, tl.plan_codec,
            tl.edge_start * 1e3, tl.edge_end * 1e3,
            tl.xfer_start * 1e3, tl.xfer_end * 1e3, tl.cloud_start * 1e3,
            tl.cloud_end * 1e3, tl.latency_s * 1e3,
        )
    log.info("pipelined makespan %.1fms vs synchronous %.1fms (%.2fx)",
             pipe.makespan_s * 1e3, pipe.synchronous_time_s() * 1e3,
             pipe.synchronous_time_s() / max(pipe.makespan_s, 1e-12))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--jalad", action="store_true",
                    help="JALAD edge-cloud decoupled mode (CNN testbed)")
    ap.add_argument("--pipeline", action="store_true",
                    help="overlap edge/link/cloud stages (with --jalad)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching scheduler (LM mode)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--bandwidth", type=float, default=1e6)
    ap.add_argument("--codec", default="auto",
                    help="boundary codec for --jalad: a registry id "
                         "(huffman|bitpack|perchannel) or 'auto' to let "
                         "the ILP choose among all registered codecs")
    ap.add_argument("--tables-cache", default="",
                    help="directory for config-hashed predictor-table "
                         "persistence; a second start with the same "
                         "config loads the tables and skips calibration "
                         "(empty = always recalibrate)")
    ap.add_argument("--acc-drop", type=float, default=0.10)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--calib", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.jalad:
        return serve_jalad(args)
    return serve_lm(args)


if __name__ == "__main__":
    sys.exit(main())
