import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
# This module — and ONLY this module — fakes the 512-chip fleet so the
# production meshes can be built for lower+compile dry-runs on CPU.

"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers, SPMD-partitions and compiles, and extract the roofline
terms from the compiled artifact.

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

train_4k lowers train_step (fwd+bwd+AdamW); prefill_32k lowers the prefill
step; decode_32k / long_500k lower serve_step — ONE new token against a KV
(or SSM-state) cache of seq_len, per the assignment.
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import INPUT_SHAPES, TrainConfig, get_config, list_archs
from repro.config.registry import assigned_archs
from repro.launch.hlo_analysis import analyze_compiled
from repro.launch.mesh import make_production_mesh
from repro.models.api import Model, build_model
from repro.optim import adamw
from repro.sharding.rules import shardings_for_specs
from repro.training.loop import make_train_step


def _tokens_of(model: Model, shape) -> int:
    """Tokens (or samples) processed by one step of this shape."""
    if model.cfg.family == "cnn":
        return shape.global_batch
    if shape.mode in ("train", "prefill"):
        return shape.global_batch * shape.seq_len
    return shape.global_batch  # decode: one token per sequence


def build_step(model: Model, shape, train_cfg: TrainConfig,
               mesh) -> Tuple[Any, Tuple, Tuple]:
    """Returns (step_fn, abstract_args, in_shardings)."""
    cfg = model.cfg
    abstract_params = model.abstract_params()
    param_sh = shardings_for_specs(
        abstract_params, model.param_logical_axes(), mesh
    )
    batch_specs = model.input_specs(shape)
    batch_sh = shardings_for_specs(
        batch_specs, model.batch_logical_axes(shape), mesh
    )

    if shape.mode == "train":
        step = make_train_step(model, train_cfg)
        opt_abstract = jax.eval_shape(adamw.init_state, abstract_params)
        opt_sh = adamw.AdamWState(
            NamedSharding(mesh, P()), param_sh, param_sh
        )
        return step, (abstract_params, opt_abstract, batch_specs), (
            param_sh, opt_sh, batch_sh
        )

    if shape.mode == "prefill":
        cache_len = model.cache_len_for(shape.seq_len)

        def prefill_step(params, batch):
            logits, caches = model.prefill(params, batch, cache_len)
            return logits[:, -1:], caches

        return prefill_step, (abstract_params, batch_specs), (
            param_sh, batch_sh
        )

    # decode
    def serve_step(params, batch):
        logits, caches = model.decode_step(
            params, batch["tokens"], batch["pos"], batch["caches"]
        )
        return logits, caches

    return serve_step, (abstract_params, batch_specs), (param_sh, batch_sh)


def dryrun_one(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    train_cfg: Optional[TrainConfig] = None,
    verbose: bool = True,
    rules=None,
    unroll: bool = True,
    overrides: Optional[Dict] = None,
) -> Dict:
    """Lower + compile one combination; return the roofline record."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if cfg.family == "cnn" and shape.mode != "train":
        raise ValueError("CNN testbed only lowers the train shape")
    if unroll:
        # XLA cost_analysis counts a while-loop body once; unroll the layer
        # scans so FLOPs and collective bytes reflect the real step.
        cfg = cfg.replace(scan_unroll=True)
    if overrides:
        cfg = cfg.replace(**overrides)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    train_cfg = train_cfg or TrainConfig()

    t0 = time.perf_counter()
    import repro.sharding.rules as rules_mod
    saved_rules = rules_mod.DEFAULT_RULES
    if rules is not None:
        # The override must stay active through lower(): the model's
        # activation constraints (sharding/activation.py) resolve against
        # DEFAULT_RULES at trace time.
        rules_mod.DEFAULT_RULES = rules
    try:
        step, args, in_sh = build_step(model, shape, train_cfg, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=in_sh).lower(*args)
            compiled = lowered.compile()
    finally:
        rules_mod.DEFAULT_RULES = saved_rules
    compile_s = time.perf_counter() - t0

    report = analyze_compiled(
        compiled,
        arch=arch,
        shape=shape_name,
        mesh_name=mesh_name,
        chips=mesh.devices.size,
        model_flops_global=_model_flops(model, shape),
        analytic_flops_global=model.analytic_step_flops(
            shape,
            block_remat=(shape.mode == "train"
                         and train_cfg.remat == "blocks"),
        ),
    )
    rec = report.to_dict()
    rec["compile_s"] = compile_s
    rec["mode"] = shape.mode
    if verbose:
        ma = compiled.memory_analysis()
        print(f"== {arch} x {shape_name} on {mesh_name} "
              f"({shape.mode}) — compiled in {compile_s:.1f}s")
        print(f"   memory_analysis: args={ma.argument_size_in_bytes/2**30:.2f}"
              f"GiB out={ma.output_size_in_bytes/2**30:.2f}GiB "
              f"temp={ma.temp_size_in_bytes/2**30:.2f}GiB per device")
        print(f"   cost_analysis: flops/dev={report.flops:.3e} "
              f"bytes/dev={report.bytes_accessed:.3e}")
        print(f"   collectives: { {k: (c, f'{b/2**20:.1f}MiB') for k, (c, b) in rec['collectives'].items()} }")
        print(f"   roofline: compute={report.compute_s*1e3:.2f}ms "
              f"memory={report.memory_s*1e3:.2f}ms "
              f"collective={report.collective_s*1e3:.2f}ms "
              f"-> dominant={report.dominant}")
        print(f"   useful-flops fraction (model/hlo): "
              f"{report.useful_flops_fraction:.3f}")
    return rec


def _model_flops(model: Model, shape) -> float:
    tokens = _tokens_of(model, shape)
    f = model.model_flops(tokens)
    if shape.mode == "train":
        return f  # model_flops uses 6ND (fwd+bwd) for transformers
    return f / 3.0  # inference: 2ND


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id")
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true",
                    help="all assigned archs x all shapes")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 (512 chips) instead of 16x16 (256)")
    ap.add_argument("--remat", default="blocks",
                    choices=["none", "full", "dots", "blocks"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-unroll", action="store_true",
                    help="keep layer scans rolled (faster compile, "
                    "undercounted flops/collectives)")
    ap.add_argument("--out", default=None, help="append JSON records here")
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip combos already recorded in --out")
    args = ap.parse_args(argv)

    train_cfg = TrainConfig(remat=args.remat, microbatches=args.microbatches)

    combos = []
    if args.all:
        for a in assigned_archs():
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("need --arch and --shape (or --all)")
        combos = [(args.arch, args.shape)]

    done = set()
    if args.skip_existing and args.out and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                r = json.loads(line)
                done.add((r["arch"], r["shape"]))

    records, failures = [], []
    for arch, shape in combos:
        if (arch, shape) in done:
            print(f"== {arch} x {shape}: already recorded, skipping")
            continue
        try:
            rec = dryrun_one(arch, shape, multi_pod=args.multi_pod,
                             train_cfg=train_cfg, unroll=not args.no_unroll)
            records.append(rec)
            if args.out:   # append immediately — survives interruption
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
        except Exception as e:  # noqa: BLE001 — report all failures at end
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))

    print(f"\n{len(records)} combinations lowered+compiled OK, "
          f"{len(failures)} failed")
    for a, s, e in failures:
        print(f"  FAIL {a} x {s}: {e}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
