import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Layer-scaling extrapolation for combos whose fully-unrolled compile is
too slow on this 1-core container (granite-34b/grok-1 trains).

Method: the full config compiles ROLLED (proves lowering+sharding; the
sweep records that). For exact per-step accounting we compile UNROLLED
depth-reduced variants (L=2 and L=6) of the same config, fit the affine
model term(L) = a + b*L (layers are homogeneous), and extrapolate to the
real L. Records land in results/dryrun_1pod.jsonl with
"source": "unrolled-extrapolated(L2,L6)".

  PYTHONPATH=src python scripts/extrapolate_heavy.py granite-34b train_4k
"""
import json
import sys

import jax

from repro.config import INPUT_SHAPES, TrainConfig, get_config
from repro.launch.dryrun import build_step, _model_flops
from repro.launch.hlo_analysis import analyze_compiled
from repro.launch.mesh import make_production_mesh
from repro.models.api import build_model

L_SMALL, L_BIG = 2, 6


def measure(cfg, shape, train_cfg, mesh):
    model = build_model(cfg)
    step, args, in_sh = build_step(model, shape, train_cfg, mesh)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=in_sh).lower(*args).compile()
    return analyze_compiled(
        compiled, arch=cfg.arch_id, shape=shape.name, mesh_name="16x16",
        chips=mesh.devices.size, model_flops_global=0.0,
    )


def main(arch: str, shape_name: str):
    base = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    tc = TrainConfig(remat="blocks")
    mesh = make_production_mesh()

    reports = {}
    for L in (L_SMALL, L_BIG):
        pattern = base.block_pattern[:L] if base.block_pattern else ""
        cfg = base.replace(num_layers=L, block_pattern=pattern,
                           scan_unroll=True)
        reports[L] = measure(cfg, shape, tc, mesh)
        print(f"L={L}: flops/dev={reports[L].flops:.3e} "
              f"bytes/dev={reports[L].bytes_accessed:.3e} "
              f"wire/dev={reports[L].wire_bytes:.3e}")

    L_full = base.num_layers
    def fit(get):
        y1, y2 = get(reports[L_SMALL]), get(reports[L_BIG])
        b = (y2 - y1) / (L_BIG - L_SMALL)
        a = y1 - b * L_SMALL
        return a + b * L_full

    model_full = build_model(base)
    flops = fit(lambda r: r.flops)
    nbytes = fit(lambda r: r.bytes_accessed)
    wire = fit(lambda r: r.wire_bytes)
    analytic = model_full.analytic_step_flops(
        shape, block_remat=(shape.mode == "train"))
    from repro.config import TPU_V5E, TPU_V5E_HBM_BW, TPU_V5E_ICI_BW
    rec = {
        "arch": arch, "shape": shape_name, "mesh": "16x16", "chips": 256,
        "flops_per_device": flops,
        "bytes_accessed_per_device": nbytes,
        "wire_bytes_per_device": wire,
        "collectives": {
            k: [int(round(fit(lambda r, k=k: r.collectives.get(k, (0, 0))[0]))),
                fit(lambda r, k=k: r.collectives.get(k, (0, 0))[1])]
            for k in set(reports[L_SMALL].collectives)
            | set(reports[L_BIG].collectives)
        },
        "argument_bytes": int(fit(lambda r: r.argument_bytes)),
        "output_bytes": int(fit(lambda r: r.output_bytes)),
        "temp_bytes": int(fit(lambda r: r.temp_bytes)),
        "model_flops_global": _model_flops(model_full, shape),
        "analytic_flops_global": analytic,
        "compute_s": analytic / 256 / TPU_V5E.flops,
        "memory_s": nbytes / TPU_V5E_HBM_BW,
        "collective_s": wire / TPU_V5E_ICI_BW,
        "hbm_gib_per_device": (fit(lambda r: r.argument_bytes)
                               + fit(lambda r: r.output_bytes)
                               + fit(lambda r: r.temp_bytes)) / 2**30,
        "useful_flops_fraction": _model_flops(model_full, shape)
        / (flops * 256) if flops else 0.0,
        "source": f"unrolled-extrapolated(L{L_SMALL},L{L_BIG})",
        "mode": shape.mode,
    }
    terms = {"compute": rec["compute_s"], "memory": rec["memory_s"],
             "collective": rec["collective_s"]}
    rec["dominant"] = max(terms, key=terms.get)
    with open("results/dryrun_1pod.jsonl", "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(f"extrapolated {arch} x {shape_name}: "
          f"compute={rec['compute_s']*1e3:.1f}ms "
          f"memory={rec['memory_s']*1e3:.1f}ms "
          f"collective={rec['collective_s']*1e3:.1f}ms "
          f"dominant={rec['dominant']}")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
