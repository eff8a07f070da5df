"""Launch-path structural tests: build_step lowers for every mode on a
1-device mesh with reduced configs (the 256/512-device meshes are
exercised by repro.launch.dryrun out of process — jax device count is
locked at first init, so tests use the real single CPU device)."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from conftest import reduced_model
from repro.config import INPUT_SHAPES, ShapeConfig, TrainConfig
from repro.launch.dryrun import build_step
from repro.launch.hlo_analysis import (
    CollectiveStats,
    parse_collectives,
)

TINY_SHAPES = {
    "train": ShapeConfig("tiny_train", 32, 4, "train"),
    "prefill": ShapeConfig("tiny_prefill", 32, 2, "prefill"),
    "decode": ShapeConfig("tiny_decode", 32, 2, "decode"),
}


def _mesh():
    dev = np.asarray(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"))


@pytest.mark.parametrize("arch", ["olmo-1b", "grok-1-314b", "zamba2-2.7b",
                                  "seamless-m4t-large-v2"])
@pytest.mark.parametrize("mode", ["train", "prefill", "decode"])
def test_build_step_lowers(arch, mode):
    model, _ = reduced_model(arch)
    mesh = _mesh()
    step, args, in_sh = build_step(model, TINY_SHAPES[mode],
                                   TrainConfig(remat="blocks"), mesh)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=in_sh).lower(*args).compile()
    from repro.launch.hlo_analysis import cost_analysis_dict

    ca = cost_analysis_dict(compiled)
    assert ca.get("flops", 0) > 0


def test_parse_collectives_on_synthetic_hlo():
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(bf16[1,128]{1,0} %x), replica_groups=[32,8]<=[8,32]T(1,0), dimensions={0}
  %ar = f32[16,16]{1,0} all-reduce(f32[16,16]{1,0} %y), replica_groups={{0,1,2,3}, {4,5,6,7}}, to_apply=%add
  %rs = f32[2,16]{1,0} reduce-scatter(f32[8,16]{1,0} %z), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = bf16[4,4]{1,0} collective-permute(bf16[4,4]{1,0} %w), source_target_pairs={{0,1}}
"""
    st = parse_collectives(hlo)
    kinds = {o.kind for o in st.ops}
    assert kinds == {"all-gather", "all-reduce", "reduce-scatter",
                     "collective-permute"}
    by = st.by_kind()
    # all-gather: group 8, out 8*128*2 bytes, wire = out * 7/8
    ag = [o for o in st.ops if o.kind == "all-gather"][0]
    assert ag.group_size == 8
    assert ag.wire_bytes == pytest.approx(8 * 128 * 2 * 7 / 8)
    # all-reduce: group 4, wire = 2 * in * 3/4
    ar = [o for o in st.ops if o.kind == "all-reduce"][0]
    assert ar.group_size == 4
    assert ar.wire_bytes == pytest.approx(2 * 16 * 16 * 4 * 3 / 4)
    # reduce-scatter wire = in * 3/4
    rs = [o for o in st.ops if o.kind == "reduce-scatter"][0]
    assert rs.wire_bytes == pytest.approx(8 * 16 * 4 * 3 / 4)
    # permute wire = size
    cp = [o for o in st.ops if o.kind == "collective-permute"][0]
    assert cp.wire_bytes == 4 * 4 * 2
    assert st.total_wire_bytes == sum(o.wire_bytes for o in st.ops)


def test_input_specs_cover_all_production_shapes():
    """Every (reduced arch, production shape) input tree builds without
    allocation (eval_shape level) — the full-size version is exercised by
    the out-of-process dry-run."""
    model, _ = reduced_model("qwen2-vl-7b")
    for name, shape in INPUT_SHAPES.items():
        specs = model.input_specs(shape)
        assert "tokens" in specs
