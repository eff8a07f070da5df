"""Multi-device meshed-serving checks, run as a SUBPROCESS by
tests/test_meshed.py (XLA device count is fixed at import time, so the
8-fake-device mesh needs its own interpreter with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set first).

Not collected by pytest (no ``test_`` prefix). Prints one OK line per
check; exits non-zero on any failure.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

assert len(jax.devices()) == 8, (
    "run under XLA_FLAGS=--xla_force_host_platform_device_count=8")

from repro.codec import get_codec                                # noqa: E402
from repro.config import JaladConfig, get_config                 # noqa: E402
from repro.config.types import EDGE_TK1, EDGE_TX2                # noqa: E402
from repro.data.synthetic import make_batch                      # noqa: E402
from repro.kernels.quantize.ops import dequantize_wire_batch_sharded  # noqa: E402
from repro.launch.mesh import make_host_mesh                     # noqa: E402
from repro.serving.edge_cloud import build_edge_cloud_server     # noqa: E402
from repro.serving.fleet import FleetRequest, FleetServer        # noqa: E402
from repro.serving.meshed import param_shardings                 # noqa: E402
from repro.sharding.activation import constrain                  # noqa: E402
from repro.sharding.rules import resolve_spec                    # noqa: E402

PROFILES = [EDGE_TX2, EDGE_TK1, EDGE_TX2, EDGE_TK1]
BW = 3e5


def check_constrain_regression(mesh):
    """Satellite: ``constrain`` must be a REAL constraint inside
    ``with jax.set_mesh(mesh):`` (committed NamedSharding over 8
    devices, spec from the rule table) and a strict no-op outside."""
    x = jnp.ones((16, 4, 8), jnp.float32)
    assert constrain(x, ("batch", "seq", "embed")) is x, \
        "constrain must be a no-op outside a mesh context"
    with jax.set_mesh(mesh):
        y = constrain(x, ("batch", "seq", "embed"))
    assert y is not x
    want = resolve_spec(x.shape, ("batch", "seq", "embed"), mesh)
    assert y.sharding == NamedSharding(mesh, want), (y.sharding, want)
    assert len(y.sharding.device_set) == mesh.size
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    print("OK constrain: real constraint inside mesh, no-op outside")


def check_sharded_wire_decode(mesh):
    """The wire-decode kernel accepts sharded outputs: batch decodes land
    directly in per-device batch shards, byte-identical per blob."""
    codec = get_codec("bitpack")
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.normal(size=(4, 6, 10)).astype(np.float32))
          for _ in range(8)]
    blobs = [codec.encode(x, 5) for x in xs]
    codes = np.stack([codec._wire_codes(b) for b in blobs])
    mn = np.stack([np.float32(b.x_min) for b in blobs])
    mx = np.stack([np.float32(b.x_max) for b in blobs])
    out = dequantize_wire_batch_sharded(codes, mn, mx, 5, blobs[0].shape,
                                        mesh)
    assert out.sharding.spec[0] == "data", out.sharding
    assert len(out.sharding.device_set) > 1
    for i, b in enumerate(blobs):
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(codec.decode(b)))
    print("OK dequantize_wire_batch_sharded: sharded out, byte-identical")


def _requests(cfg, seq, n_waves=2):
    reqs, uid = [], 0
    for _ in range(n_waves):
        for d in range(len(PROFILES)):
            reqs.append(FleetRequest(
                uid=uid, device_id=d,
                batch=dict(make_batch(cfg, 1, seq, seed=uid)),
                bandwidth=BW))
            uid += 1
    return reqs


def check_fleet_e2e(arch, seq, mesh, codec_choices=("bitpack",)):
    """Sharded-vs-single-device float contract, end-to-end through
    FleetServer: the meshed worker's fused groups must match the
    single-device fused tail within float tolerance, plan for plan."""
    cfg = get_config(arch).reduced()
    jc = JaladConfig(bits_choices=(4, 8), codec_choices=codec_choices,
                     accuracy_drop_budget=0.5, bandwidth_bytes_per_s=1e6)
    srv, params = build_edge_cloud_server(
        cfg, jc, calib_batches=1, calib_batch_size=2, seq_len=seq)
    ref = FleetServer(srv.engine, params, PROFILES, fuse_cloud_tail=True)
    done_ref = ref.serve(_requests(cfg, seq))
    # The meshed fleet gets the same weights created already sharded
    # (build_edge_cloud_server's init is Model.init(key(0))): no device
    # ever holds the whole tree, and each edge head holds only its slice.
    model = srv.engine.model
    sharded = model.init(jax.random.key(0), param_shardings(model, mesh))
    meshed = FleetServer(srv.engine, sharded, PROFILES, cloud_mesh=mesh)
    done_m = meshed.serve(_requests(cfg, seq))
    assert meshed.mesh_worker.fused_calls >= 1
    assert all(a is b for a, b in zip(
        jax.tree.leaves(meshed.mesh_worker.params),
        jax.tree.leaves(sharded)))
    for runner in meshed.runners._cache.values():
        assert all(len(a.devices()) == 1
                   for a in jax.tree.leaves(runner.edge_params))
    assert max(meshed.mesh_worker.group_sizes) >= 8, \
        meshed.mesh_worker.group_sizes
    by_r = {r.uid: r for r in done_ref}
    by_m = {r.uid: r for r in done_m}
    assert by_r.keys() == by_m.keys()
    for uid in by_r:
        rr, rm = by_r[uid], by_m[uid]
        assert (rr.plan.point, rr.plan.bits, rr.plan.codec) == \
            (rm.plan.point, rm.plan.bits, rm.plan.codec)
        np.testing.assert_allclose(
            np.asarray(rr.logits, np.float32),
            np.asarray(rm.logits, np.float32), rtol=2e-4, atol=2e-5)
        # The simulated clock is the modeled one — real batching/sharding
        # must not change accounting semantics (the meshed engine's cloud
        # times differ by the mesh model, consistently on both sides of
        # each device's log).
        assert rm.breakdown.bytes_sent == rr.breakdown.bytes_sent
    print(f"OK fleet e2e [{arch}]: meshed == single-device fused "
          f"(float tol), groups={meshed.mesh_worker.group_sizes}")
    return srv, params, cfg


def check_generic_codec_path(srv, params, cfg, mesh, seq):
    """Non-bitpack codecs go down the stack-then-reshard path (decode via
    the codec's own batch path, ONE sharded tail forward)."""
    from repro.core.decoupler import DecoupledPlan
    from repro.serving.meshed import MeshedCloudWorker

    engine = srv.engine
    point = int(engine.plan_space.point_rows[0])
    plan = DecoupledPlan(point, 8, 0.0, 0.0, 0.0, codec="huffman")
    worker = MeshedCloudWorker(engine.model, params, mesh)
    runner = engine.make_runner(params, plan, mesh_worker=worker)
    plain = engine.make_runner(params, plan)
    pairs = [runner.edge_step(dict(make_batch(cfg, 1, seq, seed=7 + i)))
             for i in range(4)]
    blobs = [p[0] for p in pairs]
    extras = [p[1] for p in pairs]
    outs = runner.cloud_step_batch(blobs, extras)
    refs = plain.cloud_step_batch(blobs, extras, fuse_tail=True)
    assert worker.fused_calls == 1
    for a, b in zip(outs, refs):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-5)
    print("OK generic codec path: huffman group sharded, float-close")


def main():
    mesh = make_host_mesh(model_axis=4)       # (2, 4) data x model
    check_constrain_regression(mesh)
    check_sharded_wire_decode(mesh)
    # Transformer boundary (extras: positions tree) + CNN boundary
    # (extras-free); granite-34b is the ISSUE's named large config, served
    # at reduced dims (same family/topology) — full-geometry HBM/flops
    # gates are the AOT checks in benchmarks/meshed_tail.py.
    srv, params, cfg = check_fleet_e2e("granite-34b", 16, mesh)
    check_generic_codec_path(srv, params, cfg, mesh, 16)
    check_fleet_e2e("resnet50", 16, make_host_mesh(model_axis=2))
    print("ALL OK")


if __name__ == "__main__":
    main()
