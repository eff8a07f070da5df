"""Meshed cloud tail: the sharded batched decode+forward path of
``serving.meshed.MeshedCloudWorker`` and its float-equivalence contract.

Two layers of coverage:

* In-process (this interpreter has ONE device): the fused-tail contract
  (``fuse_tail=True`` is float-level equivalent to per-request
  ``cloud_step`` — the tolerance pin referenced from
  ``DecoupledRunner.cloud_step_batch``), the meshed worker on a 1x1 mesh
  against the plain runner, the sharded wire decode on a 1-device mesh,
  and the worker's fall-through conditions.

* Subprocess (``tests/meshed_subprocess.py`` under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``): the real
  8-device checks — constrain inside/outside a mesh, sharded decode
  across devices, granite-34b + resnet50 fleet e2e vs the single-device
  fused tail, the huffman generic path. XLA fixes the device count at
  import, so these cannot run in the tier-1 interpreter.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.codec import get_codec
from repro.config import JaladConfig, get_config
from repro.core.decoupler import DecoupledPlan
from repro.data.synthetic import make_batch
from repro.kernels.quantize.ops import dequantize_wire_batch_sharded
from repro.serving.edge_cloud import build_edge_cloud_server
from repro.serving.meshed import MeshedCloudWorker, aot_tail_report

RTOL, ATOL = 2e-4, 2e-5


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


@pytest.fixture(scope="module")
def served():
    cfg = get_config("granite-34b").reduced()
    jc = JaladConfig(bits_choices=(4, 8), codec_choices=("bitpack",),
                     accuracy_drop_budget=0.5, bandwidth_bytes_per_s=1e6)
    srv, params = build_edge_cloud_server(
        cfg, jc, calib_batches=1, calib_batch_size=2, seq_len=16)
    return srv, params, cfg


def _group(srv, params, cfg, n=4, codec="bitpack"):
    engine = srv.engine
    point = int(engine.plan_space.point_rows[0])
    plan = DecoupledPlan(point, 8, 0.0, 0.0, 0.0, codec=codec)
    runner = engine.make_runner(params, plan)
    pairs = [runner.edge_step(dict(make_batch(cfg, 1, 16, seed=40 + i)))
             for i in range(n)]
    return plan, runner, [p[0] for p in pairs], [p[1] for p in pairs]


def test_fused_tail_float_contract(served):
    """The contract named in DecoupledRunner.cloud_step_batch's docstring:
    fuse_tail=True is float-level equivalent (NOT bitwise — XLA re-blocks
    reductions per batch shape) to the per-request cloud_step."""
    srv, params, cfg = served
    plan, runner, blobs, extras = _group(srv, params, cfg)
    fused = runner.cloud_step_batch(blobs, extras, fuse_tail=True)
    for blob, e, out in zip(blobs, extras, fused):
        ref = runner.cloud_step(blob, e)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=RTOL, atol=ATOL)


def test_meshed_worker_single_device_matches_plain(served):
    """Sharded-vs-single-device contract at mesh size 1: the worker's
    fused decode+tail must match the plain per-request path float-close
    (and exercise the same code as the multi-device subprocess run)."""
    srv, params, cfg = served
    plan, runner, blobs, extras = _group(srv, params, cfg)
    worker = MeshedCloudWorker(srv.engine.model, params, _mesh1())
    meshed = srv.engine.make_runner(params, plan, mesh_worker=worker)
    outs = meshed.cloud_step_batch(blobs, extras)
    assert worker.fused_calls == 1 and worker.group_sizes == [len(blobs)]
    for blob, e, out in zip(blobs, extras, outs):
        ref = runner.cloud_step(blob, e)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=RTOL, atol=ATOL)


def test_meshed_worker_pads_non_dividing_groups(served):
    """Group sizes that do not divide the data axis are tiled-padded and
    the padding sliced off — results still match per-request."""
    srv, params, cfg = served
    plan, runner, blobs, extras = _group(srv, params, cfg, n=3)
    worker = MeshedCloudWorker(srv.engine.model, params, _mesh1())
    meshed = srv.engine.make_runner(params, plan, mesh_worker=worker)
    outs = meshed.cloud_step_batch(blobs, extras)
    assert [np.asarray(o).shape[0] for o in outs] == [1, 1, 1]
    for blob, e, out in zip(blobs, extras, outs):
        ref = runner.cloud_step(blob, e)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=RTOL, atol=ATOL)


def test_meshed_worker_declines_unshardable_groups(served):
    """Mixed codecs / cloud-only plans return None (the runner then falls
    back to the single-device path) instead of wrong fused results."""
    srv, params, cfg = served
    plan, _, blobs, extras = _group(srv, params, cfg)
    worker = MeshedCloudWorker(srv.engine.model, params, _mesh1())
    assert worker.try_cloud_step_batch([], [], plan) is None
    cloud_only = DecoupledPlan(-1, 0, 0.0, 0.0, 0.0)
    assert worker.try_cloud_step_batch(blobs, extras, cloud_only) is None
    import dataclasses
    mixed = [blobs[0], dataclasses.replace(blobs[1], codec="huffman")]
    assert worker.try_cloud_step_batch(mixed, extras[:2], plan) is None
    assert worker.fused_calls == 0


def test_sharded_wire_decode_identity():
    """dequantize_wire_batch_sharded is byte-identical to per-blob decode
    (here on a 1-device mesh; across 8 devices in the subprocess)."""
    codec = get_codec("bitpack")
    rng = np.random.default_rng(3)
    xs = [rng.normal(size=(2, 5, 9)).astype(np.float32) for _ in range(4)]
    blobs = [codec.encode(x, 6) for x in xs]
    codes = np.stack([codec._wire_codes(b) for b in blobs])
    mn = np.stack([np.float32(b.x_min) for b in blobs])
    mx = np.stack([np.float32(b.x_max) for b in blobs])
    out = dequantize_wire_batch_sharded(codes, mn, mx, 6, blobs[0].shape,
                                        _mesh1())
    for i, b in enumerate(blobs):
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(codec.decode(b)))


def test_aot_tail_report_single_device(served):
    """Compile-only analysis works without materializing params and
    reports coherent per-device numbers at mesh=None."""
    srv, _, _ = served
    point = int(srv.engine.plan_space.point_rows[0])
    rep = aot_tail_report(srv.engine.model, point, batch=2, seq_len=16)
    assert rep["n_devices"] == 1
    assert rep["flops_per_device"] > 0
    assert rep["argument_bytes_per_device"] > 0


def test_meshed_eight_device_subprocess():
    """The real multi-device contract. XLA pins the device count at
    import, so the 8-fake-device checks need their own interpreter."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tests",
                                      "meshed_subprocess.py")],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-4000:]}")
    assert "ALL OK" in proc.stdout


def test_sharded_init_creates_params_in_their_layout(served):
    """``Model.init(rng, shardings)`` creates every leaf directly in the
    worker's layout — a worker built on them has nothing left to move —
    with the values of the plain init."""
    srv, _, _ = served
    model = srv.engine.model
    mesh = _mesh1()
    from repro.serving.meshed import param_shardings

    shardings = param_shardings(model, mesh)
    sharded = model.init(jax.random.key(3), shardings)
    plain = model.init(jax.random.key(3))
    for got, want, sh in zip(jax.tree.leaves(sharded),
                             jax.tree.leaves(plain),
                             jax.tree.leaves(shardings)):
        assert got.sharding == sh
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-6, atol=1e-7)
    worker = MeshedCloudWorker(model, sharded, mesh)
    assert all(a is b for a, b in zip(jax.tree.leaves(worker.params),
                                      jax.tree.leaves(sharded)))


@pytest.mark.parametrize("arch", ["granite-34b", "resnet50"])
def test_head_params_hold_only_the_head(arch):
    """The edge's slice of the params runs the head bit-identically and
    holds no layer past the cut and no output head."""
    from repro.models.api import build_model

    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, 2, 8, seed=1)
    point = 0
    head = model.head_params(params, point)
    got = model.run_head(head, batch, point)
    want = model.run_head(params, batch, point)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    n_head = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(head))
    n_all = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n_head < n_all
    assert "lm_head" not in head


def test_meshed_runner_cloud_step_runs_on_the_mesh(served):
    """A meshed runner's single-blob cloud step (the path for groups the
    worker cannot batch-shard) runs the worker's sharded tail, so an edge
    holding only the head's slice still serves every request."""
    srv, params, cfg = served
    plan, runner, blobs, extras = _group(srv, params, cfg, n=2)
    model = srv.engine.model
    worker = MeshedCloudWorker(model, params, _mesh1())
    meshed = srv.engine.make_runner(
        params, plan, mesh_worker=worker,
        edge_params=model.head_params(params, plan.point))
    blob, e = meshed.edge_step(dict(make_batch(cfg, 1, 16, seed=40)))
    assert blob.payload == blobs[0].payload
    np.testing.assert_allclose(np.asarray(meshed.cloud_step(blob, e),
                                          np.float32),
                               np.asarray(runner.cloud_step(blob, e),
                                          np.float32),
                               rtol=RTOL, atol=ATOL)
    assert worker.fused_calls == 0


def test_meshed_fleet_edges_hold_only_the_head(served):
    """``FleetServer(cloud_mesh=...)`` hands its edge runners only
    ``Model.head_params`` and serves float-close to the single-device
    fused tail."""
    from repro.config.types import EDGE_TK1, EDGE_TX2
    from repro.serving.fleet import FleetRequest, FleetServer

    srv, params, cfg = served
    profiles = [EDGE_TX2, EDGE_TK1]

    def reqs():
        return [FleetRequest(uid=i, device_id=i % 2,
                             batch=dict(make_batch(cfg, 1, 16, seed=60 + i)),
                             bandwidth=3e5) for i in range(4)]

    ref = {r.uid: r for r in FleetServer(
        srv.engine, params, profiles, fuse_cloud_tail=True).serve(reqs())}
    fleet = FleetServer(srv.engine, params, profiles, cloud_mesh=_mesh1())
    done = fleet.serve(reqs())
    assert fleet.mesh_worker.fused_calls >= 1
    for r in done:
        np.testing.assert_allclose(np.asarray(r.logits, np.float32),
                                   np.asarray(ref[r.uid].logits, np.float32),
                                   rtol=RTOL, atol=ATOL)
    decoupled = [r.plan for r in done if not r.plan.is_cloud_only]
    assert decoupled
    edge = fleet.runners.get(decoupled[0]).edge_params
    assert "lm_head" not in edge
    n_edge = sum(a.size for a in jax.tree.leaves(edge))
    assert n_edge < sum(a.size for a in jax.tree.leaves(params))
