"""One-shot requests from a fleet of edge devices: ``FleetServer``
serving a CNN, each request's head and encode on the chip for its edge,
the cloud decoding each group of same-plan requests in one batch and
running their tails.

The window drives ``FleetServer.serve`` on every request due so far; a
request's result is ready when its logits are (``block_until_ready``).
Spans: ``serve`` (one serve call), ``plan`` (the fleet's fused
re-plan), ``edge`` / ``cloud`` (a runner's edge step and batched cloud
step), ``encode`` / ``decode`` (the codec). Counters: ``requests``,
``model_flops``.

The comparison that decides ``correct``, for a seeded sample of the
requests the window finished: their wire bytes against the oracle and
their decodes; each served boundary against the float32 reference's
head on the same image; each request's logits against the reference's
tail on the boundary its wire bytes carry (relative L2, the worst).
"""
from __future__ import annotations

import gc
from typing import Any, Dict, List

import numpy as np

from bench import oracle
from bench.harness import Recorder, clock
from bench.systems.common import (
    Check, CodecTap, model_config, reference, same_layout, seed_key)


def _profiles(names: List[str]):
    from repro.config import types

    return [getattr(types, n) for n in names]


class FleetSystem:
    def __init__(self, cfg: Dict[str, Any], seed: int, rec: Recorder):
        import jax

        from repro.codec import get_codec
        from repro.config import JaladConfig
        from repro.models.api import build_model
        from repro.serving.fleet import build_fleet_server

        self.cfg, self.seed, self.rec = cfg, seed, rec
        self.ref = reference(cfg)
        if cfg.get("matmul_precision"):
            # The precision the configuration states for its float32
            # matmuls and convolutions, for the whole process.
            jax.config.update("jax_default_matmul_precision",
                              cfg["matmul_precision"])
        mcfg = model_config(cfg)
        model = build_model(mcfg)
        key = seed_key(seed)
        params = self.ref.make_params(cfg, key)
        bad = same_layout(params, jax.eval_shape(model.init, key))
        if bad:
            raise RuntimeError(f"weights do not fit the program: {bad}")
        plan, dep = cfg["plan"], cfg["deployment"]
        self.bits = int(plan["bits"])
        self.point = int(plan["point"])
        self.bandwidth = float(dep["bandwidth_bytes_per_s"])
        jc = JaladConfig(bits_choices=(self.bits,),
                         codec_choices=(plan["codec"],),
                         accuracy_drop_budget=1.0,
                         bandwidth_bytes_per_s=self.bandwidth)
        profiles = _profiles(dep["edge_profiles"])
        self.n_dev = int(dep["edge_devices"])
        self.fleet, _ = build_fleet_server(
            mcfg, jc, [profiles[i % len(profiles)] for i in range(self.n_dev)],
            seed=seed, calib_batches=1, calib_batch_size=1,
            points=[self.point], params=params,
            cloud_batch=int(plan["cloud_batch"]))
        pool = int(dep["image_pool"])
        imgs = self.ref.make_images(cfg, seed_key(seed, 1), pool)
        self.images = [imgs[i:i + 1] for i in range(pool)]
        jax.block_until_ready(self.images)
        self.tap = CodecTap(get_codec(plan["codec"]), rec,
                            keep=int(cfg["check"]["encodes"]), seed=seed)
        self.pending: List[Any] = []
        self.served: Dict[int, Any] = {}
        self.image_of: Dict[int, int] = {}
        self.uid_of: Dict[int, int] = {}          # id(request batch) -> uid
        self.recording = False
        self._image_flops = self.ref.image_flops(cfg)
        self._instrument()

    # ----------------------------------------------------------- spans
    def _instrument(self) -> None:
        rec, ctl = self.rec, self.fleet.controller
        plans = ctl.current_plans

        def planned(*a, **kw):
            with rec.span("plan"):
                return plans(*a, **kw)

        ctl.current_plans = planned

    def _wrap_runners(self) -> None:
        rec = self.rec
        for runner in self.fleet.runners._cache.values():
            edge, cloud = runner.edge_step, runner.cloud_step_batch

            def edge_step(batch, _f=edge):
                self.tap.tag = self.uid_of.get(id(batch))
                with rec.span("edge"):
                    return _f(batch)

            def cloud_step_batch(*a, _f=cloud, **kw):
                with rec.span("cloud"):
                    return _f(*a, **kw)

            runner.edge_step = edge_step
            runner.cloud_step_batch = cloud_step_batch

    # ---------------------------------------------------------- driving
    def _request(self, uid: int):
        from repro.serving.fleet import FleetRequest

        img = uid % len(self.images)
        self.image_of[uid] = img
        batch = {"images": self.images[img]}
        self.uid_of[id(batch)] = uid
        return FleetRequest(uid=uid, device_id=uid % self.n_dev,
                            batch=batch, bandwidth=self.bandwidth)

    def submit(self, req, uid: int) -> None:
        self.pending.append(self._request(uid))

    def busy(self) -> bool:
        return bool(self.pending)

    def queued(self) -> int:
        return len(self.pending)

    def pump(self) -> None:
        reqs, self.pending = self.pending, []
        with self.rec.span("serve"):
            done = self.fleet.serve(reqs)
        for r in done:
            r.logits.block_until_ready()
            if self.recording:
                self.rec.finish(r.uid, clock())
                self.served[r.uid] = r.logits
        if self.recording:
            self.rec.count("requests", len(done))
            self.rec.count("model_flops", self._image_flops * len(done))

    def warm(self, schedule) -> None:
        """Every group size a serve call can form: 1 .. cloud_batch
        requests, and one call of more than a group."""
        g = self.fleet.cloud_batch
        uid = 1 << 30
        for n in list(range(1, g + 1)) + [g + 1]:
            self.pending = [self._request(uid + i) for i in range(n)]
            uid += n
            self.pump()
        self._wrap_runners()
        self.group_mark = len(self.fleet.cloud_groups)

    def start(self, trace: bool) -> None:
        self.tap.arm()
        self.recording = True
        self.group_mark = len(self.fleet.cloud_groups)

    def stop(self) -> None:
        self.recording = False
        self.tap.armed = False
        self.window_groups = self.fleet.cloud_groups[self.group_mark:]

    # ----------------------------------------------------------- checks
    def sample(self) -> List[int]:
        """The requests whose encodes the tap kept (a seeded choice) and
        that the window finished."""
        return sorted({e["tag"] for e in self.tap.kept
                       if e["tag"] in self.served})

    def collect(self, uids: List[int]) -> None:
        """Keep the sample's logits on the host."""
        self.logits = {u: np.asarray(self.served[u], np.float32)
                       for u in uids}

    def release(self, uids: List[int]) -> None:
        """Keep the sample's logits on the host; free the program."""
        self.collect(uids)
        self.tap.restore()
        self.fleet = None
        self.served = {}
        gc.collect()

    def reset(self) -> None:
        """Drop every request not yet served."""
        self.pending = []
        self.served = {}

    def reseed(self, seed: int) -> None:
        """Serve the same shapes with the weights and images of ``seed``:
        the fleet, its runner cache and each runner read the new tree."""
        self.reset()
        self.seed = seed
        fleet = self.fleet
        params = self.ref.make_params(self.cfg, seed_key(seed))
        fleet.params = fleet.runners.params = params
        for runner in fleet.runners._cache.values():
            runner.params = runner.edge_params = params
        imgs = self.ref.make_images(self.cfg, seed_key(seed, 1),
                                    len(self.images))
        self.images = [imgs[i:i + 1] for i in range(len(self.images))]
        self.tap.rng = np.random.default_rng(seed)

    def checks(self, uids: List[int]) -> List[Check]:
        lim = self.cfg["limits"]
        return self.tap.wire_checks(self.bits) + [
            Check(k, v, float(lim[k])) for k, v in self.compare(uids).items()]

    def compare(self, uids: List[int], **low):
        """Worst relative L2 distances over the sample, against the
        float32 reference: of each served boundary from the reference's
        head on the same image, and of each request's logits from the
        reference's tail on the boundary its wire bytes carry (which the
        wire check holds to the oracle's codes). Comparing the two halves
        apart keeps a rounding difference next to a code's edge from
        moving a whole code. Given ``low`` arguments of the reference's
        forward, a lower-precision reference stands in for the program."""
        import jax
        import jax.numpy as jnp

        if not uids:
            return {"boundary_rel_l2": float("inf"),
                    "logits_rel_l2": float("inf")}
        cfg, ref, cut = self.cfg, self.ref, self.point
        params = ref.make_params(cfg, seed_key(self.seed))
        imgs = ref.make_images(cfg, seed_key(self.seed, 1),
                               int(cfg["deployment"]["image_pool"]))
        kept = {e["tag"]: e for e in self.tap.kept}
        x = imgs[jnp.asarray([self.image_of[u] for u in uids])]
        wire = []
        for u in uids:
            blob = kept[u]["blobs"][0]
            n = int(np.prod(blob.shape))
            q = oracle.unpack(blob.payload, blob.bits, n)
            wire.append(oracle.dequantize(q, blob.x_min, blob.x_max,
                                          blob.bits).reshape(blob.shape))
        wire = jnp.asarray(np.concatenate(wire))

        def run(start, stop, inp, **kw):
            f = jax.jit(lambda p, v: ref.forward(cfg, p, v, start, stop,
                                                 **kw))
            return np.asarray(f(params, inp), np.float64)

        want_head = run(0, cut + 1, x)
        want_tail = run(cut + 1, None, wire)
        if low:
            got_head = run(0, cut + 1, x, **low)
            got_tail = run(cut + 1, None, wire, **low)
        else:
            got_head = np.concatenate([np.asarray(kept[u]["rows"][0],
                                                  np.float64) for u in uids])
            got_tail = np.concatenate([self.logits[u] for u in uids]
                                      ).astype(np.float64)
        return {"boundary_rel_l2": _worst_rel(got_head, want_head),
                "logits_rel_l2": _worst_rel(got_tail, want_tail)}


def _worst_rel(got: np.ndarray, want: np.ndarray) -> float:
    """Worst relative L2 distance over the leading (request) axis."""
    got = got.reshape(got.shape[0], -1)
    want = want.reshape(want.shape[0], -1)
    num = np.linalg.norm(got - want, axis=-1)
    den = np.maximum(np.linalg.norm(want, axis=-1), 1e-30)
    return float(np.max(num / den))


def build(cfg, seed, rec):
    return FleetSystem(cfg, seed, rec)
