"""A whole run, chip look skipped, with the timed path broken underneath:
``correct`` has to come out false for each fault the cell can have, and
true for the program as it is."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

import bench.run  # noqa: F401  (puts the program's sources on the path)
from bench.tests import tiny

STREAM = "olmo1b-chat-steady"
FLEET = "resnet50-fleet-burst"


@pytest.mark.parametrize("name", [STREAM, FLEET])
def test_sound_run_is_correct(name):
    out = tiny.run(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"


def test_altered_token_is_caught(monkeypatch):
    """A token altered where the session selects it."""
    from repro.serving.streaming import TokenStreamSession

    select = TokenStreamSession._select_tokens

    def altered(self, slots, rows):
        toks_np, toks = select(self, slots, rows)
        return (toks_np + 1) % rows.shape[-1], (toks + 1) % rows.shape[-1]

    monkeypatch.setattr(TokenStreamSession, "_select_tokens", altered)
    out = tiny.run(STREAM)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_state_left_unchanged_is_caught(monkeypatch):
    """A decode step that returns its caches unchanged."""
    from repro.serving.streaming import TokenStreamSession

    monkeypatch.setattr(TokenStreamSession, "_masked_update",
                        staticmethod(lambda old, new, mj: old))
    out = tiny.run(STREAM, seconds=2.0)
    assert not out["correct"], out["checks"]


def test_altered_wire_bytes_are_caught(monkeypatch):
    """A boundary altered on its way through the wire."""
    from repro.codec import bitpack

    frame = bitpack._frame

    def flipped(flat, n, bits):
        raw = bytearray(frame(flat, n, bits))
        raw[0] ^= 0x80
        return bytes(raw)

    monkeypatch.setattr(bitpack, "_frame", flipped)
    out = tiny.run(STREAM)
    assert not out["correct"]
    assert out["checks"]["wire_mismatches"]["value"] > 0


def test_altered_answer_is_caught(monkeypatch):
    """A request's logits altered where the cloud produces them."""
    from repro.core.decoupler import DecoupledRunner

    step = DecoupledRunner.cloud_step_batch

    def altered(self, *a, **kw):
        return [x + 0.1 * jnp.abs(x).max() for x in step(self, *a, **kw)]

    monkeypatch.setattr(DecoupledRunner, "cloud_step_batch", altered)
    out = tiny.run(FLEET)
    assert not out["correct"]
    assert out["checks"]["logits_rel_l2"]["value"] > \
        out["checks"]["logits_rel_l2"]["limit"]


def test_missing_samples_fail():
    """A run that finished nothing to compare is not correct."""
    from bench.systems.common import Check

    assert not Check("logit_gap", float("inf"), 1.0).ok
    assert not Check("logit_gap", float(np.nan), 1.0).ok
