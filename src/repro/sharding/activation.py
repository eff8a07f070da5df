"""Activation sharding constraints from logical axis names.

XLA's sharding propagation alone can lose the batch ("data") sharding of
activations in deep unrolled graphs — it then happily replicates the whole
batch on every device and "parallelizes" only over the model axis (observed
as a 14x FLOP blow-up in the olmo-1b dry-run). MaxText-style explicit
``with_sharding_constraint`` on the layer-boundary activations pins the
intended layout.

``constrain`` is a no-op outside a ``with jax.set_mesh(mesh):`` context,
so model code can call it unconditionally (CPU smoke tests see a single
device and no mesh).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AbstractMesh, NamedSharding

from repro.sharding.rules import resolve_spec


def _ambient_mesh() -> Optional[AbstractMesh]:
    """The mesh of the innermost ``with jax.set_mesh(mesh):`` block (as
    the abstract mesh, which is also visible inside a trace), or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def constrain(x, logical: Sequence[Optional[str]]):
    """Pin ``x`` to the layout the rule table resolves for ``logical``."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    spec = resolve_spec(x.shape, logical, mesh)
    if not isinstance(x, jax.core.Tracer):
        # Eager: the concrete mesh places the committed result.
        spec = NamedSharding(jax.sharding.get_mesh(), spec)
    return jax.lax.with_sharding_constraint(x, spec)
