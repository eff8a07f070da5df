"""Logical-axis -> mesh-axis resolution (MaxText-style, shape-aware).

Every parameter/activation dimension carries a *logical* axis name (set in
the ParamSpec trees and the cache/batch annotators below). A rule table
maps logical names to an ordered list of candidate mesh-axis tuples; the
resolver assigns, per array, the first candidate that

  (a) divides the dimension size evenly, and
  (b) uses only mesh axes not already claimed by another dim of this array,

visiting dims in a fixed priority order (experts before heads before ffn
before sequence, batch first among activation dims). This makes one rule
table work across all 10 architectures x 4 input shapes x both meshes:
e.g. yi-6b's 4 KV heads can't shard 16-way on "model", so its KV cache
sequence dim picks up the "model" axis instead; grok-1's 8 experts don't
divide 16, so its expert FFN dim shards instead.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisCandidates = List[Tuple[str, ...]]

# Ordered preference of mesh axes per logical axis name. Large weight dims
# prefer fully-sharded ("data", "model") — FSDP over the data axis composed
# with tensor parallelism — and fall back to model-only / data-only when the
# dim size doesn't divide (the resolver checks divisibility per array).
DEFAULT_RULES: Dict[str, AxisCandidates] = {
    # activations
    "batch": [("pod", "data"), ("data",), ("pod",)],
    "seq": [],
    "kv_seq": [("data", "model"), ("model",), ("data",)],
    "enc_seq": [],
    # weights
    "vocab": [("data", "model"), ("model",), ("data",)],
    "embed": [],
    "embed_out": [],
    "ffn": [("data", "model"), ("model",), ("data",)],
    "heads": [("model",), ("data",)],
    "kv_heads": [("model",)],
    "head_dim": [],
    "expert": [("data", "model"), ("model",), ("data",)],
    "expert_in": [],
    "ssm_in": [("data", "model"), ("model",), ("data",)],
    "ssm_qk": [("model",)],
    "ssm_state": [],
    "conv_out": [("model",), ("data",)],
    "conv_in": [],
    "layers": [],
}

# Which dim gets first claim on a mesh axis within one array.
PRIORITY = [
    "batch", "expert", "heads", "kv_heads", "ffn", "ssm_in", "ssm_qk",
    "vocab", "conv_out", "kv_seq", "embed", "head_dim", "seq", "enc_seq",
]


def _priority(name: Optional[str]) -> int:
    if name is None:
        return len(PRIORITY) + 1
    try:
        return PRIORITY.index(name)
    except ValueError:
        return len(PRIORITY)


def resolve_spec(
    shape: Sequence[int],
    logical: Sequence[Optional[str]],
    mesh: Mesh,
    rules: Optional[Dict[str, AxisCandidates]] = None,
) -> P:
    """Resolve one array's PartitionSpec from its logical axes."""
    rules = rules if rules is not None else DEFAULT_RULES
    axis_sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    assignment: List[Optional[Tuple[str, ...]]] = [None] * len(shape)
    used: set = set()
    order = sorted(range(len(shape)), key=lambda i: _priority(logical[i]))
    for i in order:
        name = logical[i]
        if name is None:
            continue
        for cand in rules.get(name, []):
            if not all(a in axis_sizes for a in cand):
                continue
            prod = int(np.prod([axis_sizes[a] for a in cand]))
            if shape[i] % prod:
                continue
            if any(a in used for a in cand):
                continue
            assignment[i] = cand
            used.update(cand)
            break
    # Trim trailing Nones for a tidy spec.
    spec = [a if a is None else (a[0] if len(a) == 1 else a)
            for a in assignment]
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def shardings_for_specs(specs_tree, logical_tree, mesh: Mesh,
                        rules=None):
    """NamedSharding tree for a (ShapeDtypeStruct, logical-axes) tree pair."""
    return jax.tree.map(
        lambda s, l: NamedSharding(
            mesh, resolve_spec(s.shape, l, mesh, rules)
        ),
        specs_tree,
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )
