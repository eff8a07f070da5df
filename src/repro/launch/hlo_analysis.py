"""Compiled-HLO analysis for the roofline report.

Extracts the three roofline terms from a lowered+compiled step:

  compute term    = FLOPs / peak            (cost_analysis; per-device after
                                             SPMD partitioning — verified:
                                             equals global/chips)
  memory term     = bytes_accessed / HBM_bw (cost_analysis, per-device)
  collective term = wire_bytes / ICI_bw     (parsed from the compiled HLO)

Wire bytes use the standard ring-algorithm cost per device:
  all-gather       out_bytes  * (g-1)/g
  reduce-scatter   in_bytes   * (g-1)/g
  all-reduce       2 * bytes  * (g-1)/g
  all-to-all       bytes      * (g-1)/g
  collective-permute  bytes
where g is the replica-group size parsed from the op.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from repro.config.types import TPU_V5E, TPU_V5E_HBM_BW, TPU_V5E_ICI_BW

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(
    r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]"
)
_COLL_RE = re.compile(
    r"=\s*\(?[a-z0-9\[\],{}\s]*\)?\s*"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\("
)
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


@dataclass
class CollectiveOp:
    kind: str
    out_bytes: int
    in_bytes: int
    group_size: int
    wire_bytes: float


@dataclass
class CollectiveStats:
    ops: List[CollectiveOp] = field(default_factory=list)

    @property
    def total_wire_bytes(self) -> float:
        return sum(o.wire_bytes for o in self.ops)

    def by_kind(self) -> Dict[str, Tuple[int, float]]:
        out: Dict[str, Tuple[int, float]] = {}
        for o in self.ops:
            cnt, byt = out.get(o.kind, (0, 0.0))
            out[o.kind] = (cnt + 1, byt + o.wire_bytes)
        return out


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Scan compiled (post-SPMD) HLO for collective ops and estimate the
    per-device wire traffic of each."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1).replace("-start", "")
        shapes = _SHAPE_RE.findall(line)
        if not shapes:
            continue
        # First shape = output (or the tuple elements of the output);
        # shapes after the opcode's '(' are operands.
        head = line[: m.end()]
        out_shapes = _SHAPE_RE.findall(head)
        in_shapes = shapes[len(out_shapes):]
        out_bytes = sum(_shape_bytes(d, s) for d, s in out_shapes)
        in_bytes = sum(_shape_bytes(d, s) for d, s in in_shapes) or out_bytes
        g = _group_size(line)
        frac = (g - 1) / g if g > 1 else 0.0
        if kind == "all-gather":
            wire = out_bytes * frac
        elif kind == "reduce-scatter":
            wire = in_bytes * frac
        elif kind == "all-reduce":
            wire = 2.0 * in_bytes * frac
        elif kind == "all-to-all":
            wire = in_bytes * frac
        else:  # collective-permute
            wire = float(in_bytes)
        stats.ops.append(CollectiveOp(kind, out_bytes, in_bytes, g, wire))
    return stats


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2  # unknown grouping: conservative minimum


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # raw measurements (per device, post-SPMD)
    flops: float
    bytes_accessed: float
    wire_bytes: float
    collectives: Dict[str, Tuple[int, float]]
    # memory_analysis
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    # analytic references
    model_flops_global: float
    analytic_flops_global: float = 0.0
    # roofline terms in seconds
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0

    def __post_init__(self):
        # Compute term from the analytic matmul count when available (HLO
        # flops undercount rolled attention-chunk scan bodies and include
        # non-MXU elementwise work); memory/collective from the artifact.
        flops_per_dev = (
            self.analytic_flops_global / self.chips
            if self.analytic_flops_global
            else self.flops
        )
        self.compute_s = flops_per_dev / TPU_V5E.flops
        self.memory_s = self.bytes_accessed / TPU_V5E_HBM_BW
        self.collective_s = self.wire_bytes / TPU_V5E_ICI_BW

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (global). Catches remat/redundancy."""
        hlo_global = self.flops * self.chips
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    @property
    def hbm_bytes_per_device(self) -> int:
        return self.argument_bytes + self.output_bytes + self.temp_bytes

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_device": self.flops,
            "bytes_accessed_per_device": self.bytes_accessed,
            "wire_bytes_per_device": self.wire_bytes,
            "collectives": {k: list(v) for k, v in self.collectives.items()},
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "model_flops_global": self.model_flops_global,
            "analytic_flops_global": self.analytic_flops_global,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "useful_flops_fraction": self.useful_flops_fraction,
            "hbm_gib_per_device": self.hbm_bytes_per_device / 2**30,
        }


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a dict (empty if XLA produced no
    analysis)."""
    return compiled.cost_analysis() or {}


def analyze_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                     chips: int, model_flops_global: float,
                     analytic_flops_global: float = 0.0) -> RooflineReport:
    ca = cost_analysis_dict(compiled)
    ma = compiled.memory_analysis()
    coll = parse_collectives(compiled.as_text())
    return RooflineReport(
        analytic_flops_global=analytic_flops_global,
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        chips=chips,
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        wire_bytes=coll.total_wire_bytes,
        collectives=coll.by_kind(),
        argument_bytes=getattr(ma, "argument_size_in_bytes", 0),
        output_bytes=getattr(ma, "output_size_in_bytes", 0),
        temp_bytes=getattr(ma, "temp_size_in_bytes", 0),
        model_flops_global=model_flops_global,
    )
