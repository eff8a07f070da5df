"""Finds everything a cell needs by name, from BENCHMARK.json.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix. The configuration is ``bench/configs/<config>.json`` (its file is
the one BENCHMARK.json gives); it names its served path,
``bench/systems/<system>.py``, and its reference,
``bench/reference/<reference>.py``. The traffic mix is
``bench/traffic/<traffic>.json``; it names its driver,
``bench/drivers/<driver>.py``, and its arrival process,
``bench/arrivals/<process>.py``. Each end-to-end metric is computed by
``bench/end_to_end/<metric>.py`` and each per-layer metric read by
``bench/metrics/<metric>.py``. Adding a cell, a configuration, a traffic
mix, a driver or a metric adds files and entries; no code here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
BENCHMARK_JSON = ROOT / "BENCHMARK.json"


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    bound: Optional[float] = None
    layer: Optional[str] = None
    moves: Optional[str] = None
    workloads: Optional[List[str]] = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_benchmark(path: Path = BENCHMARK_JSON) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def traffic_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "traffic" / f"{name}.json"


def part_path(kind: str, name: str) -> Path:
    """The file of a part found by name: ``bench/<kind>/<name>.py``."""
    return BENCH_DIR / kind / f"{name}.py"


def resolve(workload: str, bench: Optional[Dict[str, Any]] = None,
            root: Path = ROOT) -> Cell:
    """The cell named ``workload``, with its configuration, its traffic
    mix and the metrics it reports, the files found under ``root``.
    Raises KeyError for an unknown name."""
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(traffic_path(w["traffic"], root))
    e2e = [Metric(**m) for m in bench["end_to_end"]]
    per_layer = [Metric(**m) for m in bench["per_layer"]]
    return Cell(
        name=workload, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in e2e if m.applies_to(workload)],
        per_layer=[m for m in per_layer if m.applies_to(workload)])


def load_part(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` (a name may hold dots).
    Raises KeyError where there is no such file."""
    path = part_path(kind, name)
    if not path.exists():
        raise KeyError(f"no {kind} named {name!r} ({path} is missing)")
    modname = f"bench_{kind}_" + re.sub(r"[^A-Za-z0-9_]", "_", name)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str) -> Callable[[Any], Optional[float]]:
    """The per-layer metric reader ``bench/metrics/<name>.py``: its
    ``read(run)`` returns the metric's value, or None where the run holds
    nothing to read."""
    return load_part("metrics", name).read
