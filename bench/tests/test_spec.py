"""BENCHMARK.json: every entry resolves to its files by name, and names,
units and keys stay inside what the benchmark's format allows."""
from __future__ import annotations

import copy
import json
import re
import shutil

import pytest

from bench import spec, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_and_units():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
                assert e["source"] in SOURCES
    for metric_group in (True, False):
        group = [n for g, n in names if g == metric_group]
        assert len(group) == len(set(group))


def test_configs_resolve():
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        cfg = spec.load_json(spec.ROOT / c["file"])
        assert cfg["name"] == c["name"]
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert (spec.BENCH_DIR / "systems" / f"{cfg['system']}.py").exists()
        assert (spec.BENCH_DIR / "reference"
                / f"{cfg['reference']}.py").exists()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = spec.resolve(cell)
    assert c.chips in (1, 4)
    assert spec.traffic_path(c.traffic_name).exists()
    assert spec.load_part("drivers", c.traffic["driver"]).drive
    if "arrivals" in c.traffic:
        assert spec.load_part("arrivals", c.traffic["arrivals"]["process"])
    e2e = [m.name for m in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    for name in e2e:
        assert spec.load_part("end_to_end", name).compute
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m.moves in e2e, (m.name, m.moves)


def test_closed_loop_cell_added_as_files_only(tmp_path):
    """A later cell whose clients each wait for their reply is a traffic
    file and an entry: it resolves, its driver is found by its name, and a
    whole run of it (at a size the CPU holds, chip look skipped) is
    correct."""
    mix = {"driver": "closed_loop", "clients": 3, "pool": 8,
           "prompt": {"dist": "uniform", "min": 16, "max": 32,
                      "round_up": 16},
           "output": {"dist": "uniform", "min": 4, "max": 8}}
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "traffic" / "ide_closed.json").write_text(
        json.dumps(mix))
    entry = next(c for c in BENCH["configs"] if c["name"] == "olmo-1b")
    (tmp_path / entry["file"]).parent.mkdir(parents=True)
    shutil.copy(spec.ROOT / entry["file"], tmp_path / entry["file"])
    bench = copy.deepcopy(BENCH)
    bench["workloads"].append({
        "name": "olmo1b-ide-closed", "config": "olmo-1b",
        "traffic": "ide_closed", "chips": 1,
        "why": "three clients, each waiting for its reply"})
    for m in bench["end_to_end"]:
        if m["name"] in ("itl_p95_ms", "tokens_per_s"):
            m["workloads"].append("olmo1b-ide-closed")
    cell = spec.resolve("olmo1b-ide-closed", bench, root=tmp_path)
    assert cell.traffic == mix
    sched = traffic.generate(cell.traffic, 2.0)
    assert sched.driver == "closed_loop" and len(sched.requests) == 8

    from bench.tests import tiny

    out = tiny.run("olmo1b-ide-closed", bench=bench, root=tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 3          # the clients sent again
    assert out["metrics"]["tokens_per_s"]["value"] > 0


def test_metric_keys():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_found_by_name(metric):
    assert callable(spec.load_reader(metric))


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        spec.resolve("no-such-cell")
