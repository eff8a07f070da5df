"""The control: the plain reference in the nearest precision below the
configuration's, put in the program's place, has to come out not
correct under the cell's own limits (here at a size the CPU holds; on
the chip at the cell's size, by ``python -m bench.limits``)."""
from __future__ import annotations

import pytest

import bench.run  # noqa: F401  (puts the program's sources on the path)
from bench import harness, limits
from bench.tests import tiny


@pytest.mark.parametrize("name", ["olmo1b-chat-steady",
                                  "resnet50-fleet-burst"])
def test_control_is_not_correct(name):
    from bench import run as bench_run

    cell = tiny.cell(name)
    system, rec, schedule, _, _ = bench_run.prepare(
        cell, 2**34 + 5, 1.5, False, require_chip=False,
        peaks=bench_run.peaks_for("TPU v5 lite"))
    system.start(False)
    harness.run_window(system, schedule, 1.5, rec)
    system.stop()
    uids = system.sample()
    assert uids
    system.release(uids)
    readings = limits.control(system, uids)
    lim = cell.config["limits"]
    assert any(v > lim[k] for k, v in readings.items()), (readings, lim)
