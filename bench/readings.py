"""One run of a cell as ``bench.run`` makes it, then the readings the
limits of ``correct`` are set from, on the run's own sample after the
program's release: the control's (the reference in the nearest precision
below the configuration's, as ``bench.limits`` takes it) and, with
``--fault``, a planted fault's (each served token read as the next id;
one more pass of the reference). Not one of the benchmark's runs.

    python -m bench.readings --workload <cell> --seed <n> --seconds <s> [--fault]

``bench.limits`` keeps the program's weights beside the reference's, which
a cell whose two do not fit one chip together cannot do; this takes the
same readings a run at a time. It prints ``bench.run``'s lines, and one
``readings {...}`` line before the result line.
"""
from __future__ import annotations

import argparse
import json

from bench import limits, run


def with_readings(prepared, fault: bool):
    """``bench.run.prepare``'s result, its system's checks followed by the
    control's readings (and the fault's) on the same sample."""
    system = prepared[0]
    checks = system.checks

    def checked(uids):
        result = checks(uids)
        row = {"seed": system.seed, "sampled": len(uids),
               "program": {c.name: c.value for c in result},
               "control": limits.control(system, uids)}
        if fault:
            row["fault_token"] = system.compare(uids, shift=1)["logit_gap"]
        run.log("readings " + json.dumps(row))
        return result

    system.checks = checked
    return prepared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--fault", action="store_true")
    args, rest = ap.parse_known_args(argv)
    prepare = run.prepare
    run.prepare = lambda *a, **k: with_readings(prepare(*a, **k), args.fault)
    return run.main(rest)


if __name__ == "__main__":
    raise SystemExit(main())
