#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference put in the
program's place, computed in float32, one precision below the float64
geometry the configuration states, must come out not correct.

    python3 bench/control.py --workload t1t2.join --seeds 1 2 3

For each seed it has the cell's traffic driver generate its data at the
cell's own size and compute its plain reference's answer in float32,
hands that to the driver's comparison as the window's one answer, and
prints the numbers compared. It needs no chip and touches no program
code.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def control_checks(cell, seed: int, mode: str = "f32") -> dict:
    """The cell's checks with its driver's own reference, in ``mode``, as
    the window's one answer over the driver's own data."""
    from harness import mixes
    drv = mixes.make(cell.traffic["kind"], cell.config, cell.traffic,
                     seed)
    drv.geo = drv.data(seed)
    drv.results = [drv.reference(mode)]
    return drv.check()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    from harness import spec
    cell = spec.resolve(args.workload, BENCH.parent)
    for seed in args.seeds:
        checks = control_checks(cell, seed)
        correct = all(v <= lim for v, lim in checks.values())
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "mode": "f32", "correct": correct,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
