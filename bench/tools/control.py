#!/usr/bin/env python3
"""Readings that set a serving cell's limits: the program's numbers against
the reference, and the control's (the reference in int8, one precision
below the bfloat16 the configuration states) on the same served positions.

  python3 bench/tools/control.py --workload nemo-chat --seeds 1,2,3

Each seed runs the cell as the benchmark does, at its own load and for
``run_seconds``, and judges both sets of numbers by the driver's own
``correct`` against the configuration's limits.  It exits non-zero if the program fails or the control passes on
any seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    from bench import harness
    cell = harness.Cell(args.workload, ROOT)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache(ROOT)
    drv = cell.driver
    seconds = float(cell.spec["run_seconds"])
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        out = drv.run(cell, seed, seconds, False, ROOT, time.perf_counter(),
                      control=True)
        ctrl = drv.checks_of(out["control"], cell.config["limits"],
                             out["checks"]["checked_tokens"]["value"])
        row = {"seed": seed,
               "program": {k: v["value"] for k, v in out["checks"].items()},
               "program_correct": drv.correct(out["checks"]),
               "control": {k: v["value"] for k, v in ctrl.items()},
               "control_correct": drv.correct(ctrl)}
        bad += row["control_correct"] or not row["program_correct"]
        print(json.dumps(row), flush=True)
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
