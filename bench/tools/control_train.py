#!/usr/bin/env python3
"""Readings that set a training cell's limits: the program's numbers
against the sequential fp32 reference, and each control's in the
program's place: the reference with every product in int8 (one precision
below the bfloat16 the configuration states), fed rank 0's shard alone
(the exchange left out), and fed half of each batch.

  python3 bench/tools/control_train.py --workload stablelm-dp4 --seeds 1,2,3 \
      [--seconds S]

Each seed runs the cell as the benchmark does (for ``run_seconds`` unless
``--seconds`` says otherwise: the checked steps run before the window, so
its length does not move them) and judges every set of numbers by the
driver's own ``correct`` against the configuration's limits.  It exits
non-zero if the program fails or any control passes on any seed.  The
benchmark's own runs never run this.
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
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args()
    from bench import harness
    cell = harness.Cell(args.workload, ROOT)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache(ROOT)
    drv = cell.driver
    seconds = args.seconds or float(cell.spec["run_seconds"])
    limits = cell.config["limits"]
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        out = drv.run(cell, seed, seconds, False, ROOT, time.perf_counter(),
                      control=True)
        row = {"seed": seed,
               "program": {k: v["value"] for k, v in out["checks"].items()},
               "program_correct": drv.correct(out["checks"])}
        bad += not row["program_correct"]
        for name, got in out["control"].items():
            ok = drv.correct(drv.checks_of(got, limits))
            row[name] = got
            row[name + "_correct"] = ok
            bad += ok
        print(json.dumps(row), flush=True)
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
