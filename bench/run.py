#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name from ``BENCHMARK.json`` at the root of the checkout.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of a few seconds inside the window gives its
per-layer metrics instead.  Every run checks what the timed path produced
against the plain reference (``bench/reference``) and prints each number
compared beside its limit, on standard error and under ``checks``.

Without the TPU chips the cell asks for, the run exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, bench_dir: Path = None,
             require_chip: bool = True, t_start: float = T_START) -> dict:
    """One run of one cell; returns the result line as a dict.  Tests pass
    ``require_chip=False`` to drive the rest of a run on the CPU, and a
    ``root`` and ``bench_dir`` of their own."""
    from bench import harness
    cell = harness.Cell(workload, root, bench_dir or harness.BENCH_DIR)
    if require_chip:
        harness.require_chips(cell.chips)
    harness.enable_compile_cache(root)
    out = cell.driver.run(cell, seed, seconds, trace, root, t_start)
    checks = out["checks"]
    harness.print_checks(checks)
    if trace:
        metrics = cell.read_layers(out["layer_ctx"])
    else:
        names = [m["name"] for m in cell.end_to_end()]
        metrics = {n: {"value": out["metrics"][n],
                       "unit": next(m["unit"] for m in cell.end_to_end()
                                    if m["name"] == n)} for n in names}
    result = {"correct": bool(cell.driver.correct(checks)),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": out["device"]}
    if trace and out.get("layer_ctx"):
        from bench import trace as tr_mod
        result["breakdown"] = tr_mod.breakdown(out["layer_ctx"]["trace"])
    result["checks"] = checks
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
