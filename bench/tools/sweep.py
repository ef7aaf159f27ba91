#!/usr/bin/env python3
"""Find the knee of a serving cell: run its mix at each offered rate, one
window per rate in this one process (the engine is built and warmed once),
and print per rate what was offered, what completed and the tails.

  python3 bench/tools/sweep.py --workload nemo-chat --rates 1.2,1.6,2.0 \
      --seed 7 [--greedy-shares 0,0.1]

Each window lasts the benchmark's ``run_seconds``; the k-th rate's
traffic is drawn from seed + k.  A rate is sustained when the tokens
completed keep up with those offered, the requests waiting at the
window's close do not pile up, and nothing is refused.
``--greedy-shares`` runs each rate once per share of greedy requests
given, in place of the mix's, on the same traffic seed, to see what the
greedy requests change.  Run it on the chip; its numbers are not the benchmark's, which runs one
fixed rate per cell.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--greedy-shares", default=None)
    args = ap.parse_args()
    from bench import harness, traffic
    cell = harness.Cell(args.workload, ROOT)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache(ROOT)
    drv = cell.driver
    seconds = float(cell.spec["run_seconds"])
    session, engine = drv.build(cell.config, args.seed)
    vocab = session.model.vocab_size
    drv.warm_up(engine, cell.traffic, vocab, args.seed)
    shares = ([float(x) for x in args.greedy_shares.split(",")]
              if args.greedy_shares else [cell.traffic.get("greedy_share")])
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        for share in shares:
            mix = copy.deepcopy(cell.traffic)
            mix["arrival"]["rate_per_s"] = rate
            mix["greedy_share"] = share
            reqs = traffic.generate(mix, seconds, args.seed + k, vocab)
            w = drv.open_loop(engine, mix, reqs, seconds)
            out = drv.summarize(w, engine)
            print(json.dumps({"rate_per_s": rate, "seed": args.seed + k,
                              "greedy_share": share, **out["metrics"],
                              "failed": out["failed"], **out["notes"]}),
                  flush=True)
            engine.metrics.reset()


if __name__ == "__main__":
    main()
