"""One generator for every serving mix, read from a traffic file's numbers.

Every seed gets the same work.  A run's arrivals fall in the mix's
warm-up, the measured window and a short tail, and each of those is cut
into blocks of about ``BLOCK_S`` seconds.  Each block's sizes and gaps are
one fixed draw (``SIZES_SEED``) from the stated distributions, its gaps
scaled to fill the block exactly, so each block holds the same requests
for every seed.  The run seed only orders them within their block, picks
which decode greedily, and draws the prompt tokens and the sampling seeds.
So runs with different seeds differ in order and content, not in the work
that any few seconds of the run offer.

A mix file holds (all but ``arrival`` and the lengths optional):

  arrival       {"process": "poisson", "rate_per_s": r}
  prompt_len    {"dist": "lognormal", "median": m, "sigma": s,
                 "min": a, "max": b}
  output_len    the same
  sampling      {"temperature": t, "top_p": p, "top_k": k}
  greedy_share  share of requests that decode greedily (the ones the
                correctness check reads)
  warmup_s      seconds the generator runs before the window opens
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

#: the fixed draw of sizes and gaps that every run seed shares
SIZES_SEED = 20240607
#: seconds of arrivals after the window, so the window's last requests
#: share the engine with later ones
TAIL_S = 5.0
#: about how many seconds of arrivals hold the same requests for every seed
BLOCK_S = 5.0


@dataclass
class Request:
    due_s: float                  # seconds after the generator starts
    prompt: List[int]
    max_new: int
    greedy: bool
    sample_seed: int


def draw_lengths(dist: dict, n: int, rng) -> np.ndarray:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    x = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(n))
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(np.int64)


def draw_gaps(arrival: dict, n: int, rng) -> np.ndarray:
    if arrival["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    return rng.exponential(1.0 / float(arrival["rate_per_s"]), n)


def segments(mix: dict, seconds: float):
    """(start, length, request count) of each block of the warm-up, the
    window and the tail."""
    rate = float(mix["arrival"]["rate_per_s"])
    warm = float(mix.get("warmup_s", 0.0))
    out = []
    # each part starts exactly where the driver opens and closes the window
    for begin, span in ((0.0, warm), (warm, seconds),
                        (warm + seconds, TAIL_S)):
        k = max(1, int(round(span / BLOCK_S)))
        n = int(round(span / k * rate))
        if n:
            out.extend((begin + j * span / k, span / k, n) for j in range(k))
    return out


def request_count(mix: dict, seconds: float) -> int:
    return sum(n for _, _, n in segments(mix, seconds))


def sizes(mix: dict, n: int, salt: int = 0):
    """(gaps, prompt lengths, output lengths) of n requests: the same for
    every run seed."""
    rng = np.random.default_rng([SIZES_SEED, salt])
    gaps = draw_gaps(mix["arrival"], n, rng)
    plen = draw_lengths(mix["prompt_len"], n, rng)
    olen = draw_lengths(mix["output_len"], n, rng)
    return gaps, plen, olen


def generate(mix: dict, seconds: float, seed: int,
             vocab: int) -> List[Request]:
    """The run's requests, in order of arrival."""
    rng = np.random.default_rng(int(seed))
    share = float(mix.get("greedy_share", 0.0))
    out = []
    for k, (start, span, n) in enumerate(segments(mix, seconds)):
        gaps, plen, olen = sizes(mix, n, salt=k)
        gaps = gaps * (span / gaps.sum())
        order = rng.permutation(n)
        gaps, plen, olen = gaps[rng.permutation(n)], plen[order], olen[order]
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        greedy = np.zeros(n, bool)
        greedy[rng.choice(n, int(round(share * n)), replace=False)] = True
        seeds = rng.integers(0, 2**31 - 1, n)
        for i in range(n):
            prompt = rng.integers(0, vocab, int(plen[i])).tolist()
            out.append(Request(float(due[i]), prompt, int(olen[i]),
                               bool(greedy[i]), int(seeds[i])))
    return out
