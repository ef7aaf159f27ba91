"""The traffic generator: the same work for every seed, the stated
distributions, and a different order and content per seed."""
import statistics

import numpy as np
import pytest

from bench import traffic
from conftest import TINY_CHAT

CHAT = {"arrival": {"process": "poisson", "rate_per_s": 10.0},
        "prompt_len": {"dist": "lognormal", "median": 1024, "sigma": 1.0,
                       "min": 64, "max": 3584},
        "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                       "min": 16, "max": 448},
        "sampling": {"temperature": 0.8, "top_p": 0.95},
        "greedy_share": 0.25, "warmup_s": 8.0}


def test_same_seed_same_requests():
    a = traffic.generate(CHAT, 20, 2**33 + 5, vocab=1000)
    b = traffic.generate(CHAT, 20, 2**33 + 5, vocab=1000)
    assert [(r.due_s, r.prompt, r.max_new, r.greedy, r.sample_seed)
            for r in a] == [(r.due_s, r.prompt, r.max_new, r.greedy,
                             r.sample_seed) for r in b]


def test_seeds_share_sizes_not_order():
    a = traffic.generate(CHAT, 20, 1, vocab=1000)
    b = traffic.generate(CHAT, 20, 2, vocab=1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert sum(r.greedy for r in a) == sum(r.greedy for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[0].prompt[:8] != b[0].prompt[:8]


def test_count_covers_warmup_window_and_tail():
    n = traffic.request_count(CHAT, 20)
    assert n == (8 + 20 + 5) * 10
    reqs = traffic.generate(CHAT, 20, 3, vocab=1000)
    assert len(reqs) == n
    assert reqs[0].due_s == 0.0
    assert all(b.due_s >= a.due_s for a, b in zip(reqs, reqs[1:]))


def test_window_holds_the_same_work_for_every_seed():
    def window(seed):
        return [r for r in traffic.generate(CHAT, 20, seed, 1000)
                if 8.0 <= r.due_s < 28.0]
    a, b = window(11), window(12)
    assert len(a) == len(b) == 200
    assert sorted((len(r.prompt), r.max_new) for r in a) == \
        sorted((len(r.prompt), r.max_new) for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_stated_distributions():
    n = 20000
    gaps, plen, olen = traffic.sizes(CHAT, n)
    assert abs(np.mean(gaps) - 0.1) < 0.005               # Poisson, 10/s
    assert abs(np.std(gaps) / np.mean(gaps) - 1.0) < 0.03  # exponential
    assert plen.min() >= 64 and plen.max() <= 3584
    assert abs(statistics.median(plen) - 1024) < 40
    assert abs(statistics.median(olen) - 128) < 6
    # log-normal spread: the quartiles sit at median * exp(+-0.674 sigma)
    q1, q3 = np.percentile(plen, [25, 75])
    assert abs(np.log(q3 / q1) / (2 * 0.674) - 1.0) < 0.05
    # each block decodes its share greedily, rounded
    want = sum(round(0.25 * n) for _, _, n in traffic.segments(CHAT, 20))
    assert sum(r.greedy for r in traffic.generate(CHAT, 20, 4, 1000)) == want
    assert abs(want / traffic.request_count(CHAT, 20) - 0.25) < 0.02


def test_every_block_holds_the_same_work_for_every_seed():
    def blocks(seed):
        reqs = traffic.generate(CHAT, 20, seed, 1000)
        out, i = [], 0
        for start, span, n in traffic.segments(CHAT, 20):
            part = reqs[i:i + n]
            i += n
            assert all(start <= r.due_s < start + span for r in part)
            out.append((sorted((len(r.prompt), r.max_new) for r in part),
                        sum(r.greedy for r in part)))
        assert i == len(reqs)
        return out
    assert blocks(21) == blocks(2**33 + 22)
    # warm-up 8 s in 2 blocks, window 20 s in 4, tail 5 s in 1
    spans = [span for _, span, _ in traffic.segments(CHAT, 20)]
    assert spans == [4.0, 4.0, 5.0, 5.0, 5.0, 5.0, 5.0]


def test_unknown_processes_and_distributions_are_refused():
    with pytest.raises(ValueError, match="arrival process"):
        traffic.generate(dict(CHAT, arrival={"process": "gamma",
                                             "rate_per_s": 1.0, "cv": 3}),
                         5, 1, 100)
    with pytest.raises(ValueError, match="length distribution"):
        traffic.generate(dict(CHAT, prompt_len={"dist": "fixed",
                                                "value": 8}), 5, 1, 100)


def test_tiny_mix_is_valid():
    reqs = traffic.generate(TINY_CHAT, 2, 9, vocab=256)
    assert all(8 <= len(r.prompt) <= 60 and 2 <= r.max_new <= 24
               for r in reqs)
