"""The traffic generator: seeded, same work for every seed."""

import numpy as np

from bench import generate
from bench.tests.conftest import TINY_CHAT

CHAT = {"arrivals": {"process": "mmpp", "rate_rps": 20.0,
                     "burst_factor": 3.0, "burst_share": 0.1,
                     "burst_len": 8},
        "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                       "min": 16, "max": 1536},
        "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                       "min": 16, "max": 512}}
BIG_SEED = 2 ** 31 + 12345


def _key(reqs):
    return [(r.prompt.tolist(), r.max_new_tokens, r.arrival_s) for r in reqs]


def test_same_seed_same_trace():
    a = generate.requests(CHAT, BIG_SEED, 30, 49152)
    b = generate.requests(CHAT, BIG_SEED, 30, 49152)
    assert _key(a) == _key(b)


def test_seeds_permute_the_same_work():
    a = generate.requests(CHAT, 1, 30, 49152)
    b = generate.requests(CHAT, 2 ** 33 + 7, 30, 49152)
    assert len(a) == len(b) == 600
    assert _key(a) != _key(b)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    for reqs in (a, b):
        t = np.array([r.arrival_s for r in reqs])
        assert t[0] == 0 and np.all(np.diff(t) >= 0)
        assert 25 < t[-1] <= 30


def test_lengths_heavy_tailed_and_clipped():
    n = 600
    p = generate.length_quantiles(CHAT["prompt_len"], n)
    assert p.min() >= 16 and p.max() <= 1536
    assert abs(np.median(p) - 256) <= 2
    assert p.mean() > np.median(p) * 1.2       # right tail


def test_bursts_come_at_three_times_the_rate():
    rng = generate.rng_for(3, "arrivals")
    gaps = generate.arrival_gaps(CHAT["arrivals"], 600, 30.0, rng)
    assert len(gaps) == 600 and abs(gaps.sum() - 30.0) < 1e-9
    base = (540 + 60 / 3) / 30.0
    small = np.sort(gaps)[:60].mean()
    assert small < 1 / base                    # burst gaps are shorter


def test_tiny_mix_is_valid():
    reqs = generate.requests(TINY_CHAT, 9, 2, 256)
    assert all(1 <= r.prompt.min() and r.prompt.max() < 256 for r in reqs)
