"""Traffic generator: one general reader of the data files in ``traffic/``.

Grown from the program's ``serve/sim.py`` (``poisson_requests``,
``bursty_requests``) and extended: lengths are heavy-tailed (lognormal,
clipped), arrivals are an open loop, steady Poisson or Markov-modulated
Poisson with bursts.  Nothing here imports the program's generator.

Every seed gets the same multiset of sizes and gaps, in another order:
prompt lengths, output lengths and inter-arrival gaps are the quantiles
of their distributions at ``(i + 0.5) / n``, and the seed permutes them
and places the bursts.  So two seeds offer the same work at the same
mean rate, and a run-to-run spread is the system's, not the draw's.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Arrival:
    """One request as the generator offers it (no program types)."""
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int
    arrival_s: float            # offset from the start of the window


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named stream of one seed (any int >= 0)."""
    words = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF,
             *stream.encode()]
    return np.random.default_rng(np.random.SeedSequence(words))


def length_quantiles(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``spec``.

    ``spec``: ``{"dist": "lognormal", "median", "sigma", "min", "max"}``
    or ``{"dist": "fixed", "value"}``."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(vals, spec["min"], spec["max"]).astype(np.int64)


def _exp_quantiles(n: int) -> np.ndarray:
    """Quantiles of Exp(1) at (i + 0.5) / n, rescaled to mean exactly 1."""
    if n == 0:
        return np.zeros(0)
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q / q.mean()


def arrival_gaps(spec: Dict, n: int, seconds: float,
                 rng: np.random.Generator) -> np.ndarray:
    """Gaps before each of ``n`` arrivals spanning ``seconds`` in all.

    ``{"process": "poisson"}`` or ``{"process": "mmpp", "burst_factor",
    "burst_share", "burst_len"}``: a ``burst_share`` of the requests come
    in episodes of ``burst_len`` at ``burst_factor`` times the base rate."""
    if spec["process"] == "poisson":
        return rng.permutation(_exp_quantiles(n)) * (seconds / n)
    if spec["process"] != "mmpp":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    blen, factor = int(spec["burst_len"]), float(spec["burst_factor"])
    episodes = int(round(spec["burst_share"] * n / blen))
    n_burst = episodes * blen
    n_base = n - n_burst
    # base rate such that the whole trace spans `seconds` at the mean rate
    base = (n_base + n_burst / factor) / seconds
    base_gaps = rng.permutation(_exp_quantiles(n_base)) / base
    burst_gaps = rng.permutation(_exp_quantiles(n_burst)) / (factor * base)
    # an episode starts after one of the base gaps, never two at one spot
    starts = set(rng.choice(n_base, size=episodes, replace=False).tolist())
    gaps: List[float] = []
    bi = 0
    for i, g in enumerate(base_gaps):
        gaps.append(float(g))
        if i in starts:
            gaps.extend(burst_gaps[bi:bi + blen].tolist())
            bi += blen
    return np.asarray(gaps)


def requests(mix: Dict, seed: int, seconds: float,
             vocab_size: int) -> List[Arrival]:
    """The open-loop trace of one serving mix for one seed and window."""
    n = max(1, int(round(mix["arrivals"]["rate_rps"] * seconds)))
    rng = rng_for(seed, "arrivals")
    gaps = arrival_gaps(mix["arrivals"], n, seconds, rng)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps[1:])])
    plens = rng_for(seed, "prompt_len").permutation(
        length_quantiles(mix["prompt_len"], n))
    olens = rng_for(seed, "output_len").permutation(
        length_quantiles(mix["output_len"], n))
    tok = rng_for(seed, "tokens")
    out = []
    for i in range(n):
        prompt = tok.integers(1, vocab_size, size=int(plens[i]),
                              dtype=np.int64).astype(np.int32)
        out.append(Arrival(prompt=prompt, max_new_tokens=int(olens[i]),
                           arrival_s=float(arrivals[i])))
    return out


def offered(mix: Dict, seconds: float) -> Dict[str, float]:
    """What a mix offers over a window, independent of the seed."""
    n = max(1, int(round(mix["arrivals"]["rate_rps"] * seconds)))
    return {"requests": n,
            "prompt_tokens": int(length_quantiles(mix["prompt_len"], n).sum()),
            "output_tokens": int(length_quantiles(mix["output_len"], n).sum()),
            "mean_rate_rps": n / seconds}

