"""Serving window: the program's continuous engine under open-loop traffic.

Set-up makes the weights on the device from the seed, builds the engine
through ``repro.serve.make_engine("continuous", ...)`` and serves one
two-chunk request, which compiles every program the window runs (the
chunk step, the splice into the batch cache, the decode tick).  The
window is one ``engine.serve`` over the whole trace: arrivals span
``--seconds``, and the drain counts.  Afterwards a seeded sample of the
finished requests, the longest among them, is checked against the plain
reference: the widest gap by which a served token's reference logit lies
below the reference's best.
"""

from __future__ import annotations

import importlib
import math
from typing import Dict, List

import numpy as np

from bench import counters, generate

SERVED_TOKENS = 400     # served tokens the check covers, at the least


def p95(values: List[float]) -> float:
    """Nearest-rank 95th percentile (a missing value counts as +inf)."""
    vals = sorted(values)
    return vals[max(0, math.ceil(0.95 * len(vals)) - 1)]


def build(cfg: Dict, seed: int):
    """Weights from ``seed`` and a warm engine: (engine, weights, ref)."""
    import jax
    from repro.serve import Request, ServeConfig, make_engine

    system = importlib.import_module(f"bench.systems.{cfg['system']}")
    ref = importlib.import_module(f"bench.reference.{cfg['system']}")
    weights = ref.make_weights(cfg, seed)
    jax.block_until_ready(weights)
    sc = cfg["serve"]
    engine = make_engine("continuous", system.program_model(cfg),
                         system.program_params(cfg, weights),
                         ServeConfig(max_batch=sc["max_batch"],
                                     max_seq=sc["max_seq"],
                                     prefill_chunk=sc["prefill_chunk"],
                                     eos_token=None))
    C, V = sc["prefill_chunk"], cfg["vocab_size"]
    engine.serve([Request(prompt=(np.arange(C + 1) % (V - 1) + 1)
                          .astype(np.int32), max_new_tokens=2)])
    return engine, weights, ref


def trace_requests(mix: Dict, seed: int, seconds: float, vocab: int):
    from repro.serve import Request
    return [Request(prompt=a.prompt, max_new_tokens=a.max_new_tokens,
                    arrival_s=a.arrival_s, request_id=i)
            for i, a in enumerate(generate.requests(mix, seed, seconds,
                                                    vocab))]


def served(reqs, outs, stats) -> Dict:
    """End-to-end numbers of one ``serve`` call over ``reqs``."""
    done = [len(o) == q.max_new_tokens for o, q in zip(outs, reqs)]
    ms = stats.requests                   # in input order
    ttft = [m.ttft_s if ok else math.inf for m, ok in zip(ms, done)]
    tpot = [m.decode_s / (m.new_tokens - 1) if ok else math.inf
            for m, ok in zip(ms, done) if m.new_tokens > 1 or not ok]
    return {"done": done,
            "tokens_per_s": stats.total_new_tokens / stats.wall_s,
            "ttft_p95_ms": p95(ttft) * 1e3, "tpot_p95_ms": p95(tpot) * 1e3}


def run(r) -> None:
    cfg, mix = r.config, r.traffic
    engine, weights, ref = build(cfg, r.seed)
    reqs = trace_requests(mix, r.seed, r.seconds, cfg["vocab_size"])
    r.setup_done()

    r.open_window()
    outs, stats = engine.serve(reqs)
    r.close_window()
    del engine

    e2e = served(reqs, outs, stats)
    done = e2e.pop("done")
    r.attempted, r.failed = len(reqs), done.count(False)
    r.stats = stats
    r.e2e.update(e2e)
    r.counts.update(
        serve_wall_s=stats.wall_s,
        useful_flops=sum(counters.request_flops(cfg, len(q.prompt),
                                                len(o))
                         for q, o in zip(reqs, outs)),
        decode_tokens=sum(max(0, len(o) - 1) for o in outs),
        decode_steps=stats.decode_steps,
        max_batch=cfg["serve"]["max_batch"])

    pairs = [(reqs[i].prompt, outs[i])
             for i in sample(reqs, outs, done, r.seed, SERVED_TOKENS)]
    gap = ref.widest_gap(cfg, weights, pairs)
    if r.control:
        # the control stands in the program's place and is judged alike
        r.counts["program"] = {"logit_gap": gap}
        gap = ref.widest_gap(cfg, weights, pairs, control=r.control)
    r.check("logit_gap", gap, cfg["limits"]["logit_gap"])


def sample(reqs, outs, done, seed: int, tokens: int) -> List[int]:
    """Finished requests to check: the one with the most served tokens,
    the one with the longest prompt, then others in an order drawn from
    the seed, until ``tokens`` served tokens are covered."""
    ok = [i for i, d in enumerate(done) if d]
    if not ok:
        return []
    first = [max(ok, key=lambda i: len(outs[i])),
             max(ok, key=lambda i: len(reqs[i].prompt))]
    order = first + [int(i) for i in
                     generate.rng_for(seed, "check").permutation(ok)]
    picked: Dict[int, None] = {}
    for i in order:
        if len(picked) >= 2 and sum(len(outs[j]) for j in picked) >= tokens:
            break
        picked[i] = None
    return list(picked)
