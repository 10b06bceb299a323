"""Serving simulation helpers: a deterministic forced-EOS model + traces.

``countdown_model(V)`` is a stub :class:`repro.models.Model` whose greedy
next token is always ``(t + 1) % V``: a prompt ending in token ``t0``
generates ``t0+1, t0+2, ..., V-1, 0`` — so with ``eos_token=0`` the output
length is exactly ``V - t0``, deterministically heterogeneous across
prompts.  It honors the full decode-step cache contract (chunked prefill,
``kv_start``, parked slots) while costing almost nothing per step, which
makes it the scheduler-isolation workload of the EOS and overload
regression tests and of ``benchmarks/trace_demo.py``.
"""

from __future__ import annotations

from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from repro.models.api import Model
from repro.models.config import ModelConfig

from .stats import Request


def countdown_model(vocab_size: int = 48, work_dim: int = 0) -> Model:
    """Deterministic stub model: argmax(logits) == (token + 1) % V.

    ``work_dim > 0`` attaches a fixed compute load per step (two
    ``(tokens, work_dim) @ (work_dim, work_dim)`` matmuls whose sum is
    added as the *same* scalar to every logit — argmax-invariant), so a
    scheduler benchmark measures step-count efficiency under a realistic
    model-step cost instead of host overhead."""
    cfg = ModelConfig(name="countdown", family="dense", num_layers=1,
                      d_model=max(8, work_dim), num_heads=1, num_kv_heads=1,
                      d_ff=8, vocab_size=vocab_size, dtype="float32")

    def _logits(params, tokens):
        logits = jnp.eye(vocab_size, dtype=jnp.float32)[
            (tokens + 1) % vocab_size]                # (..., V)
        if work_dim:
            x = tokens.reshape(-1, 1).astype(jnp.float32) \
                + jnp.arange(work_dim, dtype=jnp.float32)[None, :]
            for _ in range(2):
                x = jnp.tanh(x @ params["w"])
            logits = logits + x.sum() * 1e-12         # same scalar everywhere
        return logits

    def init(key):
        if not work_dim:
            return {}
        import jax
        if key is None:  # a key array has no truth value — explicit check
            key = jax.random.key(0)
        return {"w": jax.random.normal(key, (work_dim, work_dim),
                                       jnp.float32) / np.sqrt(work_dim)}

    def forward(params, batch, want_cache=False):
        tokens = batch["tokens"]                      # (B, S)
        B, S = tokens.shape
        cache = None
        if want_cache:
            cache = {"k": jnp.zeros((1, B, S, 1, 1), jnp.float32),
                     "v": jnp.zeros((1, B, S, 1, 1), jnp.float32)}
        return _logits(params, tokens), cache

    def init_cache(B, T, **kw):
        return {"k": jnp.zeros((1, B, T, 1, 1), jnp.float32),
                "v": jnp.zeros((1, B, T, 1, 1), jnp.float32)}

    def decode_step(params, cache, tokens, pos, kv_start=None):
        return _logits(params, tokens), cache         # (B, C, V)

    return Model(cfg=cfg, init=init, forward=forward,
                 init_cache=init_cache, decode_step=decode_step,
                 supports_ragged=True)


def poisson_requests(n: int, rate_rps: float, vocab_size: int,
                     prompt_len: range = range(2, 12),
                     max_new_tokens: int = 64,
                     seed: int = 0) -> List[Request]:
    """A Poisson-arrival trace of random prompts (token 0 excluded so an
    ``eos_token=0`` config never terminates on a prompt echo).
    ``rate_rps <= 0`` means every request is queued at t=0."""
    rng = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for i in range(n):
        if rate_rps > 0:
            t += float(rng.exponential(1.0 / rate_rps))
        plen = int(rng.integers(prompt_len.start, prompt_len.stop))
        prompt = rng.integers(1, vocab_size, size=plen).astype(np.int32)
        reqs.append(Request(prompt=prompt, max_new_tokens=max_new_tokens,
                            arrival_s=t, request_id=i))
    return reqs


def bursty_requests(n: int, base_rps: float, burst_rps: float,
                    vocab_size: int,
                    burst_every: int = 8, burst_len: int = 4,
                    prompt_len: range = range(2, 12),
                    max_new_tokens: int = 64,
                    deadline_s: Optional[float] = None,
                    seed: int = 0) -> List[Request]:
    """A bursty (Markov-modulated Poisson) arrival trace.

    Arrivals alternate between a ``base_rps`` phase and a ``burst_rps``
    phase: every ``burst_every`` requests, the next ``burst_len`` arrive
    at the burst rate.  This is the overload workload for the admission
    control / deadline-eviction chaos gate (``benchmarks/chaos.py``):
    bursts drive the queue past the watermark while the base phase lets
    it drain.  ``deadline_s`` stamps each request's per-request SLO.
    """
    rng = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for i in range(n):
        in_burst = (i % (burst_every + burst_len)) >= burst_every
        rate = burst_rps if in_burst else base_rps
        if rate > 0:
            t += float(rng.exponential(1.0 / rate))
        plen = int(rng.integers(prompt_len.start, prompt_len.stop))
        prompt = rng.integers(1, vocab_size, size=plen).astype(np.int32)
        reqs.append(Request(prompt=prompt, max_new_tokens=max_new_tokens,
                            arrival_s=t, request_id=i,
                            deadline_s=deadline_s))
    return reqs
