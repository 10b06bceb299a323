"""Serving plumbing: request validation, the fused decode tick, step builders.

:class:`EngineBase` holds what the scheduler
(:class:`repro.serve.continuous.ContinuousServingEngine`, DESIGN.md §10)
runs on: the model and its weights, the :class:`ServeConfig`, the jit'd
greedy decode tick, and the admission queue's validation.

The step builders are what the dry-run lowers for the ``prefill_*`` /
``decode_*`` / ``long_*`` shape cells.  Served GEMMs lower through XLA's
dot, so an engine resolves no kernel block shape.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model

from .stats import Request, ServeStats, as_requests
from repro.obs import install_compile_listener

install_compile_listener()      # before the engine's first jit


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8          # decode-slot count
    max_seq: int = 256          # KV-cache capacity per slot
    # EOS token id; None disables EOS stopping (0 is a valid vocab id).
    # When set, generation stops at the first EOS and the returned sequence
    # is truncated to end with it.
    eos_token: Optional[int] = None
    prefill_chunk: int = 32     # tokens prefilled per tick
    # -- overload/fault policy (DESIGN.md §15) --------------------------
    # Default per-request deadline from arrival (Request.deadline_s
    # overrides); past it a queued request is timed out without a slot
    # and an in-flight one is evicted keeping its partial output.
    deadline_s: Optional[float] = None
    # Admission watermark: when more than this many *arrived* requests
    # are waiting, the newest arrivals are shed (finish_reason "shed")
    # instead of queueing unboundedly.  None = never shed.
    admit_watermark: Optional[int] = None
    # Bounded retry of the fused decode tick on transient (OS-level)
    # errors before giving up; retries land in ServeStats.retried.
    tick_retries: int = 3


def build_prefill_step(model: Model) -> Callable:
    """(params, batch) -> (last_logits, cache_of_seq_len)."""

    def prefill(params, batch):
        logits, cache = model.forward(params, batch, want_cache=True)
        return logits[:, -1], cache

    return prefill


def build_decode_step(model: Model) -> Callable:
    """(params, cache, tokens (B,1), pos (B,)) -> (logits (B,V), cache)."""

    def decode(params, cache, tokens, pos):
        logits, cache = model.decode_step(params, cache, tokens, pos)
        return logits[:, 0], cache

    return decode


def greedy_mismatches(model: Model, params, requests: List[Request],
                      outs: List[np.ndarray]) -> List[str]:
    """Served greedy tokens against a reference that uses no cache.

    One ``model.forward`` over every request's prompt plus its served
    tokens, right-padded into one batch (causal attention keeps each
    position blind to the padding); the argmax at each position from the
    last prompt token on must be the next served token.  Returns one
    message per request that diverges, empty when all match."""
    seqs = [np.concatenate([r.prompt, o]).astype(np.int32)
            for r, o in zip(requests, outs)]
    toks = np.zeros((len(seqs), max(len(s) for s in seqs)), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    logits, _ = jax.jit(model.forward)(params, {"tokens": jnp.asarray(toks)})
    pred = np.asarray(jnp.argmax(logits, -1))
    bad = []
    for i, (r, o) in enumerate(zip(requests, outs)):
        p = len(r.prompt)
        want = pred[i, p - 1:p - 1 + len(o)]
        if not np.array_equal(want, o):
            t = int(np.argmax(want != o))
            bad.append(f"request {i}: served token {t} is {int(o[t])}, the "
                       f"cache-free forward predicts {int(want[t])}")
    return bad


class EngineBase:
    """Shared plumbing: the model, its weights and the jit'd decode tick."""

    scheduler = "base"

    def __init__(self, model: Model, params, cfg: ServeConfig):
        self.model = model
        self.params = params
        self.cfg = cfg

        # one fused greedy tick: decode + argmax + position advance in a
        # single dispatch (the scheduler's hot loop makes one host sync per
        # tick — the harvested tokens — and nothing else)
        def tick(params, cache, tokens, pos, step, kv_start):
            if model.supports_ragged:
                logits, cache = model.decode_step(params, cache, tokens,
                                                  pos, kv_start=kv_start)
            else:
                logits, cache = model.decode_step(params, cache, tokens, pos)
            nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)[:, None]
            return nxt, pos + step, cache

        self.decode_tick = jax.jit(tick)

    # ------------------------------------------------------------------ #
    def generate(self, prompts: List[np.ndarray],
                 max_new_tokens: int = 32) -> List[np.ndarray]:
        """Greedy generation; returns one token array per prompt, truncated
        at EOS when ``cfg.eos_token`` is set."""
        outs, _ = self.serve(as_requests(prompts, max_new_tokens))
        return outs

    def serve(self, requests: List[Request]
              ) -> Tuple[List[np.ndarray], ServeStats]:
        raise NotImplementedError

    @staticmethod
    def _sorted_queue(requests: List[Request]
                      ) -> "deque[Tuple[int, Request]]":
        """Admission queue of (input position, request), arrival-ordered.

        Outputs are always returned in input order (the position, not the
        caller-supplied ``request_id``, indexes them); metrics carry the
        caller's ``request_id`` when set, else the position."""
        reqs = []
        for i, r in enumerate(requests):
            if len(r.prompt) == 0:
                raise ValueError(f"request {i}: empty prompt")
            if r.max_new_tokens < 1:
                raise ValueError(f"request {i}: max_new_tokens must be >= 1 "
                                 f"(got {r.max_new_tokens})")
            if r.request_id < 0:
                r = dataclasses.replace(r, request_id=i)
            reqs.append((i, r))
        return deque(sorted(reqs, key=lambda e: (e[1].arrival_s, e[0])))
