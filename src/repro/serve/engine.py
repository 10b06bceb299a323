"""Batched serving: prefill + greedy decode over a fixed-capacity KV cache.

Two schedulers share this module's plumbing (DESIGN.md §10):

  * :class:`ServingEngine` — **wave** batching: admits up to ``max_batch``
    arrived requests, left-pads them into one prefill, and decodes the wave
    until every member has finished (EOS or its token budget).  The wave
    barrier is the baseline the continuous engine is measured against.
  * :class:`repro.serve.continuous.ContinuousServingEngine` — slot-based
    continuous batching (no wave barrier; see that module).

The step builders are also what the dry-run lowers for the ``prefill_*`` /
``decode_*`` / ``long_*`` shape cells.

Engines can consult a :class:`repro.registry.TuningService`: at
construction the model's core GEMM shapes are resolved through the
shared design registry, so a fleet of replicas tunes each kernel once
(first replica searches, the rest do pure lookups) — see DESIGN.md §9.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model

from .stats import Request, RequestMetrics, ServeStats, as_requests
from repro.obs import get_tracer, install_compile_listener

install_compile_listener()      # before the engines' first jit


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8          # wave width / continuous decode-slot count
    max_seq: int = 256          # KV-cache capacity per slot (continuous)
    # EOS token id; None disables EOS stopping (0 is a valid vocab id).
    # When set, generation stops at the first EOS and the returned sequence
    # is truncated to end with it.
    eos_token: Optional[int] = None
    prefill_chunk: int = 32     # continuous: tokens prefilled per tick
    # -- overload/fault policy (continuous engine, DESIGN.md §15) -------
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


def model_gemm_shapes(mcfg, cfg: "ServeConfig") -> List[Tuple[int, int, int]]:
    """The (M, N, K) GEMMs a serving step issues, prefill and decode.

    Delegates to the network-level layer graph
    (``repro.network.model_config_graph``, DESIGN.md §11) — the same
    single source of truth ``launch/serve.py --pretune`` resolves — so
    engine provisioning and the pre-tune pass can never diverge.  M is
    the token-parallel dim: ``max_batch * max_seq`` at prefill,
    ``max_batch`` at decode; N/K walk the exact per-layer projection,
    MLP/MoE, SSM and LM-head weights.
    """
    from repro.network.graph import model_config_graph
    graph = model_config_graph(mcfg, batch=cfg.max_batch,
                               prefill_len=cfg.max_seq)
    return graph.gemm_shapes()


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


def _pad_cache_to(cache: Dict, T: int):
    """Right-pad the (stacked) KV time axis of a prefill cache to T."""
    def pad(x):
        # KV leaves: (L, B, S, Hkv, hd) — pad dim 2; state leaves untouched
        if x.ndim == 5:
            padw = [(0, 0)] * 5
            padw[2] = (0, T - x.shape[2])
            return jnp.pad(x, padw)
        return x

    return {k: (pad(v) if k in ("k", "v") else v) for k, v in cache.items()}


class EngineBase:
    """Shared plumbing: jit'd steps + registry-tuned GEMM resolution."""

    scheduler = "base"

    def __init__(self, model: Model, params, cfg: ServeConfig,
                 tuning=None, tune_evals: int = 800):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.tuning = tuning
        self.tune_evals = tune_evals
        self.kernel_configs: Dict[Tuple[int, int, int], object] = {}
        self.kernel_stats = {"shared": 0, "tuned": 0}
        if tuning is not None:
            self._resolve_kernels()
        self.prefill = jax.jit(build_prefill_step(model))

        # one fused greedy tick: decode + argmax + position advance in a
        # single dispatch (the schedulers' hot loop makes one host sync per
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

    def _resolve_kernels(self) -> None:
        """Resolve block shapes for this engine's GEMMs via the registry.

        Resolution warms the shared store and the process-wide config
        LRU that ``kernels.matmul.matmul(..., config="auto")`` and
        :meth:`kernel_config` read.  Note the jit'd prefill/decode steps
        themselves currently lower through XLA's own GEMMs
        (``models/layers.py`` uses jnp ops, not the Pallas kernel), so
        this is provisioning for the Pallas path — callers that issue
        Pallas matmuls (custom kernels, benchmarks) get tuned shapes
        with zero search; swapping the model GEMMs onto
        ``kernels.matmul`` is the remaining step.  Each miss is a fast
        analytic-model search (tens of ms), so resolving synchronously
        at construction is cheaper than one jit compile; replicas after
        the first share everything from disk.
        """
        from repro.kernels.autotune import (resolve_matmul_config,
                                            tuner_counts)
        before = tuner_counts()
        for (M, N, K) in model_gemm_shapes(self.model.cfg, self.cfg):
            self.kernel_configs[(M, N, K)] = resolve_matmul_config(
                M, N, K, registry=self.tuning.store, evals=self.tune_evals)
        n = {s: c - before[s] for s, c in tuner_counts().items()}
        self.kernel_stats = {"shared": n["disk_hits"] + n["lru_hits"],
                             "tuned": n["tuned"]}

    def kernel_config(self, M: int, N: int, K: int):
        """Tuned MatmulConfig for an ad-hoc GEMM shape (LRU -> registry)."""
        cfg = self.kernel_configs.get((M, N, K))
        if cfg is None:
            from repro.kernels.autotune import resolve_matmul_config
            store = self.tuning.store if self.tuning is not None else None
            cfg = resolve_matmul_config(M, N, K, registry=store,
                                        evals=self.tune_evals)
            self.kernel_configs[(M, N, K)] = cfg
        return cfg

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


class ServingEngine(EngineBase):
    """Wave-synchronous scheduler: one left-padded prefill per admission
    wave; every member of a wave waits for the slowest before the next
    wave starts (the continuous engine removes this barrier)."""

    scheduler = "wave"

    def serve(self, requests: List[Request]
              ) -> Tuple[List[np.ndarray], ServeStats]:
        t0 = time.perf_counter()
        tr = get_tracer()
        queue = self._sorted_queue(requests)
        outs: List[Optional[np.ndarray]] = [None] * len(requests)
        metrics: List[Tuple[int, RequestMetrics]] = []
        decode_steps = prefills = 0
        while queue:
            now = time.perf_counter() - t0
            if queue[0][1].arrival_s > now:    # replaying a timed trace
                time.sleep(queue[0][1].arrival_s - now)
                now = time.perf_counter() - t0
            wave: List[Tuple[int, Request]] = []
            while queue and len(wave) < self.cfg.max_batch \
                    and queue[0][1].arrival_s <= now:
                wave.append(queue.popleft())
            admit = time.perf_counter() - t0
            if tr.enabled:
                tr.counter("serve.queue_depth", depth=len(queue))
                for idx, req in wave:
                    tr.instant("serve.admit", cat="serve",
                               request_id=req.request_id,
                               queue_wait_ms=(admit - req.arrival_s) * 1e3)
            with tr.span("serve.wave", cat="serve", batch=len(wave)):
                toks, reasons, first_s, finish_s, steps = self._wave(
                    [req for _, req in wave], t0)
            decode_steps += steps
            prefills += 1
            for r, (idx, req) in enumerate(wave):
                outs[idx] = toks[r]
                m = RequestMetrics(
                    request_id=req.request_id, prompt_len=len(req.prompt),
                    new_tokens=len(toks[r]),
                    queue_wait_s=admit - req.arrival_s,
                    ttft_s=first_s - req.arrival_s,
                    decode_s=finish_s[r] - first_s,
                    finish_reason=reasons[r])
                metrics.append((idx, m))
                if tr.enabled:
                    tr.instant("serve.finish", cat="serve",
                               request_id=req.request_id,
                               reason=reasons[r], new_tokens=m.new_tokens)
                    tr.counter("serve.request", ttft_ms=m.ttft_s * 1e3,
                               decode_tps=m.decode_tps)
        stats = ServeStats(scheduler=self.scheduler,
                           requests=[m for _, m in sorted(metrics)],
                           wall_s=time.perf_counter() - t0,
                           decode_steps=decode_steps,
                           prefill_chunks=prefills,  # one prefill per wave
                           engine=type(self).__name__)
        return outs, stats

    def _wave(self, wave: List[Request], t0: float):
        """Prefill + decode one wave.  Returns (tokens per row, finish
        reasons, first-token time, per-row finish times, decode steps)."""
        cfg = self.cfg
        B = len(wave)
        prompts = [r.prompt for r in wave]
        budgets = np.array([r.max_new_tokens for r in wave], np.int64)
        plen = max(len(p) for p in prompts)
        pads = np.array([plen - len(p) for p in prompts], np.int32)
        toks = np.zeros((B, plen), np.int32)
        for r, p in enumerate(prompts):
            toks[r, plen - len(p):] = p  # left-pad (simplest batching)
        batch = {"tokens": jnp.asarray(toks)}
        ragged = bool(pads.any())
        if ragged and self.model.supports_ragged:
            # per-row positions skip the pad; pad rows are masked out as
            # attention keys, so a short row decodes exactly as if unbatched
            pos_grid = np.maximum(
                np.arange(plen)[None, :] - pads[:, None], 0).astype(np.int32)
            if getattr(self.model.cfg, "mrope", False):
                pos_grid = np.broadcast_to(pos_grid, (3, B, plen))
            batch["positions"] = jnp.asarray(pos_grid)
            batch["attn_mask"] = jnp.asarray(
                np.arange(plen)[None, :] >= pads[:, None])
        last, cache = self.prefill(self.params, batch)
        max_new = int(budgets.max())
        cache = _pad_cache_to(cache, plen + max_new)
        kv_start = jnp.asarray(pads)
        one = jnp.ones((B,), jnp.int32)
        cur = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
        pos = jnp.full((B,), plen, jnp.int32)

        host_cur = np.asarray(cur)[:, 0]   # blocks until prefill is done
        first_s = time.perf_counter() - t0
        gen: List[List[int]] = [[int(t)] for t in host_cur]
        reasons = ["length"] * B
        finish_s = [first_s] * B
        eos = cfg.eos_token
        done = np.zeros(B, bool)
        for r in range(B):
            if eos is not None and host_cur[r] == eos:
                done[r], reasons[r] = True, "eos"
            elif budgets[r] == 1:
                done[r] = True
        steps = 0
        while not done.all():
            cur, pos, cache = self.decode_tick(self.params, cache, cur,
                                               pos, one, kv_start)
            steps += 1
            host_cur = np.asarray(cur)[:, 0]
            now_s = time.perf_counter() - t0
            for r in range(B):
                if done[r]:
                    continue
                gen[r].append(int(host_cur[r]))
                finish_s[r] = now_s
                if eos is not None and host_cur[r] == eos:
                    done[r], reasons[r] = True, "eos"
                elif len(gen[r]) >= budgets[r]:
                    done[r] = True
        return ([np.array(g, np.int32) for g in gen], reasons, first_s,
                finish_s, steps)
