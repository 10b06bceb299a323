"""Continuous batching: slot-based scheduling over a fixed-capacity KV cache.

The serving scheduler (DESIGN.md §10).  A wave barrier (every request
waits for the slowest in its batch) would be the serving analog of the
pruned design spaces the Odyssey paper quantifies: convenient, but it
idles compute slots on synchronization.  This engine has none:

  * ``max_batch`` **decode slots** back a single batched cache of capacity
    ``max_seq`` per slot; a request occupies one slot from admission to its
    EOS/budget, then the slot is recycled for the next queued request
    mid-stream — no wave barrier;
  * **chunked prefill**: prompts enter the slot cache ``prefill_chunk``
    tokens per scheduler tick through the model's chunked decode step, so a
    long prompt never stalls decode of the other slots for more than one
    chunk;
  * the decode tick always runs the full slot batch; free/prefilling slots
    are *parked* — fed a dummy token with their write index pinned to the
    last cache row, which the cache-frontier contract
    (``layers.attn_decode``) makes invisible: a parked write is overwritten
    before any query can attend it.  Parked rows cost FLOPs, not
    correctness — the slot count trades that against admission latency;
  * per-request queue wait / TTFT / decode tok/s land in a
    :class:`repro.serve.ServeStats` report.

Mid-prefill slots keep their chunk cache aside and splice it into the
batched cache only when the prompt completes, so decode ticks in between
cannot pollute recurrent (SSM/conv) state; attention-family models prefill
through fixed-size padded chunks (one jit trace), recurrent families through
exact-length chunks (the SSD scan cannot mask padding out of its state).

The hot loop is one fused jit dispatch per tick (decode + argmax + position
advance, see ``EngineBase.decode_tick``) plus a single device->host sync
for the harvested tokens; slot splices and decode inputs are rebuilt only
when slot membership changes.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .engine import EngineBase
from .stats import Request, RequestMetrics, ServeStats
from repro import faults
from repro.obs import get_metrics, get_tracer


class _Slot:
    """Host-side bookkeeping for one decode slot."""

    def __init__(self, index: int):
        self.index = index
        self.state = "free"               # free | prefill | decode
        self.req: Optional[Request] = None
        self.req_idx = -1                 # input position of self.req
        self.pos = 0                      # cache rows written so far
        self.chunks: List[np.ndarray] = []  # pending prompt chunks
        self.cache: Optional[Dict] = None   # private cache while prefilling
        self.gen: List[int] = []
        self.admit_s = 0.0
        self.first_s = 0.0


class ContinuousServingEngine(EngineBase):
    """Slot scheduler: admit requests into free decode slots mid-stream."""

    scheduler = "continuous"

    def __init__(self, model, params, cfg):
        super().__init__(model, params, cfg)
        self._cache_dtype = jnp.float32 \
            if getattr(model.cfg, "dtype", "bfloat16") == "float32" \
            else jnp.bfloat16
        # padded fixed-size chunks need the attention cache-frontier
        # contract; recurrent state (SSM/conv) must see exact tokens only
        self._padded_chunks = model.supports_ragged
        self._chunk_fns: Dict[int, object] = {}
        # splice a one-slot cache into the batch cache (slot axis is 1 on
        # every leaf); the slot index is a traced arg — one compile total
        self._insert_fn = jax.jit(
            lambda cache, slot, s: {
                k: jax.lax.dynamic_update_slice_in_dim(cache[k], slot[k],
                                                       s, axis=1)
                for k in cache})

    # ------------------------------------------------------------------ #
    def _chunk_fn(self, C: int):
        """jit'd chunked prefill step for chunk length C: greedy next
        tokens (1, C) + updated slot cache (one trace per C; the padded
        path only ever uses C = cfg.prefill_chunk)."""
        if C not in self._chunk_fns:
            model = self.model

            def chunk(params, cache, tokens, pos):
                logits, cache = model.decode_step(params, cache, tokens, pos)
                return jnp.argmax(logits, -1).astype(jnp.int32), cache

            self._chunk_fns[C] = jax.jit(chunk)
        return self._chunk_fns[C]

    def _chunks_of(self, prompt: np.ndarray) -> List[np.ndarray]:
        C = self.cfg.prefill_chunk
        if not self._padded_chunks:
            return [prompt[i:i + C] for i in range(0, len(prompt), C)]
        out = []
        for i in range(0, len(prompt), C):
            part = prompt[i:i + C]
            if len(part) < C:  # pad to the fixed trace length; the pad rows
                part = np.pad(part, (0, C - len(part)))  # are never attended
            out.append(part)
        return out

    def _writes_needed(self, plen: int) -> int:
        C = self.cfg.prefill_chunk
        return ((plen + C - 1) // C) * C if self._padded_chunks else plen

    # ------------------------------------------------------------------ #
    def serve(self, requests: List[Request]
              ) -> Tuple[List[np.ndarray], ServeStats]:
        cfg = self.cfg
        S, T = cfg.max_batch, cfg.max_seq
        for r in requests:
            need = max(self._writes_needed(len(r.prompt)),
                       len(r.prompt) + r.max_new_tokens)
            if need > T:
                raise ValueError(
                    f"request needs {need} cache rows "
                    f"(prompt {len(r.prompt)} + {r.max_new_tokens} new) "
                    f"> max_seq={T}")
        t0 = time.perf_counter()
        tr = get_tracer()
        queue = self._sorted_queue(requests)
        cache = self.model.init_cache(S, T, dtype=self._cache_dtype)
        # every admission starts from this (immutable) empty one-slot cache
        fresh_slot = self.model.init_cache(1, T, dtype=self._cache_dtype)
        slots = [_Slot(s) for s in range(S)]
        outs: List[Optional[np.ndarray]] = [None] * len(requests)
        metrics: List[Tuple[int, RequestMetrics]] = []
        decode_steps = prefill_chunks = 0
        eos = cfg.eos_token

        # device-resident decode inputs: rebuilt from the host mirrors only
        # when slot membership changes (admission/finish), advanced inside
        # the fused tick between — the steady-state tick does a single D2H
        # transfer (the harvested tokens)
        kv0 = jnp.zeros((S,), jnp.int32)
        cur_host = np.zeros(S, np.int32)
        pos_host = np.full(S, T - 1, np.int32)   # parked rows: see module doc
        cur_dev = pos_dev = step_dev = None
        membership_dirty = True
        shed = timed_out = retried = 0

        # overload policy (DESIGN.md §15): deadlines + admission control
        # are policed once per tick; both paths account the request in
        # ``metrics`` exactly once, so nothing is ever silently dropped
        def _deadline(req: Request) -> Optional[float]:
            dl = req.deadline_s if req.deadline_s is not None \
                else cfg.deadline_s
            return None if dl is None else req.arrival_s + dl
        policed = cfg.deadline_s is not None \
            or cfg.admit_watermark is not None \
            or any(r.deadline_s is not None for r in requests)

        def drop(req_idx: int, req: Request, reason: str, now_s: float):
            """Account a request that never reached a slot (shed, or timed
            out while queued): empty output, zero tokens."""
            nonlocal shed, timed_out
            outs[req_idx] = np.zeros(0, np.int32)
            metrics.append((req_idx, RequestMetrics(
                request_id=req.request_id, prompt_len=len(req.prompt),
                new_tokens=0, queue_wait_s=now_s - req.arrival_s,
                ttft_s=0.0, decode_s=0.0, finish_reason=reason)))
            if reason == "shed":
                shed += 1
            else:
                timed_out += 1
            get_metrics().counter("serve." + reason)
            tr.instant("serve." + reason, cat="serve",
                       request_id=req.request_id,
                       queued_s=now_s - req.arrival_s)

        def police_queue(now_s: float):
            """Time out arrived requests past their deadline; shed the
            newest arrivals above the admission watermark."""
            kept: List = []
            waiting = 0
            while queue:
                idx, req = queue[0]
                if req.arrival_s > now_s:
                    break              # sorted by arrival: rest is future
                queue.popleft()
                dl = _deadline(req)
                if dl is not None and now_s > dl:
                    drop(idx, req, "timeout", now_s)
                elif cfg.admit_watermark is not None \
                        and waiting >= cfg.admit_watermark:
                    drop(idx, req, "shed", now_s)
                else:
                    kept.append((idx, req))
                    waiting += 1
            for item in reversed(kept):
                queue.appendleft(item)

        def finish(slot: _Slot, reason: str, now_s: float):
            nonlocal membership_dirty, timed_out
            req = slot.req
            outs[slot.req_idx] = np.array(slot.gen, np.int32)
            # a slot evicted mid-prefill has no first token: its TTFT and
            # decode time are undefined, reported as 0 and excluded from
            # ServeStats' TTFT aggregates (new_tokens == 0)
            started = bool(slot.gen)
            m = RequestMetrics(
                request_id=req.request_id, prompt_len=len(req.prompt),
                new_tokens=len(slot.gen),
                queue_wait_s=slot.admit_s - req.arrival_s,
                ttft_s=slot.first_s - req.arrival_s if started else 0.0,
                decode_s=now_s - slot.first_s if started else 0.0,
                finish_reason=reason)
            metrics.append((slot.req_idx, m))
            if reason == "timeout":
                timed_out += 1
                get_metrics().counter("serve.timeout")
            if tr.enabled:
                tr.instant("serve.finish", cat="serve",
                           request_id=req.request_id, slot=slot.index,
                           reason=reason, new_tokens=m.new_tokens)
                # rolling request-level latency series: render alongside
                # the slot-occupancy track for a live Perfetto view
                tr.counter("serve.request", ttft_ms=m.ttft_s * 1e3,
                           decode_tps=m.decode_tps)
            slot.state, slot.req, slot.gen = "free", None, []
            slot.chunks, slot.cache = [], None
            pos_host[slot.index] = T - 1
            membership_dirty = True

        while queue or any(s.state != "free" for s in slots):
            now = time.perf_counter() - t0
            with tr.span("serve.schedule", cat="serve"):
                if policed:
                    police_queue(now)
                    # deadline eviction of in-flight requests: a timed-out
                    # slot frees immediately (partial output kept) so a
                    # stuck/slow request can never wedge the slot forever
                    for slot in slots:
                        if slot.state == "free":
                            continue
                        dl = _deadline(slot.req)
                        if dl is not None and now > dl:
                            finish(slot, "timeout", now)
                # --- admission: recycle free slots from the arrived queue
                for slot in slots:
                    if slot.state != "free" or not queue \
                            or queue[0][1].arrival_s > now:
                        continue
                    slot.req_idx, slot.req = queue.popleft()
                    slot.state = "prefill"
                    slot.pos = 0
                    slot.chunks = self._chunks_of(slot.req.prompt)
                    slot.cache = fresh_slot
                    slot.admit_s = now
                    tr.instant("serve.admit", cat="serve",
                               request_id=slot.req.request_id,
                               slot=slot.index,
                               queue_wait_ms=(now - slot.req.arrival_s)
                               * 1e3)
            if tr.enabled:
                tr.counter("serve.slots",
                           decode=sum(1 for s in slots
                                      if s.state == "decode"),
                           prefill=sum(1 for s in slots
                                       if s.state == "prefill"),
                           free=sum(1 for s in slots if s.state == "free"))
                tr.counter("serve.queue_depth", depth=len(queue))
            if all(s.state == "free" for s in slots):
                # queue is non-empty but nothing has arrived yet
                time.sleep(max(0.0, queue[0][1].arrival_s
                               - (time.perf_counter() - t0)))
                continue

            # --- one prefill chunk per mid-prefill slot (keeps long --- #
            # --- prompts from stalling the decode of other slots)   --- #
            for slot in slots:
                if slot.state != "prefill":
                    continue
                chunk = slot.chunks.pop(0)
                fn = self._chunk_fn(len(chunk))
                with tr.span("serve.prefill_chunk", cat="serve",
                             slot=slot.index, tokens=len(chunk),
                             request_id=slot.req.request_id):
                    toks, slot.cache = fn(
                        self.params, slot.cache,
                        jnp.asarray(chunk[None, :].astype(np.int32)),
                        jnp.asarray([slot.pos], jnp.int32))
                slot.pos += len(chunk)
                prefill_chunks += 1
                if slot.chunks:
                    continue
                # prompt complete: splice the private cache into the batch
                # cache and take the first generated token from the last
                # real prompt row of this chunk
                plen = len(slot.req.prompt)
                # last *real* prompt row of this final chunk: padded chunks
                # have fixed length C, exact chunks end at their last row
                last_row = (plen - 1) % len(chunk) if self._padded_chunks \
                    else len(chunk) - 1
                with tr.span("serve.first_token_harvest", cat="serve",
                             slot=slot.index):
                    first = int(np.asarray(toks)[0, last_row])
                cache = self._insert_fn(cache, slot.cache,
                                        jnp.int32(slot.index))
                slot.cache = None
                slot.pos = plen          # decode writes resume at plen
                slot.gen = [first]
                slot.first_s = time.perf_counter() - t0
                if eos is not None and first == eos:
                    finish(slot, "eos", slot.first_s)
                elif slot.req.max_new_tokens == 1:
                    finish(slot, "length", slot.first_s)
                else:
                    slot.state = "decode"
                    cur_host[slot.index] = first
                    pos_host[slot.index] = plen
                    membership_dirty = True

            # --- one fused decode tick over the full slot batch --- #
            if not any(s.state == "decode" for s in slots):
                continue
            if membership_dirty:
                cur_dev = jnp.asarray(cur_host[:, None])
                pos_dev = jnp.asarray(pos_host)
                step_host = np.array([1 if s.state == "decode" else 0
                                      for s in slots], np.int32)
                step_dev = jnp.asarray(step_host)
                membership_dirty = False
            # transient errors (device hiccup, injected TransientIOError)
            # retry the whole tick: its inputs are unchanged until the
            # assignment below succeeds, so a retry is exact
            last_exc: Optional[BaseException] = None
            for _ in range(max(1, cfg.tick_retries)):
                try:
                    faults.fault_point("serve.tick")
                    with tr.span("serve.decode_tick", cat="serve",
                                 active=int(sum(1 for s in slots
                                                if s.state == "decode"))
                                 if tr.enabled else 0):
                        nxt_cur, nxt_pos, nxt_cache = self.decode_tick(
                            self.params, cache, cur_dev, pos_dev, step_dev,
                            kv0)
                        # writable host mirror (np.asarray of a jax array
                        # is read-only); this D2H copy is the tick's one
                        # device sync, so the span brackets real work,
                        # not dispatch latency
                        with tr.span("serve.tick_harvest", cat="serve"):
                            nxt_host = np.array(nxt_cur)[:, 0]
                    cur_dev, pos_dev, cache = nxt_cur, nxt_pos, nxt_cache
                    cur_host = nxt_host
                    decode_steps += 1
                except OSError as exc:
                    last_exc = exc
                    retried += 1
                    get_metrics().counter("serve.tick_retries")
                    tr.instant("fault.tick_retry", cat="fault",
                               error=repr(exc))
                    continue
                break
            else:
                raise last_exc
            pos_host += step_host
            now_s = time.perf_counter() - t0
            for slot in slots:
                if slot.state != "decode":
                    continue
                tok = int(cur_host[slot.index])
                slot.gen.append(tok)
                slot.pos += 1
                if eos is not None and tok == eos:
                    finish(slot, "eos", now_s)
                elif len(slot.gen) >= slot.req.max_new_tokens:
                    finish(slot, "length", now_s)

        stats = ServeStats(scheduler=self.scheduler,
                           requests=[m for _, m in sorted(metrics)],
                           wall_s=time.perf_counter() - t0,
                           decode_steps=decode_steps,
                           prefill_chunks=prefill_chunks,
                           engine=type(self).__name__,
                           shed=shed, timed_out=timed_out, retried=retried)
        return outs, stats
