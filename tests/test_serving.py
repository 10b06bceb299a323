"""Serving engines: prefill+decode equals teacher forcing; EOS stopping;
ragged left-padded batches; continuous-batching slot recycling."""

import dataclasses

import pytest

pytest.importorskip("jax")  # noqa: E402
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serve import (ContinuousServingEngine, Request, ServeConfig,
                         ServingEngine, greedy_mismatches, make_engine)
from repro.faults import FaultPlan, FaultSpec, injected
from repro.serve.sim import (bursty_requests, countdown_model,
                             poisson_requests)


def _engine(arch="smollm-135m", scheduler="wave", **cfg_kw):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cfg_kw.setdefault("max_batch", 4)
    cfg_kw.setdefault("max_seq", 64)
    return model, params, make_engine(scheduler, model, params,
                                      ServeConfig(**cfg_kw))


def test_greedy_generation_matches_manual_decode():
    model, params, eng = _engine()
    prompt = np.array([5, 9, 2, 7], np.int32)
    out = eng.generate([prompt], max_new_tokens=6)[0]
    # manual: full forward re-run per step (teacher forcing on own output)
    seq = list(prompt)
    manual = []
    for _ in range(6):
        logits, _ = model.forward(
            params, {"tokens": jnp.asarray([seq], jnp.int32)})
        nxt = int(jnp.argmax(logits[0, -1]))
        manual.append(nxt)
        seq.append(nxt)
    assert list(out) == manual


@pytest.mark.parametrize("scheduler", ["wave", "continuous"])
def test_served_tokens_match_cache_free_reference(scheduler):
    """The served-model check of ``chip_smoke.py`` at smoke size: every
    request's greedy tokens equal the argmax of one cache-free forward."""
    model, params, eng = _engine(scheduler=scheduler)
    reqs = poisson_requests(8, rate_rps=0.0, vocab_size=model.cfg.vocab_size,
                            prompt_len=range(2, 8), max_new_tokens=16, seed=0)
    with jax.default_matmul_precision("highest"):
        outs, stats = eng.serve(reqs)
        assert [len(o) for o in outs] == [16] * 8
        assert greedy_mismatches(model, params, reqs, outs) == []
        # a corrupted token is reported, naming its request
        outs[3] = outs[3].copy()
        outs[3][5] = (outs[3][5] + 1) % model.cfg.vocab_size
        bad = greedy_mismatches(model, params, reqs, outs)
    assert len(bad) == 1 and bad[0].startswith("request 3: served token 5")


def test_generation_batching_waves():
    model, params, eng = _engine()
    prompts = [np.array([i + 1, i + 2], np.int32) for i in range(7)]
    outs = eng.generate(prompts, max_new_tokens=4)
    assert len(outs) == 7
    assert all(len(o) == 4 for o in outs)
    # batching must not change results
    solo = eng.generate([prompts[5]], max_new_tokens=4)[0]
    np.testing.assert_array_equal(outs[5], solo)


@pytest.mark.parametrize("scheduler", ["wave", "continuous"])
def test_ragged_prompts_match_unbatched(scheduler):
    """Left-padded short prompts in a batch must produce exactly the greedy
    tokens of serving each prompt unbatched — every row, not just the
    longest (positions/caches for rows shorter than plen)."""
    model, params, eng = _engine(scheduler=scheduler, prefill_chunk=4)
    prompts = [np.array([3], np.int32),
               np.array([4, 5, 6], np.int32),
               np.array([9, 1, 9, 1, 9, 1, 9], np.int32)]
    outs = eng.generate(prompts, max_new_tokens=5)
    _, _, solo_eng = _engine()  # fresh wave engine, one request at a time
    for i, p in enumerate(prompts):
        solo = solo_eng.generate([p], max_new_tokens=5)[0]
        np.testing.assert_array_equal(
            outs[i], solo, err_msg=f"{scheduler}: row {i} diverged")


@pytest.mark.parametrize("scheduler", ["wave", "continuous"])
def test_eos_stops_and_truncates(scheduler):
    """A model forced to emit EOS: generation must stop there and the
    returned sequence must end with EOS (no post-EOS tokens)."""
    model = countdown_model(vocab_size=16)
    params = model.init(None)
    eng = make_engine(scheduler, model, params,
                      ServeConfig(max_batch=2, max_seq=64, eos_token=0,
                                  prefill_chunk=4))
    prompts = [np.array([12], np.int32),          # -> 13,14,15,0
               np.array([5, 9], np.int32),        # -> 10..15,0
               np.array([14, 14, 15], np.int32)]  # -> 0 (EOS immediately)
    outs, stats = eng.serve(
        [Request(prompt=p, max_new_tokens=32, request_id=i)
         for i, p in enumerate(prompts)])
    assert [list(o) for o in outs] == [
        [13, 14, 15, 0], [10, 11, 12, 13, 14, 15, 0], [0]]
    assert all(m.finish_reason == "eos" for m in stats.requests)
    # without EOS the same model decodes the full budget
    eng2 = make_engine(scheduler, model, params,
                       ServeConfig(max_batch=2, max_seq=64, eos_token=None,
                                   prefill_chunk=4))
    outs2, _ = eng2.serve([Request(prompt=prompts[0], max_new_tokens=8,
                                   request_id=0)])
    assert len(outs2[0]) == 8


def test_continuous_recycles_slots_and_reports_stats():
    """EOS must free the slot for the next queued request: 12 requests
    drain through 2 slots, and the per-request metrics are coherent."""
    model = countdown_model(vocab_size=16)
    params = model.init(None)
    eng = ContinuousServingEngine(model, params,
                                  ServeConfig(max_batch=2, max_seq=48,
                                              eos_token=0, prefill_chunk=4))
    reqs = poisson_requests(12, rate_rps=0, vocab_size=16,
                            max_new_tokens=32, seed=3)
    outs, stats = eng.serve(reqs)
    assert all(o is not None and o[-1] == 0 for o in outs)
    # every output is the deterministic countdown to EOS
    for r, o in zip(reqs, outs):
        assert len(o) == 16 - int(r.prompt[-1])
    assert len(stats.requests) == 12
    assert stats.total_new_tokens == sum(len(o) for o in outs)
    for m in stats.requests:
        assert m.finish_reason == "eos"
        assert 0 <= m.queue_wait_s <= m.ttft_s
        assert m.decode_s >= 0
    # 12 requests through 2 slots: decode steps must be far below the
    # wave bound (here: proof the barrier is gone and slots recycle)
    assert stats.decode_steps < sum(len(o) for o in outs)


def test_continuous_chunked_prefill_crosses_chunks():
    """Prompts longer than prefill_chunk must prefill over multiple chunks
    and still match the unbatched wave decode."""
    model, params, eng = _engine(scheduler="continuous", max_batch=2,
                                 prefill_chunk=3)
    prompt = np.array([7, 3, 9, 1, 4, 8, 2, 6, 5, 1, 2], np.int32)  # 11 > 3
    out = eng.generate([prompt], max_new_tokens=6)[0]
    _, _, wave = _engine()
    solo = wave.generate([prompt], max_new_tokens=6)[0]
    np.testing.assert_array_equal(out, solo)


def test_continuous_matches_wave_on_common_workload():
    model, params, weng = _engine()
    ceng = ContinuousServingEngine(model, params,
                                   ServeConfig(max_batch=3, max_seq=64,
                                               prefill_chunk=8))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, size=rng.integers(1, 9)).astype(np.int32)
               for _ in range(7)]
    wouts = weng.generate(prompts, max_new_tokens=4)
    couts = ceng.generate(prompts, max_new_tokens=4)
    for w, c in zip(wouts, couts):
        np.testing.assert_array_equal(w, c)


def test_wave_serve_per_request_budgets():
    """Mixed decode budgets in one wave: each row stops at its own."""
    model, params, eng = _engine()
    reqs = [Request(prompt=np.array([2, 3], np.int32), max_new_tokens=n,
                    request_id=i) for i, n in enumerate([1, 3, 6])]
    outs, stats = eng.serve(reqs)
    assert [len(o) for o in outs] == [1, 3, 6]
    assert [m.new_tokens for m in stats.requests] == [1, 3, 6]
    assert all(m.finish_reason == "length" for m in stats.requests)
    assert stats.throughput_tps > 0


def test_mamba_serving_still_works():
    """Non-attention family through both schedulers (whole-prompt chunks,
    no ragged contract)."""
    cfg = get_smoke_config("mamba2-130m")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompts = [np.array([3, 1, 4, 1, 5], np.int32)]
    w = ServingEngine(model, params,
                      ServeConfig(max_batch=2, max_seq=48)
                      ).generate(prompts, max_new_tokens=4)
    c = ContinuousServingEngine(model, params,
                                ServeConfig(max_batch=2, max_seq=48,
                                            prefill_chunk=3)
                                ).generate(prompts, max_new_tokens=4)
    np.testing.assert_array_equal(w[0], c[0])


def test_request_ids_are_labels_not_indices():
    """Caller-supplied request_ids (arbitrary, even duplicated) must not
    break output ordering: outputs come back in input order."""
    model = countdown_model(vocab_size=16)
    params = model.init(jax.random.key(0))  # real key must work too
    eng = ContinuousServingEngine(model, params,
                                  ServeConfig(max_batch=2, max_seq=48,
                                              eos_token=0, prefill_chunk=4))
    reqs = [Request(prompt=np.array([12], np.int32), max_new_tokens=8,
                    request_id=7),
            Request(prompt=np.array([10], np.int32), max_new_tokens=8,
                    request_id=7)]
    outs, stats = eng.serve(reqs)
    assert [list(o) for o in outs] == [[13, 14, 15, 0], [11, 12, 13, 14, 15, 0]]
    assert [m.request_id for m in stats.requests] == [7, 7]


@pytest.mark.parametrize("scheduler", ["wave", "continuous"])
def test_empty_prompt_rejected(scheduler):
    model = countdown_model(vocab_size=16)
    params = model.init(None)
    eng = make_engine(scheduler, model, params,
                      ServeConfig(max_batch=2, max_seq=48))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.serve([Request(prompt=np.array([], np.int32))])


@pytest.mark.parametrize("scheduler", ["wave", "continuous"])
def test_nonpositive_budget_rejected(scheduler):
    model = countdown_model(vocab_size=16)
    params = model.init(None)
    eng = make_engine(scheduler, model, params,
                      ServeConfig(max_batch=2, max_seq=48))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.serve([Request(prompt=np.array([3], np.int32),
                           max_new_tokens=0)])


# ------------------------------------------------------------------ #
# Overload policy (DESIGN.md §15): deadlines, shedding, tick retry.
# Invariant under every policy: each request is accounted exactly once.
# ------------------------------------------------------------------ #
def _countdown_engine(**cfg_kw):
    model = countdown_model(vocab_size=16)
    params = model.init(None)
    cfg_kw.setdefault("max_seq", 48)
    cfg_kw.setdefault("eos_token", 0)
    return ContinuousServingEngine(model, params, ServeConfig(**cfg_kw))


def test_continuous_deadline_timeout_accounts_everything():
    """Requests whose deadline expired while queued finish as "timeout"
    with empty output; the rest complete normally — nobody vanishes."""
    eng = _countdown_engine(max_batch=1)
    reqs = poisson_requests(8, rate_rps=0, vocab_size=16,
                            max_new_tokens=32, seed=5)
    # odd requests get a deadline that is already expired by the first
    # policing pass (sub-microsecond SLO)
    for i, r in enumerate(reqs):
        if i % 2:
            r.deadline_s = 1e-6
    outs, stats = eng.serve(reqs)
    assert len(stats.requests) == len(reqs)
    assert all(o is not None for o in outs)
    reasons = {m.request_id: m.finish_reason for m in stats.requests}
    for i, r in enumerate(reqs):
        if i % 2:
            assert reasons[r.request_id] == "timeout"
            assert len(outs[i]) == 0
        else:
            assert reasons[r.request_id] == "eos"
            assert len(outs[i]) > 0
    assert stats.timed_out == 4 and stats.shed == 0
    assert stats.to_dict()["timed_out"] == 4
    # zero-token drops are excluded from TTFT aggregates
    assert all(m.new_tokens >= 1
               for m in stats.requests if m.finish_reason == "eos")


def test_continuous_sheds_above_watermark_under_burst():
    """A bursty trace against a 1-slot engine with a shallow admission
    watermark: excess arrivals are shed, everything is accounted."""
    eng = _countdown_engine(max_batch=1, admit_watermark=2)
    reqs = bursty_requests(16, base_rps=2000.0, burst_rps=20000.0,
                           vocab_size=16, max_new_tokens=32, seed=2)
    outs, stats = eng.serve(reqs)
    assert len(stats.requests) == len(reqs)
    assert all(o is not None for o in outs)
    assert stats.shed >= 1
    counts = {}
    for m in stats.requests:
        counts[m.finish_reason] = counts.get(m.finish_reason, 0) + 1
    assert counts.get("shed", 0) == stats.shed
    assert sum(counts.values()) == len(reqs)
    assert all(m.new_tokens == 0 for m in stats.requests
               if m.finish_reason == "shed")
    assert "shed" in stats.summary()


def test_continuous_tick_retry_is_transparent():
    """Transient I/O faults inside the decode tick are retried with the
    pre-tick state: outputs are bit-identical to the fault-free run."""
    reqs = poisson_requests(6, rate_rps=0, vocab_size=16,
                            max_new_tokens=32, seed=7)
    clean_outs, clean_stats = _countdown_engine(max_batch=2).serve(reqs)
    assert clean_stats.retried == 0
    plan = FaultPlan((FaultSpec("serve.tick", "io_error", times=2),))
    with injected(plan):
        outs, stats = _countdown_engine(max_batch=2).serve(reqs)
    assert stats.retried == 2
    for a, b in zip(clean_outs, outs):
        np.testing.assert_array_equal(a, b)
    assert [m.finish_reason for m in stats.requests] == \
        [m.finish_reason for m in clean_stats.requests]
