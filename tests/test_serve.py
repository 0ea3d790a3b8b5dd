"""Serving engine (mxnet_tpu/serve): continuous batching, shape-bucketed
decode, admission control, HTTP frontend, zero-recompile steady state."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np
from mxnet_tpu.models import GPTModel, LlamaForCausalLM, generate
from mxnet_tpu.models.gpt import GPTConfig
from mxnet_tpu.models.llama import LlamaConfig
from mxnet_tpu.serve import (EngineClosedError, HTTPFrontend,
                             InferenceEngine, QueueFullError, bucket_for,
                             bucket_ladder, next_pow2)


@pytest.fixture(scope="module")
def gpt_model():
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=32, hidden_size=32, num_layers=2,
                             num_heads=2, max_position_embeddings=64,
                             dropout=0.0))
    net.initialize()
    return net


def _mixed_prompts(n, lo=3, hi=13, vocab=30, seed=0):
    rng = onp.random.RandomState(seed)
    return [rng.randint(1, vocab, size=rng.randint(lo, hi)).astype(onp.int32)
            for _ in range(n)]


def _wait_running(handle, timeout=30.0):
    t0 = time.perf_counter()
    while handle.status == "queued":
        if time.perf_counter() - t0 > timeout:
            raise AssertionError("request never admitted")
        time.sleep(0.005)


# ------------------------------------------------------------------ bucketing
def test_bucketing_helpers():
    assert next_pow2(1) == 1 and next_pow2(5) == 8 and next_pow2(8) == 8
    assert bucket_for(3, 8, 32) == 8
    assert bucket_for(9, 8, 32) == 16
    # the cap itself is a bucket even when not a power of two
    assert bucket_for(33, 8, 48) == 48
    assert bucket_ladder(8, 48) == [8, 16, 32, 48]
    with pytest.raises(mx.MXNetError, match="exceeds"):
        bucket_for(49, 8, 48)


# ------------------------------------------------------------ core batching
def test_engine_matches_sequential_generate(gpt_model):
    """Continuous batching must emit exactly the tokens the one-request
    compiled decode loop emits (greedy)."""
    # two distinct (P, max_new) signatures keep the generate() reference
    # cheap; the engine still sees mixed lengths and buckets
    rng = onp.random.RandomState(0)
    prompts = [rng.randint(1, 30, size=(4 if i % 2 else 9)).astype(onp.int32)
               for i in range(6)]
    eng = InferenceEngine(gpt_model, max_batch_size=4, max_len=32,
                          min_prompt_bucket=8).start()
    try:
        handles = [eng.submit(p, 6) for p in prompts]
        results = [h.result(120) for h in handles]
        for p, r in zip(prompts, results):
            assert r.status == "ok"
            ref = generate(gpt_model, np.array(p[None, :]), 6).asnumpy()[0]
            assert r.generated_ids == list(ref[len(p):])
            assert r.output_ids == list(ref)
            assert r.ttft_s is not None and r.ttft_s >= 0
    finally:
        eng.shutdown()


def test_slot_refill_midflight(gpt_model):
    """More requests than slots with staggered lengths: finished slots
    must be refilled while the rest of the batch keeps decoding."""
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32).start()
    try:
        prompts = _mixed_prompts(5, lo=3, hi=8, seed=1)
        news = [3, 9, 5, 7, 4]
        handles = [eng.submit(p, n) for p, n in zip(prompts, news)]
        results = [h.result(120) for h in handles]
        assert all(r.status == "ok" for r in results)
        assert [len(r.generated_ids) for r in results] == news
        st = eng.stats()
        assert st["completed"] == {"ok": 5}
        assert st["max_active"] == 2          # batch was full mid-flight
        assert st["submitted"] == 5           # 5 requests through 2 slots
    finally:
        eng.shutdown()


def test_eos_stops_slot_early(gpt_model):
    """A slot that hits eos retires immediately (and frees capacity);
    output ends at the first eos token."""
    p = onp.array([3, 1, 4, 1, 5], onp.int32)
    ref = generate(gpt_model, np.array(p[None, :]), 10).asnumpy()[0]
    gen_ref = list(ref[len(p):])
    eos = gen_ref[2]                          # force an early stop
    k = gen_ref.index(eos)                    # first occurrence (may be < 2)
    eng = InferenceEngine(gpt_model, max_batch_size=1, max_len=32).start()
    try:
        r = eng.generate(p, 10, eos_token_id=int(eos))
        assert r.status == "ok"
        assert r.generated_ids == gen_ref[:k + 1]  # up to and incl. eos
    finally:
        eng.shutdown()


def test_llama_and_stacked_llama_engine():
    """The engine drives any cache_spec/forward_cached model — per-layer
    GQA caches (batch axis 0) and stacked scan caches (batch axis 1)."""
    for stacked in (False, True):
        mx.random.seed(0)
        cfg = LlamaConfig(vocab_size=32, hidden_size=32, intermediate_size=64,
                          num_layers=2, num_heads=4, num_kv_heads=2,
                          dtype=onp.float32, stacked=stacked)
        net = LlamaForCausalLM(cfg)
        net.initialize()
        prompts = [onp.array([5, 9, 1, 7], onp.int32),
                   onp.array([2, 4, 6, 8, 10, 12], onp.int32)]
        eng = InferenceEngine(net, max_batch_size=2, max_len=32).start()
        try:
            handles = [eng.submit(p, 5) for p in prompts]
            for p, h in zip(prompts, handles):
                r = h.result(120)
                assert r.status == "ok"
                ref = generate(net, np.array(p[None, :]), 5).asnumpy()[0]
                assert r.generated_ids == list(ref[len(p):]), \
                    f"stacked={stacked}"
        finally:
            eng.shutdown()


def test_sampling_deterministic_per_request(gpt_model):
    """Per-request fold_in(key(seed), n) streams: same seed -> same
    tokens across engine runs; different seed differs."""
    p = onp.array([1, 2, 3, 4, 5], onp.int32)
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32).start()
    try:
        kw = dict(temperature=1.0, top_p=0.9, top_k=8)
        a = eng.generate(p, 12, seed=7, **kw)
        b = eng.generate(p, 12, seed=7, **kw)
        c = eng.generate(p, 12, seed=8, **kw)
        assert a.status == b.status == c.status == "ok"
        assert a.generated_ids == b.generated_ids
        assert a.generated_ids != c.generated_ids
    finally:
        eng.shutdown()


# ------------------------------------------------------------ lookahead
@pytest.mark.slow  # heaviest lookahead variant (~22 s): full sync-vs-
# lookahead token parity sweep; the cheaper lookahead tests (EOS at
# boundary, dispatch-failure salvage) stay tier-1 per the 870 s budget
def test_lookahead_parity_with_sync_engine(gpt_model):
    """Decode lookahead (dispatch N+1 before reading N) must be
    token-for-token identical to the synchronous engine AND to generate(),
    including mid-flight slot refill (6 requests through 2 slots with
    staggered lengths — every retire lands at a lookahead boundary)."""
    prompts = _mixed_prompts(6, lo=3, hi=9, seed=5)
    news = [1, 2, 5, 8, 3, 6]      # 1/2 finish at/next-to the boundary
    outs = {}
    for la in (False, True):
        eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32,
                              lookahead=la).start()
        try:
            handles = [eng.submit(p, n) for p, n in zip(prompts, news)]
            results = [h.result(120) for h in handles]
            assert all(r.status == "ok" for r in results)
            outs[la] = [r.generated_ids for r in results]
            assert eng.stats()["lookahead"] == la
            assert eng.stats()["max_active"] == 2   # refill mid-flight
        finally:
            eng.shutdown()
    assert outs[True] == outs[False]
    for p, n, got in zip(prompts, news, outs[True]):
        ref = generate(gpt_model, np.array(p[None, :]), n).asnumpy()[0]
        assert got == list(ref[len(p):])


def test_lookahead_eos_at_boundary(gpt_model):
    """EOS landing exactly when a speculative step is already in flight:
    the retired slot's lookahead token must be discarded — output ends at
    the first eos, byte-identical to generate()'s truncation."""
    p = onp.array([7, 2, 9], onp.int32)
    ref = list(generate(gpt_model, np.array(p[None, :]), 8).asnumpy()[0][3:])
    eng = InferenceEngine(gpt_model, max_batch_size=1, max_len=32,
                          lookahead=True).start()
    try:
        # every position: tok0 (prefill), first decode step (the first
        # lookahead boundary), and the final token
        for k in (0, 1, len(ref) - 1):
            eos = int(ref[k])
            first = ref.index(eos)      # eos may appear earlier
            r = eng.generate(p, 8, eos_token_id=eos)
            assert r.status == "ok"
            assert r.generated_ids == ref[:first + 1], f"eos at {k}"
    finally:
        eng.shutdown()


def test_lookahead_dispatch_failure_salvages_pending_tokens(gpt_model):
    """A decode-dispatch failure must not lose the PREVIOUS step's
    already-computed tokens: the pending read is salvaged first, so a
    request completing on that token retires OK, and an unfinished one
    errors with every token generated so far."""
    p = onp.array([4, 2, 7], onp.int32)
    ref = list(generate(gpt_model, np.array(p[None, :]), 6).asnumpy()[0][3:])

    def run(max_new):
        eng = InferenceEngine(gpt_model, max_batch_size=1,
                              max_len=32).start()
        try:
            orig = eng._get_step
            calls = {"n": 0}

            def flaky(sb):
                fn = orig(sb)

                def wrapped(*a):
                    calls["n"] += 1
                    if calls["n"] == 3:     # third decode dispatch dies
                        raise RuntimeError("injected dispatch failure")
                    return fn(*a)
                return wrapped
            eng._get_step = flaky
            return eng.generate(p, max_new)
        finally:
            eng.shutdown()

    # unfinished at the failure: error, but tok0 + the two computed
    # decode tokens (incl. the salvaged pending one) survive
    r = run(10)
    assert r.status == "error"
    assert r.generated_ids == ref[:3]
    # finishing exactly on the salvaged token: completes OK
    r = run(3)
    assert r.status == "ok"
    assert r.generated_ids == ref[:3]


def test_lookahead_host_sync_telemetry(gpt_model):
    """The host-read time the lookahead overlaps must be observable:
    mxnet_serve_host_sync_seconds flows on both the prefill tok0 read and
    the decode token reads."""
    from mxnet_tpu import metrics
    was_enabled = metrics.enabled()
    metrics.enable()
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32).start()
    try:
        before = metrics.get_sample_value(
            "mxnet_serve_host_sync_seconds_count") or 0
        r = eng.generate(onp.array([1, 2, 3], onp.int32), 6)
        assert r.status == "ok"
        after = metrics.get_sample_value(
            "mxnet_serve_host_sync_seconds_count")
        # >= 1 prefill read + >= 5 decode reads
        assert after >= before + 6
    finally:
        eng.shutdown()
        if not was_enabled:
            metrics.disable()


# ------------------------------------------------------------ multi-token
@pytest.mark.slow
def test_multi_token_parity_with_single_token(gpt_model):
    """multi_token=K (the on-device lax.while_loop emitting K tokens per
    host round-trip) must be token-for-token identical to multi_token=1
    and to generate(), through mid-flight slot refill (6 requests over 2
    slots, staggered lengths so retires land mid-K-block)."""
    prompts = _mixed_prompts(6, lo=3, hi=9, seed=5)
    news = [1, 2, 5, 8, 3, 6]
    outs = {}
    for K in (1, 4):
        eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32,
                              multi_token=K).start()
        try:
            handles = [eng.submit(p, n) for p, n in zip(prompts, news)]
            results = [h.result(120) for h in handles]
            assert all(r.status == "ok" for r in results)
            outs[K] = [r.generated_ids for r in results]
            assert eng.stats()["multi_token"] == K
            assert eng.stats()["max_active"] == 2   # refill mid-flight
        finally:
            eng.shutdown()
    assert outs[4] == outs[1]
    for p, n, got in zip(prompts, news, outs[4]):
        ref = generate(gpt_model, np.array(p[None, :]), n).asnumpy()[0]
        assert got == list(ref[len(p):])


def test_multi_token_sampled_parity(gpt_model):
    """The device loop samples with fold_in(key(seed), counter + j): the
    SAME streams the K=1 engine uses, so sampled output is identical
    across K (and deterministic per seed)."""
    p = onp.array([1, 2, 3, 4, 5], onp.int32)
    kw = dict(temperature=1.0, top_p=0.9, top_k=8, seed=7)
    outs = {}
    for K in (1, 3):
        eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32,
                              multi_token=K).start()
        try:
            outs[K] = eng.generate(p, 12, **kw).generated_ids
        finally:
            eng.shutdown()
    assert outs[3] == outs[1]


def test_multi_token_eos_at_k_boundary(gpt_model):
    """EOS landing at every position relative to the K-block boundary
    (first token of a block, mid-block, last token): the speculative rows
    past EOS must be discarded — output ends at the first eos, identical
    to generate()'s truncation."""
    p = onp.array([7, 2, 9], onp.int32)
    ref = list(generate(gpt_model, np.array(p[None, :]), 8).asnumpy()[0][3:])
    eng = InferenceEngine(gpt_model, max_batch_size=1, max_len=32,
                          multi_token=4).start()
    try:
        for k in (0, 1, 3, 4, len(ref) - 1):
            eos = int(ref[k])
            first = ref.index(eos)
            r = eng.generate(p, 8, eos_token_id=eos)
            assert r.status == "ok"
            assert r.generated_ids == ref[:first + 1], f"eos at {k}"
    finally:
        eng.shutdown()


def test_multi_token_llama_stacked(gpt_model):
    """The multi-token loop drives any cache_spec/forward_cached model —
    including the stacked-scan Llama decoder (cache batch axis 1)."""
    mx.random.seed(0)
    cfg = LlamaConfig(vocab_size=32, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      dtype=onp.float32, stacked=True)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    p = onp.array([5, 9, 1, 7], onp.int32)
    ref = generate(net, np.array(p[None, :]), 6).asnumpy()[0]
    eng = InferenceEngine(net, max_batch_size=2, max_len=32,
                          multi_token=3).start()
    try:
        r = eng.generate(p, 6)
        assert r.status == "ok"
        assert r.generated_ids == list(ref[len(p):])
    finally:
        eng.shutdown()


def test_multi_token_headroom_admission(gpt_model):
    """multi_token reserves K-1 cache rows of speculative-write headroom:
    a request that fits at K=1 but not at K=4 is rejected up front."""
    eng4 = InferenceEngine(gpt_model, max_batch_size=1, max_len=16,
                           multi_token=4)
    with pytest.raises(mx.MXNetError, match="headroom"):
        eng4.submit(onp.arange(1, 9, dtype=onp.int32), 8)
    with pytest.raises(mx.MXNetError, match="multi_token"):
        InferenceEngine(gpt_model, max_batch_size=1, max_len=16,
                        multi_token=0)


def test_multi_token_zero_recompiles_and_roundtrips(gpt_model):
    """The K-ladder smoke: warmup compiles every (batch-bucket, K)
    executable; mixed traffic (max_new not divisible by K, EOS
    mid-block, per-row budgets as data) must then run with ZERO new
    serve executables (analysis.no_recompile() guard) while host
    round-trips per decode token stay well under 1."""
    from mxnet_tpu import metrics
    from mxnet_tpu.analysis import guards
    was_enabled = metrics.enabled()
    metrics.enable()
    eng = InferenceEngine(gpt_model, max_batch_size=4, max_len=32,
                          min_prompt_bucket=8, multi_token=3).start()
    try:
        eng.warmup()
        rt0 = metrics.get_sample_value("mxnet_serve_host_roundtrips_total",
                                       {"path": "decode"}) or 0
        tok0 = metrics.get_sample_value("mxnet_serve_tokens_total") or 0
        prompts = _mixed_prompts(8, lo=2, hi=20, seed=3)
        with guards.no_recompile(block="serve"):
            handles = [eng.submit(p, 5 + i % 4,
                                  temperature=0.5 * (i % 2),
                                  top_k=4 * (i % 2), seed=i)
                       for i, p in enumerate(prompts)]
            results = [h.result(120) for h in handles]
        assert all(r.status == "ok" for r in results)
        rt = (metrics.get_sample_value("mxnet_serve_host_roundtrips_total",
                                       {"path": "decode"}) or 0) - rt0
        toks = (metrics.get_sample_value("mxnet_serve_tokens_total")
                or 0) - tok0
        decode_toks = toks - len(prompts)      # tok0s come from prefill
        assert rt > 0 and decode_toks > 0
        # one round-trip covers up to K=3 tokens; mid-flight retires make
        # it < K on average but the overlap must still be visible
        assert rt < decode_toks
    finally:
        eng.shutdown()
        if not was_enabled:
            metrics.disable()


# ------------------------------------------------------------ admission
def test_deadline_returns_partial_output(gpt_model):
    """A deadline that expires mid-decode completes the request with the
    tokens generated so far (status 'timeout')."""
    eng = InferenceEngine(gpt_model, max_batch_size=1, max_len=64).start()
    eng._step_delay = 0.02                    # fault injection: slow steps
    try:
        r = eng.generate(onp.array([1, 2, 3], onp.int32), 50, timeout_s=0.3)
        assert r.status == "timeout"
        assert 0 < len(r.generated_ids) < 50  # partial, not empty
        assert r.output_ids[:3] == [1, 2, 3]
    finally:
        eng.shutdown()


def test_queue_backpressure_and_cancel(gpt_model):
    eng = InferenceEngine(gpt_model, max_batch_size=1, max_len=64,
                          max_queue_depth=1).start()
    eng._step_delay = 0.02
    try:
        a = eng.submit(onp.array([1, 2], onp.int32), 50)
        _wait_running(a)
        b = eng.submit(onp.array([3, 4], onp.int32), 5)   # fills the queue
        with pytest.raises(QueueFullError):
            eng.submit(onp.array([5, 6], onp.int32), 5)   # backpressure
        # cancel the queued request: dropped before admission, no tokens
        assert b.cancel()
        rb = b.result(60)
        assert rb.status == "cancelled" and rb.generated_ids == []
        # cancel the in-flight request: stops at a step boundary, partial
        time.sleep(0.1)
        assert a.cancel()
        ra = a.result(60)
        assert ra.status == "cancelled"
        assert 0 < len(ra.generated_ids) < 50
        assert not a.cancel()                 # already terminal
    finally:
        eng.shutdown()


def test_queued_deadline_not_blocked_by_live_head(gpt_model):
    """A cancelled/expired request BEHIND a live unadmittable head must
    complete promptly (and release its queue-depth credit), not wait for
    the head to be admitted."""
    eng = InferenceEngine(gpt_model, max_batch_size=1, max_len=64,
                          max_queue_depth=4).start()
    eng._step_delay = 0.02
    try:
        a = eng.submit(onp.array([1, 2], onp.int32), 50)
        _wait_running(a)
        b = eng.submit(onp.array([3, 4], onp.int32), 5)   # live head, queued
        c = eng.submit(onp.array([5, 6], onp.int32), 5,
                       timeout_s=0.05)                    # expires behind b
        rc = c.result(30)
        assert rc.status == "timeout" and rc.generated_ids == []
        assert not a.done()           # completed while the slot was busy
        a.cancel()
        b.cancel()
    finally:
        eng.shutdown()


def test_shutdown_drains_inflight(gpt_model):
    """drain=True finishes in-flight slots; queued requests complete with
    status 'shutdown'; later submits raise."""
    eng = InferenceEngine(gpt_model, max_batch_size=1, max_len=64).start()
    eng._step_delay = 0.01
    a = eng.submit(onp.array([1, 2, 3], onp.int32), 20)
    _wait_running(a)
    b = eng.submit(onp.array([4, 5], onp.int32), 5)       # stays queued
    eng.shutdown(drain=True)
    ra, rb = a.result(1), b.result(1)
    assert ra.status == "ok" and len(ra.generated_ids) == 20
    assert rb.status == "shutdown" and rb.generated_ids == []
    with pytest.raises(EngineClosedError):
        eng.submit(onp.array([1], onp.int32), 2)
    assert not eng._thread.is_alive()


def test_shutdown_abort_returns_partial(gpt_model):
    eng = InferenceEngine(gpt_model, max_batch_size=1, max_len=64).start()
    eng._step_delay = 0.02
    a = eng.submit(onp.array([1, 2, 3], onp.int32), 50)
    _wait_running(a)
    time.sleep(0.1)
    eng.shutdown(drain=False)
    ra = a.result(1)
    assert ra.status == "shutdown"
    assert 0 < len(ra.generated_ids) < 50


def test_submit_validation(gpt_model):
    eng = InferenceEngine(gpt_model, max_batch_size=1, max_len=16).start()
    try:
        p = onp.array([1, 2, 3], onp.int32)
        with pytest.raises(mx.MXNetError, match="max_new_tokens"):
            eng.submit(p, 0)
        with pytest.raises(mx.MXNetError, match="max_len"):
            eng.submit(p, 14)                 # 3 + 14 > 16
        with pytest.raises(mx.MXNetError, match="top_k"):
            eng.submit(p, 4, top_k=-1)
        with pytest.raises(mx.MXNetError, match="top_p"):
            eng.submit(p, 4, top_p=0.0)
        with pytest.raises(mx.MXNetError, match="top_p"):
            eng.submit(p, 4, top_p=1.5)
        with pytest.raises(mx.MXNetError, match="non-empty"):
            eng.submit(onp.zeros((0,), onp.int32), 4)
        with pytest.raises(mx.MXNetError, match="outside"):
            eng.submit(onp.array([1, 99], onp.int32), 4)  # vocab is 32
        with pytest.raises(mx.MXNetError, match="outside"):
            eng.submit(onp.array([-1, 2], onp.int32), 4)
        with pytest.raises(mx.MXNetError, match="temperature"):
            eng.submit(p, 4, temperature=float("nan"))
    finally:
        eng.shutdown()


def test_engine_rejects_uncacheable_model():
    """MoE configs refuse KV-cache decode; the engine must refuse them."""
    cfg = LlamaConfig(vocab_size=32, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      dtype=onp.float32, num_experts=2,
                      num_experts_per_tok=1)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    with pytest.raises(mx.MXNetError, match="cache"):
        InferenceEngine(net, max_batch_size=2, max_len=32)


# ------------------------------------------------------------ one layout
def test_default_engine_is_paged_off_the_chip(gpt_model):
    """No backend switch: a default-constructed engine on the CPU serves
    through pages, and the pool's keys of ``stats()`` are always there."""
    stats = InferenceEngine(gpt_model, max_batch_size=2, max_len=32).stats()
    assert stats["paged"] is True
    assert stats["page_size"] == 16 and stats["pages"]["pages"] == 4
    assert {"prefilling", "preemptions", "kv_walk_blocks", "state_bytes",
            "prefix_summary"} <= set(stats)


def test_stats_count_the_rows_that_filter(gpt_model):
    """``sample_rows`` counts every row a program selects a token for (a
    final prefill one, a decode step its decoding rows) and
    ``sample_rows_filtered`` those among them that run filter_logits'
    search: sampled, under a top-k or a nucleus. A greedy row asks for
    none whatever top_k and top_p it carries, nor a sampled row with
    neither. Without lookahead a request's rows are its tokens."""
    eng = InferenceEngine(gpt_model, max_batch_size=4, max_len=32,
                          lookahead=False)
    asked = [dict(temperature=0.0, top_p=0.5), dict(temperature=0.0),
             dict(temperature=0.8, top_p=0.9), dict(temperature=1.0, top_k=5),
             dict(temperature=0.9)]
    with eng:
        hs = [eng.submit(onp.arange(4 + i) % 30 + 1, 6, seed=i, **kw)
              for i, kw in enumerate(asked)]
        done = [h.result(120) for h in hs]
        stats = eng.stats()
    assert all(r.ok and len(r.generated_ids) == 6 for r in done)
    assert stats["sample_rows"] == 5 * 6
    assert stats["sample_rows_filtered"] == 2 * 6


def _tiny_llama():
    net = LlamaForCausalLM(LlamaConfig(
        vocab_size=32, hidden_size=32, intermediate_size=64, num_layers=1,
        num_heads=4, num_kv_heads=2, dtype=onp.float32))
    net.initialize()
    return net


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_paged_false_is_refused_with_the_reason(gpt_model, family):
    net = gpt_model if family == "gpt" else _tiny_llama()
    with pytest.raises(mx.MXNetError, match="one cache layout") as err:
        InferenceEngine(net, max_batch_size=2, max_len=32, paged=False)
    assert "models.generate" in str(err.value)
    assert InferenceEngine(net, max_batch_size=2, max_len=32,
                           paged=True).stats()["paged"] is True


def test_explicit_page_size_must_divide_max_len(gpt_model):
    with pytest.raises(mx.MXNetError, match="multiple of page_size"):
        InferenceEngine(gpt_model, max_batch_size=2, max_len=40,
                        page_size=16)


def test_tuned_page_size_that_does_not_divide_falls_back(gpt_model,
                                                         monkeypatch):
    """A page size from the environment or the tuned layer, measured at
    another max_len, must not brick a default-constructed engine: it
    warns and serves with the knob's default; where that does not divide
    either, the pool's own error stands."""
    monkeypatch.setenv("MXNET_TUNE_SERVE_PAGE_SIZE", "12")
    with pytest.warns(UserWarning, match="serve_page_size=12"):
        eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32)
    assert eng.page_size == 16 and eng.stats()["page_size"] == 16
    assert InferenceEngine(gpt_model, max_batch_size=2,
                           max_len=48).page_size == 12    # divides: kept
    with pytest.warns(UserWarning, match="serve_page_size=12"), \
            pytest.raises(mx.MXNetError, match="multiple of page_size"):
        InferenceEngine(gpt_model, max_batch_size=2, max_len=40)


def test_model_without_the_paged_protocol_is_refused(gpt_model):
    """``cache_spec``/``forward_cached`` alone served the contiguous
    layout; the engine now says what is missing."""
    class ContiguousOnly:
        cfg = gpt_model.cfg
        cache_spec = gpt_model.cache_spec
        forward_cached = gpt_model.forward_cached

    with pytest.raises(mx.MXNetError, match="cache_spec_paged"):
        InferenceEngine(ContiguousOnly(), max_batch_size=2, max_len=32)


# ------------------------------------------------------------ telemetry
def test_zero_recompiles_after_warmup(gpt_model):
    """The tier-1 serving smoke: boot the engine in-process, warm the
    bucket ladder, then serve 8 concurrent mixed requests inside the
    analysis.no_recompile() guard — any new serve executable raises
    (shape bucketing contract), replacing the old hand-rolled telemetry
    scrape."""
    from mxnet_tpu import metrics
    from mxnet_tpu.analysis import guards
    was_enabled = metrics.enabled()
    metrics.enable()
    eng = InferenceEngine(gpt_model, max_batch_size=4, max_len=32,
                          min_prompt_bucket=8).start()
    try:
        eng.warmup()
        # the ladder: prefill buckets up to prefill_chunk (one page), the
        # step buckets, and one chunk, copy, extract and inject program
        assert eng.stats()["compiled_buckets"] == {"prefill": [8, 16],
                                                   "decode": [1, 2, 4]}
        assert [len(d) for d in (eng._chunk_fns, eng._copy_fns,
                                 eng._extract_fns, eng._inject_fns)] \
            == [1, 1, 1, 1]
        prompts = _mixed_prompts(8, lo=2, hi=20, seed=3)
        results = [None] * 8
        errors = []

        def client(i):
            try:
                results[i] = eng.generate(prompts[i], 6 + i % 5,
                                          temperature=0.5 * (i % 2),
                                          top_k=4 * (i % 2), seed=i)
            except Exception as e:  # noqa: BLE001 - surfaced below
                errors.append(e)

        with guards.no_recompile(block="serve"):
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
        assert not errors
        assert all(r is not None and r.status == "ok" for r in results)
        # queue-wait/ttft/step telemetry flowed
        assert metrics.get_sample_value("mxnet_serve_requests_total",
                                        {"status": "ok"}) >= 8
        assert metrics.get_sample_value("mxnet_serve_ttft_seconds_count") >= 8
        assert metrics.get_sample_value("mxnet_serve_tokens_total") > 8
    finally:
        eng.shutdown()
        if not was_enabled:
            metrics.disable()


# ------------------------------------------------------------ HTTP frontend
def test_http_endpoints(gpt_model):
    from mxnet_tpu import metrics
    was_enabled = metrics.enabled()
    metrics.enable()
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32).start()
    fe = HTTPFrontend(eng, port=0).start()
    url = fe.url
    try:
        prompt = [1, 2, 3]
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"input_ids": prompt,
                             "max_new_tokens": 5}).encode(),
            headers={"Content-Type": "application/json"})
        doc = json.loads(urllib.request.urlopen(req, timeout=120).read())
        ref = generate(gpt_model, np.array(onp.array([prompt], onp.int32)),
                       5).asnumpy()[0]
        assert doc["status"] == "ok"
        assert doc["output_ids"] == list(int(t) for t in ref)

        h = json.loads(urllib.request.urlopen(url + "/healthz",
                                              timeout=10).read())
        assert h["ok"] is True and h["slots"] == 2

        m = urllib.request.urlopen(url + "/metrics", timeout=10).read()
        text = m.decode()
        assert "mxnet_serve_requests_total" in text
        assert "# TYPE mxnet_serve_ttft_seconds histogram" in text

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                url + "/generate", data=b'{"max_new_tokens": 3}',
                headers={"Content-Type": "application/json"}), timeout=10)
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + "/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        fe.stop()
        eng.shutdown()
        if not was_enabled:
            metrics.disable()
    # stopped engine surfaces as 503 on a fresh frontend
    fe2 = HTTPFrontend(eng, port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(
                    fe2.url + "/generate",
                    data=json.dumps({"input_ids": [1],
                                     "max_new_tokens": 2}).encode()),
                timeout=10)
        assert ei.value.code == 503
    finally:
        fe2.stop()


# ------------------------------------------------------------ throughput demo
@pytest.mark.slow
def test_batched_throughput_vs_sequential():
    """Acceptance demo: 16 concurrent mixed-length requests through the
    engine vs. the sequential one-request-at-a-time generate() baseline
    (warm pass measured). Mixed shapes are the serving workload: the
    per-request compiled loop pays a compile per novel shape, the engine's
    buckets amortize one executable across the mix."""
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                             num_heads=4, max_position_embeddings=256,
                             dropout=0.0))
    net.initialize()
    rng = onp.random.RandomState(0)
    prompts = [rng.randint(1, 250, size=rng.randint(4, 25)).astype(onp.int32)
               for _ in range(16)]
    new = 48

    seq = float("inf")
    for _ in range(2):                        # second pass is warm
        t0 = time.perf_counter()
        for p in prompts:
            generate(net, np.array(p[None, :]), new)
        seq = min(seq, time.perf_counter() - t0)

    eng = InferenceEngine(net, max_batch_size=16, max_len=128).start()
    try:
        eng.warmup()
        bat = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            handles = [eng.submit(p, new) for p in prompts]
            results = [h.result(300) for h in handles]
            bat = min(bat, time.perf_counter() - t0)
            assert all(r.status == "ok" for r in results)
        for p, r in zip(prompts, results):
            ref = generate(net, np.array(p[None, :]), new).asnumpy()[0]
            assert r.generated_ids == list(ref[len(p):])
    finally:
        eng.shutdown()
    assert seq / bat >= 2.0, f"batched speedup only {seq / bat:.2f}x"
