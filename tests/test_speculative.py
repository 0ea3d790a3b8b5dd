"""Self-speculative decoding (ISSUE 15): n-gram prompt-lookup drafts
from the request's own history, verified in ONE batched step.

The tier-1 contracts:

- TOKEN-EXACTNESS: ``speculate=K`` output is identical to ``speculate=0``
  for greedy AND sampled requests, both cache layouts — the verify step
  recomputes exactly the token the sequential path would emit (same
  bitwise logits by T-invariance, same stateless fold_in keys), so
  speculation can change latency, never content.
- Composition: paging + COW prefix sharing + chunked prefill + int8
  block decode all serve speculative traffic unchanged; the router
  serves paged+int8+speculative end-to-end with zero steady-state
  recompiles (no_recompile()-guarded).
- The drafting source is deterministic and the tuned-config knobs
  (serve_speculate / serve_spec_draft / serve_spec_lookup) resolve per
  the PR-13 layer.
"""
import json
import urllib.request

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import GPTModel, LlamaForCausalLM
from mxnet_tpu.models.gpt import GPTConfig
from mxnet_tpu.models.llama import LlamaConfig
from mxnet_tpu.serve import (HTTPFrontend, InferenceEngine, Router,
                             draft_from_history)


@pytest.fixture(scope="module")
def gpt_model():
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                             num_heads=2, max_position_embeddings=128,
                             dropout=0.0))
    net.initialize()
    net(np.array(onp.zeros((1, 4), "int32")))
    return net


def _prompts(n, lo=3, hi=12, vocab=60, seed=0):
    rng = onp.random.RandomState(seed)
    return [rng.randint(1, vocab, size=rng.randint(lo, hi))
            .astype(onp.int32) for _ in range(n)]


def _serve_all(net, prompts, max_new, reqs=None, **kw):
    """Serve every prompt; per-request kwargs via ``reqs`` (list of
    dicts). Every request must succeed."""
    eng = InferenceEngine(net, **kw).start()
    try:
        handles = [eng.submit(p, max_new, **(reqs[i] if reqs else {}))
                   for i, p in enumerate(prompts)]
        outs = []
        for h in handles:
            r = h.result(300)
            assert r.status == "ok", (r.status, r.error)
            outs.append(list(r.generated_ids))
        return outs, eng.stats()
    finally:
        eng.shutdown()


# ------------------------------------------------------------ draft source
def test_draft_from_history_ngram_lookup():
    # longest suffix n-gram [7, 8] re-occurs at index 1: continuation
    # copies what followed it
    h = [1, 7, 8, 9, 4, 7, 8]
    assert draft_from_history(h, 2, 4) == [9, 4]
    # continuation shorter than the draft: pad by repeating the tail
    assert draft_from_history(h, 4, 4) == [9, 4, 7, 8]
    # no earlier occurrence of any suffix n-gram: repeat the last token
    assert draft_from_history([1, 2, 3], 3, 4) == [3, 3, 3]
    # constant runs draft themselves
    assert draft_from_history([5, 5, 5, 5], 3, 4) == [5, 5, 5]
    # deterministic + exact length
    assert len(draft_from_history(list(range(50)) * 2, 7, 4)) == 7


def test_draft_prefers_longest_and_most_recent_match():
    # suffix [2, 3] occurs twice earlier; the MOST RECENT one (index 4)
    # wins, so the draft copies 9 not 7
    h = [2, 3, 7, 0, 2, 3, 9, 1, 2, 3]
    assert draft_from_history(h, 1, 4)[0] == 9


# ------------------------------------------------------- exact verification
def test_spec_verify_tokens_acceptance_arithmetic():
    import jax.numpy as jnp
    from mxnet_tpu.models.generation import (_fold_keys, sample_tokens,
                                             spec_verify_tokens)
    rng = onp.random.RandomState(0)
    B, T, V = 3, 4, 16
    logits = jnp.asarray(rng.randn(B, T, V), jnp.float32)
    temps = jnp.asarray([0.0, 0.8, 0.0], jnp.float32)
    topks = jnp.zeros((B,), jnp.int32)
    topps = jnp.ones((B,), jnp.float32)
    seeds = jnp.asarray([3, 5, 7], jnp.uint32)
    counters = jnp.asarray([2, 0, 9], jnp.int32)
    # the per-column reference: exactly what the sequential path emits
    want = []
    for j in range(T):
        keys = _fold_keys(seeds, counters + j)
        want.append(onp.asarray(sample_tokens(logits[:, j], keys, temps,
                                              topks, topps)))
    want = onp.stack(want, axis=1)
    # craft inputs: row 0 drafts everything right (acc=T), row 1 breaks
    # at the first draft (acc=1), row 2 at the second (acc=2)
    inputs = onp.zeros((B, T), onp.int32)
    inputs[0, 1:] = want[0, :-1]
    inputs[1, 1:] = (want[1, :-1] + 1) % V
    inputs[2, 1] = want[2, 0]
    inputs[2, 2:] = (want[2, 1:-1] + 1) % V
    toks, acc = spec_verify_tokens(logits, jnp.asarray(inputs), temps,
                                   topks, topps, seeds, counters)
    assert (onp.asarray(toks) == want).all()
    assert onp.asarray(acc).tolist() == [T, 1, 2]


# ------------------------------------------------------- engine token-exact
@pytest.mark.parametrize("page_size", [8, 16])
def test_spec_token_exact_mixed_sampling_gpt(gpt_model, page_size):
    """speculate=K output must be IDENTICAL to speculate=0 for a mix of
    greedy, temperature-sampled and filtered requests, whether a verify
    round crosses a page boundary often (8) or rarely (16) —
    the sampled rows are the sharp edge: the verify recomputes the same
    categorical draw from the same stateless fold_in key."""
    prompts = _prompts(6, seed=1)
    reqs = [dict(temperature=(0.0 if i % 2 == 0 else 0.9),
                 top_k=(5 if i % 3 == 0 else 0), seed=i * 11)
            for i in range(6)]
    kw = dict(page_size=page_size)
    base, _ = _serve_all(gpt_model, prompts, 9, reqs, max_batch_size=2,
                         max_len=48, **kw)
    spec, st = _serve_all(gpt_model, prompts, 9, reqs, max_batch_size=2,
                          max_len=48, speculate=4, **kw)
    assert spec == base
    assert st["spec"]["rounds"] > 0
    assert st["spec"]["drafted"] > 0


def test_spec_eos_mid_round(gpt_model):
    """A row whose EOS lands inside an accepted draft run must stop
    there — tokens past the EOS in the verify round are discarded, and
    the result matches the non-speculative engine exactly."""
    prompts = _prompts(3, seed=2)
    base, _ = _serve_all(gpt_model, prompts, 10, max_batch_size=2,
                         max_len=48, page_size=8)
    # pick an eos that actually occurs mid-stream for at least one row
    eos = next((t for out in base for t in out[:-1]), None)
    reqs = [dict(eos_token_id=int(eos))] * 3
    base_eos, _ = _serve_all(gpt_model, prompts, 10, reqs,
                             max_batch_size=2, max_len=48, page_size=8)
    spec_eos, _ = _serve_all(gpt_model, prompts, 10, reqs,
                             max_batch_size=2, max_len=48, page_size=8,
                             speculate=5)
    assert spec_eos == base_eos


def test_spec_composes_with_prefix_cache_and_chunked_prefill(gpt_model):
    """Shared-prefix structured traffic through a small paged pool:
    speculation must compose with COW prefix mapping and chunked
    prefill without changing a token."""
    rng = onp.random.RandomState(4)
    shared = rng.randint(1, 60, size=12).astype(onp.int32)
    prompts = [onp.concatenate([shared,
                                rng.randint(1, 60, size=3 + i)
                                .astype(onp.int32)])
               for i in range(4)]
    kw = dict(max_batch_size=2, max_len=64, page_size=8,
              prefill_chunk=8, prefix_cache=True)
    base, _ = _serve_all(gpt_model, prompts, 8, **kw)
    spec, st = _serve_all(gpt_model, prompts, 8, speculate=4, **kw)
    assert spec == base
    assert st["pages"]["prefix_hits"] >= 1      # the composition is real


def test_spec_with_quantized_paged_decode():
    """The whole stack at once: int8-quantized model + paged pool +
    speculation — token-exact vs the non-speculative paged engine."""
    from mxnet_tpu.contrib.quantization import quantize_net
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                             num_heads=2, max_position_embeddings=128,
                             dropout=0.0))
    net.initialize()
    net(np.array(onp.zeros((1, 4), "int32")))
    quantize_net(net, calib_mode="none")
    prompts = _prompts(4, seed=6)
    base, _ = _serve_all(net, prompts, 8, max_batch_size=2,
                         max_len=48, page_size=8)
    spec, _ = _serve_all(net, prompts, 8, max_batch_size=2,
                         max_len=48, page_size=8,
                         speculate=4)
    assert spec == base


def test_spec_parity_llama(gpt_model):
    """The llama family (GQA + RoPE, per-layer caches) through paged
    speculative decode: token-exact vs speculate=0."""
    mx.random.seed(0)
    cfg = LlamaConfig(vocab_size=32, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      dtype=onp.float32)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    prompts = _prompts(3, vocab=30, seed=7)
    base, _ = _serve_all(net, prompts, 6, max_batch_size=2, max_len=32,
                         page_size=8)
    spec, _ = _serve_all(net, prompts, 6, max_batch_size=2, max_len=32,
                         page_size=8, speculate=3)
    assert spec == base


# --------------------------------------------------------- router end-to-end
def test_router_serves_paged_quantized_speculative_no_recompiles():
    """The acceptance smoke: a router fronting paged+int8+speculative
    replicas serves mixed traffic end-to-end with ZERO steady-state
    recompiles (no_recompile()-guarded) and speculation visibly active."""
    from mxnet_tpu import metrics
    from mxnet_tpu.analysis import guards
    from mxnet_tpu.contrib.quantization import quantize_net
    was = metrics.enabled()
    metrics.enable()
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                             num_heads=2, max_position_embeddings=128,
                             dropout=0.0))
    net.initialize()
    net(np.array(onp.zeros((1, 4), "int32")))
    quantize_net(net, calib_mode="none")
    eng = InferenceEngine(net, max_batch_size=2, max_len=48, page_size=8,
                          speculate=4).start()
    eng.warmup()
    rounds0 = metrics.get_sample_value("mxnet_spec_rounds_total") or 0
    prompts = _prompts(5, seed=8)
    try:
        with HTTPFrontend(eng, port=0) as fe:
            router = Router([fe.url], health_interval=0.2).start()
            try:
                with guards.no_recompile(block="serve"):
                    for i, p in enumerate(prompts):
                        doc = router.generate({
                            "input_ids": [int(t) for t in p],
                            "max_new_tokens": 6,
                            "temperature": 0.7 * (i % 2), "seed": i})
                        assert doc["status"] == "ok", doc
                        assert len(doc["generated_ids"]) == 6
            finally:
                router.stop()
        rounds = metrics.get_sample_value("mxnet_spec_rounds_total") or 0
        assert rounds > rounds0           # speculation actually served
        rate = metrics.get_sample_value("mxnet_spec_acceptance_rate")
        assert rate is not None and 0.0 <= rate <= 1.0
    finally:
        eng.shutdown()
        if not was:
            metrics.disable()


# ----------------------------------------------------------- knobs/validation
def test_spec_validation(gpt_model):
    with pytest.raises(MXNetError, match="speculate"):
        InferenceEngine(gpt_model, max_len=32, speculate=1)
    with pytest.raises(MXNetError, match="mutually exclusive"):
        InferenceEngine(gpt_model, max_len=32, speculate=4, multi_token=2)
    with pytest.raises(MXNetError, match="spec_lookup"):
        InferenceEngine(gpt_model, max_len=32, speculate=4, spec_lookup=0)
    # headroom: the verify may write speculate-1 rows past the budget
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32,
                          speculate=4)
    with pytest.raises(MXNetError, match="headroom"):
        eng.start().submit(list(range(1, 25)), 6)
    eng.shutdown()


def test_spec_knobs_are_tunable(gpt_model):
    """The PR-13 contract: speculate/spec_draft/spec_lookup are born
    tunable — defaults pinned, an activated serve-site config applies,
    an explicit argument outranks it."""
    from mxnet_tpu.tune import config as tune
    assert tune.knob_default("serve_speculate") == 0
    assert tune.knob_default("serve_spec_draft") == 0
    assert tune.knob_default("serve_spec_lookup") == 4
    ctx = tune.serve_context(gpt_model, 2, 32)
    tune.activate(tune.SERVE_SITE, {"serve_speculate": 4,
                                    "serve_spec_lookup": 6}, ctx)
    try:
        eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32)
        assert eng.spec == 4 and eng._spec_lookup == 6
        # explicit argument outranks the tuned winner
        eng2 = InferenceEngine(gpt_model, max_batch_size=2, max_len=32,
                               speculate=0)
        assert eng2.spec == 0
        # invalid stored value (speculate=1) is dropped at lookup
        tune.invalidate()
        tune.activate(tune.SERVE_SITE, {"serve_speculate": 1}, ctx)
        eng3 = InferenceEngine(gpt_model, max_batch_size=2, max_len=32)
        assert eng3.spec == 0
    finally:
        tune.deactivate_all()


def test_tuned_spec_multitoken_conflict_degrades_not_crashes(gpt_model):
    """Merged mxtune winners can carry BOTH serve_multi_token>1 and
    serve_speculate>=2 in one cache entry; a default-constructed engine
    must degrade with a warning (PR-13: never a crashed constructor),
    and an explicit argument on either side wins over the tuned other."""
    import warnings as _w
    from mxnet_tpu.tune import config as tune
    ctx = tune.serve_context(gpt_model, 2, 32)
    tune.activate(tune.SERVE_SITE, {"serve_multi_token": 4,
                                    "serve_speculate": 4}, ctx)
    try:
        with _w.catch_warnings(record=True) as rec:
            _w.simplefilter("always")
            eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32)
        assert eng.spec == 0 and eng.K == 4     # conflict -> spec yields
        assert any("mutually exclusive" in str(r.message) for r in rec)
        with _w.catch_warnings(record=True):
            _w.simplefilter("always")
            eng2 = InferenceEngine(gpt_model, max_batch_size=2,
                                   max_len=32, speculate=6)
        assert eng2.spec == 6 and eng2.K == 1   # explicit spec wins
        # two EXPLICIT conflicting arguments stay a caller error
        with pytest.raises(MXNetError, match="mutually exclusive"):
            InferenceEngine(gpt_model, max_len=32, speculate=4,
                            multi_token=2)
    finally:
        tune.deactivate_all()


