"""Paged KV serving (mxnet_tpu/serve/paging + engine + router).

The tier-1 contracts of the engine's cache layout:

- ledger invariants: page lease/free accounting never leaks across slot
  refills, copy-on-write forks on the first divergent token, prefix-hash
  collisions fall back to full prefill;
- bitwise parity: greedy decode is token-identical to ``generate()``,
  which decodes through the contiguous cache — gpt, llama (per-layer and
  stacked-scan caches), ``multi_token=K``, prefix reuse, chunked
  prefill, preemption-resume;
- capacity: 4x the contiguous slot count served on the SAME pool bytes,
  with zero steady-state recompiles under the ``no_recompile()`` guard;
- fleet: the 2-replica router survives a drain + rejoin mid-traffic
  without a single failed request.
"""
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
from mxnet_tpu.serve import (HTTPFrontend, InferenceEngine, OutOfPages,
                             PagePool, Router, pages_for)


@pytest.fixture(scope="module")
def gpt_model():
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=32, hidden_size=32, num_layers=2,
                             num_heads=2, max_position_embeddings=128,
                             dropout=0.0))
    net.initialize()
    return net


def _prompts(n, lo=3, hi=13, vocab=30, seed=0):
    rng = onp.random.RandomState(seed)
    return [rng.randint(1, vocab, size=rng.randint(lo, hi)).astype(onp.int32)
            for _ in range(n)]


def _serve_all(net, prompts, max_new, seeds=None, **engine_kwargs):
    """Run every prompt through one engine; returns the generated id
    lists (every request must succeed)."""
    eng = InferenceEngine(net, **engine_kwargs).start()
    try:
        handles = [eng.submit(p, max_new,
                              seed=(seeds[i] if seeds else 0))
                   for i, p in enumerate(prompts)]
        outs = []
        for h in handles:
            r = h.result(300)
            assert r.status == "ok", (r.status, r.error)
            outs.append(list(r.generated_ids))
        return outs
    finally:
        eng.shutdown()


def _reference(net, prompt, max_new):
    ref = generate(net, np.array(prompt[None, :]), max_new).asnumpy()[0]
    return list(ref[len(prompt):])


# ------------------------------------------------------------ pool ledger
def test_pool_lease_free_accounting_across_refills():
    """Random lease/release churn across slots must keep refcounts, the
    free list, and the tables consistent — and return every page once
    the slots drain (the never-leaks-across-refills invariant)."""
    pool = PagePool(num_pages=16, page_size=4, max_len=16, slots=4,
                    prefix_cache=False)
    rng = onp.random.RandomState(0)
    live = set()
    for _ in range(200):
        s = int(rng.randint(4))
        if s in live and rng.rand() < 0.4:
            pool.release(s)          # slot refill: retire + readmit
            live.discard(s)
        else:
            try:
                pool.lease(s, int(rng.randint(1, 17)))
                live.add(s)
            except OutOfPages:
                pool.release(s)
                live.discard(s)
        pool.check_consistent()
    pool.release_all()
    pool.check_consistent()
    assert pool.pages_in_use() == 0
    assert pool.free_pages() == 16
    assert pool.leases == pool.frees + 0   # every lease returned


def test_pool_lease_all_or_nothing():
    """A lease the pool cannot satisfy must leave the slot's table
    untouched (no partial grant to unwind)."""
    pool = PagePool(num_pages=4, page_size=4, max_len=16, slots=2,
                    prefix_cache=False)
    pool.lease(0, 12)                       # 3 of 4 pages
    before = pool.table(1).copy()
    with pytest.raises(OutOfPages):
        pool.lease(1, 8)                    # needs 2, only 1 free
    assert (pool.table(1) == before).all()
    pool.check_consistent()
    with pytest.raises(mx.MXNetError, match="max_len"):
        pool.lease(1, 17)


def test_pool_prefix_publish_match_and_cow_fork():
    """Publish a prompt, match it from a second slot, and verify the
    shared pages fork on the first write (copy-on-write bookkeeping)."""
    pool = PagePool(num_pages=8, page_size=4, max_len=16, slots=2)
    toks = list(range(1, 11))               # 10 tokens: 2 full + 1 tail
    pool.lease(0, len(toks))
    pool.insert_prefix(0, toks)
    pool.check_consistent()

    # same prompt again: the full pages map (the partial tail entry is
    # capped at len - 1, so the last span re-prefills)
    pages, matched = pool.match_prefix(toks)
    assert matched == 8
    assert len(pages) == 2
    pool.map_prefix(1, pages, matched)
    pool.check_consistent()
    # slot 0's tail page is pinned by the cache (ref 2): its first
    # decode write past the published prompt must fork — the
    # first-divergent-token COW
    shared = pool.writable(0, 10, 11)
    assert [ti for ti, _ in shared] == [2]
    src, dst = pool.fork(0, 2)
    assert src != dst
    assert pool.cow_forks == 1
    assert pool.writable(0, 10, 11) == []   # now exclusively owned
    pool.check_consistent()

    # divergence mid-prefix only maps the page-boundary prefix
    div = toks[:6] + [99, 98, 97]
    pages, matched = pool.match_prefix(div)
    assert matched == 4                     # page 0 only (page 1 differs)
    pool.release_all()
    pool.check_consistent()
    # cache pins survive slot release; clearing them empties the pool
    pool.clear_prefix_cache()
    assert pool.pages_in_use() == 0


def test_pool_hash_collision_falls_back_to_prefill():
    """A chain-key collision (same hash, different tokens) must stop the
    match walk — never serve another prompt's KV pages."""
    pool = PagePool(num_pages=8, page_size=4, max_len=16, slots=2)
    pool._hash = lambda toks: 7             # every prefix collides
    a = [1, 2, 3, 4, 5]
    b = [9, 8, 7, 6, 5]
    pool.lease(0, len(a))
    pool.insert_prefix(0, a)
    pages, matched = pool.match_prefix(b)
    assert matched == 0 and pages == []
    assert pool.prefix_collisions > 0
    # the colliding prompt's own publish still works (token comparison)
    pool.lease(1, len(b))
    pool.insert_prefix(1, b)
    pages, matched = pool.match_prefix(b)
    assert matched == len(b) - 1
    pool.check_consistent()


def test_pool_eviction_reclaims_cache_only_pages():
    """Pool exhaustion evicts LRU prefix entries (cache-only refs free
    their pages) before giving up."""
    pool = PagePool(num_pages=4, page_size=4, max_len=16, slots=2)
    toks = list(range(1, 9))                # 2 pages
    pool.lease(0, len(toks))
    pool.insert_prefix(0, toks)
    pool.release(0)                         # pages now cache-only
    assert pool.pages_in_use() == 2
    pool.lease(1, 16)                       # needs all 4 pages
    assert pool.prefix_evictions == 2
    assert pool.match_prefix(toks) == ([], 0)
    pool.check_consistent()


def test_pages_for():
    assert pages_for(0, 8) == 0
    assert pages_for(1, 8) == 1
    assert pages_for(8, 8) == 1
    assert pages_for(9, 8) == 2


# ------------------------------------------------------ engine bitwise parity
@pytest.mark.slow
def test_paged_vs_contiguous_parity_gpt(gpt_model):
    """Greedy decode through the on-device multi-token loop must be
    token-identical to generate(), the contiguous reference. (K=1
    output is asserted against the same reference by the
    prefix/chunked/preemption tests below, so only the K>1 engine is
    built here — tier-1 budget.)"""
    prompts = _prompts(4, seed=1)
    paged = _serve_all(gpt_model, prompts, 8, max_batch_size=2,
                       max_len=32, page_size=8,
                       multi_token=3)
    assert paged == [_reference(gpt_model, p, 8) for p in prompts]


@pytest.mark.slow
def test_paged_fused_parity_llama():
    """The llama half of the paged-fused contract: a tie_embeddings
    llama with an int8-quantized tied head (quantize_net sets
    ``_q_lm_head``, so ``head_weights()`` feeds the fused LM-head
    sampler through ``forward_cached_paged_hidden``) decoded through
    the on-device multi-token loop over the PAGED pool must be
    token-identical to generate() on the same net at K∈{1,4} — tier-1,
    per-layer decoder (llama has no fused block kernel; its fused
    decode surface is the head + the device loop)."""
    from mxnet_tpu.contrib.quantization import quantize_net
    mx.random.seed(0)
    cfg = LlamaConfig(vocab_size=32, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      dtype=onp.float32, tie_embeddings=True)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    net(np.array(onp.zeros((1, 4), "int32")))
    # int8 weight-only everywhere incl. the tied head — the engines and
    # the reference below run this same quantized net, so the comparison
    # isolates the paged fused-head/multi-token machinery, not quantization
    quantize_net(net, calib_mode="none", quantize_tied_head=True)
    assert net.head_weights() is not None
    prompts = _prompts(3, vocab=30, seed=5)
    base = [_reference(net, p, 6) for p in prompts]
    for K in (1, 4):
        paged = _serve_all(net, prompts, 6, max_batch_size=2, max_len=32,
                           page_size=8, multi_token=K)
        assert paged == base, f"multi_token={K}"


@pytest.mark.slow
def test_paged_fused_parity_llama_int4():
    """The int4 llama surface: bits=4 packs the tied head as nibble
    codes (``head_weights()`` hands the uint8 table to the fused
    sampler), and paged multi-token decode stays token-identical to
    generate() — same contract as the int8 test one up, on the
    quartered weight stream."""
    import jax.numpy as jnp
    from mxnet_tpu.contrib.quantization import quantize_net
    mx.random.seed(0)
    cfg = LlamaConfig(vocab_size=32, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      dtype=onp.float32, tie_embeddings=True)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    net(np.array(onp.zeros((1, 4), "int32")))
    quantize_net(net, calib_mode="none", quantize_tied_head=True, bits=4)
    assert net.head_weights()[0].dtype == jnp.uint8
    prompts = _prompts(3, vocab=30, seed=5)
    base = [_reference(net, p, 6) for p in prompts]
    # K=4 is the full surface (fused int4 head + device loop); K=1 adds
    # only engine builds (the int8 twin above covers it)
    paged = _serve_all(net, prompts, 6, max_batch_size=2, max_len=32,
                       page_size=8, multi_token=4)
    assert paged == base


@pytest.mark.slow
def test_paged_parity_llama_per_layer_and_stacked():
    """The paged protocol covers llama's per-layer GQA caches AND the
    stacked-scan caches ([layers, pages, ...] pools, shared table)."""
    prompts = _prompts(4, vocab=30, seed=2)
    for stacked in (False, True):
        mx.random.seed(0)
        cfg = LlamaConfig(vocab_size=32, hidden_size=32,
                          intermediate_size=64, num_layers=2, num_heads=4,
                          num_kv_heads=2, dtype=onp.float32,
                          stacked=stacked)
        net = LlamaForCausalLM(cfg)
        net.initialize()
        base = [_reference(net, p, 6) for p in prompts]
        for K in (1, 4):
            paged = _serve_all(net, prompts, 6, max_batch_size=2,
                               max_len=32, page_size=8,
                               multi_token=K)
            assert paged == base, f"stacked={stacked} multi_token={K}"


@pytest.mark.slow
def test_prefix_reuse_parity_and_cow(gpt_model):
    """Repeated system prompts must map their cached prefix pages
    (prefix hits, tokens saved) and still emit exactly generate()'s
    tokens — the shared tail page forks on the first divergent token."""
    rng = onp.random.RandomState(3)
    sysp = rng.randint(1, 30, size=18).astype(onp.int32)
    prompts = [onp.concatenate([sysp,
                                rng.randint(1, 30, size=3 + i)
                                .astype(onp.int32)])
               for i in range(5)]
    eng = InferenceEngine(gpt_model, max_batch_size=1, max_len=64,
                          page_size=8).start()
    try:
        outs = []
        for i, p in enumerate(prompts):     # sequential: prefix publishes
            r = eng.submit(p, 6).result(300)
            assert r.status == "ok"
            outs.append(list(r.generated_ids))
        stats = eng.stats()["pages"]
        eng._pages.check_consistent()
    finally:
        eng.shutdown()
    assert stats["prefix_hits"] >= 4
    assert stats["prefix_tokens_saved"] > 0
    assert stats["cow_forks"] > 0           # first divergent token forked
    for p, out in zip(prompts, outs):
        assert out == _reference(gpt_model, p, 6)


@pytest.mark.slow
def test_prefix_collision_engine_fallback(gpt_model):
    """With the chain hash degraded to a constant, every lookup collides:
    the engine must detect the token mismatch, prefill fully, and still
    match the reference output. (The ledger-level collision contract
    stays tier-1 in test_pool_hash_collision_falls_back_to_prefill.)"""
    prompts = _prompts(3, lo=6, hi=12, seed=4)
    eng = InferenceEngine(gpt_model, max_batch_size=1, max_len=32,
                          page_size=8).start()
    eng._pages._hash = lambda toks: 13
    try:
        outs = []
        for p in prompts:
            r = eng.submit(p, 6).result(300)
            assert r.status == "ok"
            outs.append(list(r.generated_ids))
        stats = eng.stats()["pages"]
        eng._pages.check_consistent()
    finally:
        eng.shutdown()
    assert stats["prefix_collisions"] > 0
    assert stats["prefix_hits"] == 0
    for p, out in zip(prompts, outs):
        assert out == _reference(gpt_model, p, 6)


@pytest.mark.slow
def test_chunked_prefill_interleaves_with_decode(gpt_model):
    """A near-max_len prompt prefills in page-sized chunks; a short
    request admitted alongside keeps decoding (its inter-token gap stays
    bounded) and both outputs match the reference."""
    from mxnet_tpu import metrics
    was = metrics.enabled()
    metrics.enable()
    rng = onp.random.RandomState(5)
    long_p = rng.randint(1, 30, size=50).astype(onp.int32)
    short_p = rng.randint(1, 30, size=4).astype(onp.int32)
    chunks0 = metrics.get_sample_value(
        "mxnet_serve_page_prefill_chunks_total") or 0
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=64,
                          page_size=8).start()
    try:
        h_short = eng.submit(short_p, 12)
        h_long = eng.submit(long_p, 6)
        r_short, r_long = h_short.result(300), h_long.result(300)
        assert r_short.status == "ok" and r_long.status == "ok"
        chunks = (metrics.get_sample_value(
            "mxnet_serve_page_prefill_chunks_total") or 0) - chunks0
        assert chunks >= 5                  # 50 tokens / 8-token chunks
        assert list(r_long.generated_ids) == _reference(gpt_model,
                                                        long_p, 6)
        assert list(r_short.generated_ids) == _reference(gpt_model,
                                                         short_p, 12)
    finally:
        eng.shutdown()
        if not was:
            metrics.disable()


def test_preemption_resume_is_exact(gpt_model):
    """Pool exhaustion preempts a slot (release + requeue); the stateless
    sampling streams make the resume token-exact."""
    prompts = [onp.random.RandomState(10 + i).randint(1, 30, size=18)
               .astype(onp.int32) for i in range(3)]
    # 2 slots but pages for ~1.5 requests: preemption is forced
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=64,
                          page_size=8, num_pages=8,
                          prefix_cache=False).start()
    try:
        handles = [eng.submit(p, 18, seed=i)
                   for i, p in enumerate(prompts)]
        results = [h.result(300) for h in handles]
        stats = eng.stats()
        eng._pages.check_consistent()
    finally:
        eng.shutdown()
    assert stats["preemptions"] > 0
    for p, r in zip(prompts, results):
        assert r.status == "ok"
        assert list(r.generated_ids) == _reference(gpt_model, p, 18)


# ------------------------------------------- pools in place (PR 31)
def _tiny_llama(stacked):
    mx.random.seed(0)
    net = LlamaForCausalLM(LlamaConfig(
        vocab_size=32, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, dtype=onp.float32, stacked=stacked))
    net.initialize()
    return net


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["per-layer", "stacked"])
def test_pool_is_rows_of_all_kv_heads(stacked):
    """One row a token, ``kv_heads * head_dim`` wide (16 here: grouped
    heads, no multiple of 128 lanes); the stacked decoder puts its layers
    in front and the engine still finds the page axis."""
    net = _tiny_llama(stacked)
    spec = net.cache_spec_paged(5, 8)
    lead = (2,) if stacked else ()
    assert [s for s, _ in spec] == [lead + (5, 8, 2 * 8)] * (
        2 if stacked else 4)
    eng = InferenceEngine(net, max_batch_size=2, max_len=32, page_size=8,
                          num_pages=6)
    assert eng._paxes == [len(lead)] * len(spec)
    assert eng._pools[0].shape == lead + (7, 8, 16)
    assert [a.shape for a in eng._page_payload_spec()] == [
        lead + (1, 8, 16)] * len(spec)


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["per-layer", "stacked"])
def test_paged_llama_grouped_heads_match_generate(stacked):
    """Grouped K/V heads through the row-shaped pools, per layer and under
    the stacked decoder's scan: greedy tokens of ``generate()``."""
    net = _tiny_llama(stacked)
    prompts = _prompts(3, vocab=30, seed=2)
    served = _serve_all(net, prompts, 6, max_batch_size=2, max_len=32,
                        page_size=8)
    assert served == [_reference(net, p, 6) for p in prompts]


IN_PLACE_ENGINES = {
    "plain": dict(),
    "chunked": dict(prefill_chunk=8, max_len=64),
    "multi-token-2": dict(multi_token=2),
    "speculate-4": dict(speculate=4),
    "grammar": dict(grammar=True),
    "stacked-llama": dict(),
}


@pytest.mark.parametrize("name", list(IN_PLACE_ENGINES))
def test_every_program_updates_the_pools_in_place(gpt_model, name):
    """After ``warmup()`` every program that writes the pools has been
    built, and the least of their aliased bytes is all of the pools': no
    program copies a pool. The examples ran on the live pools, which the
    programs were given: the engine holds what they returned."""
    net = _tiny_llama(True) if name == "stacked-llama" else gpt_model
    kw = dict(max_batch_size=2, max_len=32, page_size=8)
    kw.update(IN_PLACE_ENGINES[name])
    eng = InferenceEngine(net, **kw)
    assert eng.stats()["pool_bytes_in_place"] == 0      # nothing built yet
    assert eng.stats()["pool_copies"] == 0
    given = eng._pools
    eng.warmup()
    stats = eng.stats()
    if given[0].is_deleted():       # the backend donates (the CPU's does)
        assert stats["pool_bytes_in_place"] == stats["kv_bytes"] > 0
    # the first program of each kind was read back: none relays a pool out
    assert type(stats["pool_copies"]) is int and stats["pool_copies"] == 0
    assert not any(p.is_deleted() for p in eng._pools)
    # the examples wrote the sink page only
    for p, ax in zip(eng._pools, eng._paxes):
        live = onp.take(onp.asarray(p, onp.float32),
                        range(p.shape[ax] - 1), axis=ax)
        assert not live.any()


# optimized HLO as the described v5e gives it, cut to the lines that matter:
# a pool relaid out for a scatter and back (heads ahead of rows), the same pool
# staged in another memory space as declared, and an array of another size
_POOL_HLO = {
    "relaid": ("%kp.1 = bf16[9,2,4,128]{3,2,1,0:T(8,128)(2,1)} parameter(3)\n"
               "%copy.1 = bf16[9,2,4,128]{3,1,2,0:T(2,128)(2,1)S(1)} "
               "copy(%kp.1), sharding={replicated}\n"
               "%fusion.8 = bf16[9,2,4,128]{3,1,2,0:T(2,128)(2,1)S(1)} "
               "fusion(%copy.1), kind=kCustom\n"
               "ROOT %copy.2 = bf16[9,2,4,128]{3,2,1,0:T(8,128)(2,1)} "
               "copy(%fusion.8)", 2),
    "flat": ("%p = bf16[9,8,256]{2,1,0:T(8,128)(2,1)} parameter(0)\n"
             "%copy-start = (bf16[72,2,128]{2,1,0:T(2,128)(2,1)}, "
             "bf16[9,8,256]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) "
             "copy-start(%p)", 1),
    "staged": ("%f = bf16[9,8,256]{2,1,0:T(8,128)(2,1)S(1)} fusion(%p)\n"
               "%copy-start.1 = (bf16[9,8,256]{2,1,0:T(8,128)(2,1)}, "
               "bf16[9,8,256]{2,1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) "
               "copy-start(%f)\n"
               "%copy-done.1 = bf16[9,8,256]{2,1,0:T(8,128)(2,1)} "
               "copy-done(%copy-start.1)\n"
               "%q = bf16[9,8,256]{2,1,0:T(8,128)(2,1)} parameter(1)\n"
               "%copy.3 = bf16[9,8,256]{2,1,0:T(8,128)(2,1)S(1)} copy(%q)",
               0),
    "other_size": ("%a = bf16[9,4,128]{2,1,0} parameter(0)\n"
                   "%copy.4 = bf16[9,4,128]{1,2,0} copy(%a)", 0),
}


@pytest.mark.parametrize("case", list(_POOL_HLO))
def test_pool_copies_count_what_moves_a_pool_into_another_layout(case):
    """``count_pool_copies`` (``stats()["pool_copies"]``) counts a copy whose
    result holds as many elements as a pool, laid out otherwise than its
    operand: relaid, or flattened for a scatter; not a copy that only moves
    the pool to another memory space, nor one of another size."""
    from mxnet_tpu.serve.engine import count_pool_copies
    text, want = _POOL_HLO[case]
    assert count_pool_copies(text, [(9, 2, 4, 128), (9, 8, 256)]) == want


@pytest.mark.parametrize("running", [False, True],
                         ids=["stopped", "running"])
def test_second_warmup_then_fork_and_inject_serve_identically(gpt_model,
                                                              running):
    """``warmup()`` twice (on a running engine the ladder runs on the
    loop, between ticks), then a COW fork of a shared prefix and an
    injected page: no program is handed an array that an earlier one
    was given, and the tokens are ``generate()``'s."""
    rng = onp.random.RandomState(5)
    shared = rng.randint(1, 30, size=16).astype(onp.int32)    # two pages
    a = onp.concatenate([shared, rng.randint(1, 30, size=3)]).astype(
        onp.int32)
    b = onp.concatenate([shared, rng.randint(1, 30, size=5)]).astype(
        onp.int32)
    kw = dict(max_batch_size=2, max_len=48, page_size=8)
    src = InferenceEngine(gpt_model, **kw)
    dst = InferenceEngine(gpt_model, **kw)
    if running:
        src.start(), dst.start()
    src.warmup()
    src.warmup()
    dst.warmup()
    if not running:
        src.start(), dst.start()
    try:
        ra = src.generate(a, 6)
        src.warmup()                        # mid-life, pages leased
        rb = src.generate(b, 6)             # maps the prefix, forks its tail
        assert src.stats()["pages"]["prefix_hits"] >= 1
        doc = src.export_pages(a)
        got = dst.import_pages(doc)
        assert got["adopted"] >= 2 and got["verify_failures"] == 0
        dst.warmup()
        rc = dst.generate(a, 6)
        assert dst.stats()["pages"]["prefix_hits"] >= 1
    finally:
        src.shutdown()
        dst.shutdown()
    assert list(ra.generated_ids) == _reference(gpt_model, a, 6)
    assert list(rb.generated_ids) == _reference(gpt_model, b, 6)
    assert list(rc.generated_ids) == list(ra.generated_ids)


def test_a_program_that_fails_with_the_pools_closes_the_engine(gpt_model):
    """A dispatch that fails after the program took the pools leaves
    deleted arrays behind: the engine fails its requests with that
    reason and closes; it does not serve on."""
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32,
                          page_size=8).start()
    try:
        assert eng.generate(onp.arange(1, 6), 3).status == "ok"
        real = eng._get_step(1)

        def takes_the_pools_and_fails(values, pools, *rest):
            for p in pools:
                p.delete()
            raise RuntimeError("injected failure after donation")

        eng._step_fns[1] = takes_the_pools_and_fails
        with pytest.warns(UserWarning):
            r = eng.submit(onp.arange(1, 6), 3).result(120)
        assert r.status == "error" and "injected failure" in r.error
        eng._step_fns[1] = real
        deadline = time.time() + 30
        while eng.stats()["running"] and time.time() < deadline:
            time.sleep(0.01)
        assert not eng.stats()["running"]
        with pytest.raises(mx.MXNetError):
            eng.submit(onp.arange(1, 6), 3)
    finally:
        eng.shutdown()


# greedy tokens of the tiny GPT (``gpt_model``), as the parent commit of
# PR 31 served them from pools of [pages, heads, page_size, head_dim]
PARENT_TOKENS = {
    "prefill-decode": (
        (4, 3, 13, 0), 10, dict(max_batch_size=4, max_len=64),
        [[22, 22, 22, 28, 6, 6, 6, 6, 6, 6],
         [25, 25, 25, 25, 25, 25, 25, 25, 25, 13],
         [15, 15, 12, 12, 12, 28, 28, 28, 6, 6],
         [17, 7, 7, 13, 13, 13, 13, 13, 13, 13]]),
    "chunked-prefill": (
        (3, 30, 50, 1), 8,
        dict(max_batch_size=2, max_len=96, prefill_chunk=16),
        [[21, 21, 21, 1, 1, 22, 22, 22], [24, 16, 16, 16, 16, 16, 16, 16],
         [1, 1, 1, 22, 22, 22, 22, 22]]),
    "speculate-4": (
        (3, 5, 20, 2), 12, dict(max_batch_size=4, max_len=64, speculate=4),
        [[22, 13, 13, 13, 13, 13, 13, 13, 13, 13, 13, 13],
         [0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 16, 16],
         [19, 19, 19, 5, 5, 5, 5, 5, 5, 5, 16, 16]]),
    "multi-token-2": (
        (3, 5, 20, 4), 9, dict(max_batch_size=4, max_len=64, multi_token=2),
        [[16, 16, 16, 16, 16, 16, 16, 16, 16],
         [13, 13, 13, 13, 13, 13, 13, 13, 13],
         [22, 22, 22, 22, 22, 22, 22, 22, 28]]),
    "preemption-resume": (
        (3, 18, 19, 3), 18,
        dict(max_batch_size=2, max_len=64, num_pages=8, prefix_cache=False),
        [[11, 11, 11, 11, 11, 11, 11, 11, 0, 16, 16, 16, 16, 16, 16, 16, 16,
          16],
         [15, 15, 15, 1, 1, 1, 1, 1, 1, 6, 6, 22, 22, 22, 22, 22, 16, 16],
         [11, 11, 11, 11, 11, 11, 11, 11, 0, 16, 16, 16, 16, 16, 16, 16, 16,
          16]]),
}


@pytest.mark.parametrize("path", list(PARENT_TOKENS))
def test_greedy_tokens_are_the_parents(gpt_model, path):
    """The pool's shape and the donation change where bytes lie, not one
    product or sum: every serving path emits the parent's tokens."""
    (n, lo, hi, seed), new, kw, want = PARENT_TOKENS[path]
    rng = onp.random.RandomState(seed)
    prompts = [rng.randint(1, 30, size=rng.randint(lo, hi)).astype(onp.int32)
               for _ in range(n)]
    eng = InferenceEngine(gpt_model, page_size=8, **kw).start()
    try:
        handles = [eng.submit(p, new, seed=i) for i, p in enumerate(prompts)]
        results = [h.result(300) for h in handles]
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert [r.status for r in results] == ["ok"] * n
    assert [list(r.generated_ids) for r in results] == want
    if path == "preemption-resume":
        assert stats["preemptions"] > 0


@pytest.mark.slow
def test_page_accounting_clean_after_mixed_traffic(gpt_model):
    """After deadline/cancel/success churn the pool must hold ZERO leased
    pages (nothing leaks across slot refills) and zero prefix pins with
    the cache off."""
    prompts = _prompts(10, seed=6)
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32,
                          page_size=8,
                          prefix_cache=False).start()
    try:
        handles = [eng.submit(p, 6 + (i % 5), timeout_s=(
            0.001 if i % 4 == 3 else None))
            for i, p in enumerate(prompts)]
        handles[1].cancel()
        for h in handles:
            h.result(300)
        deadline = time.perf_counter() + 30
        while eng.stats()["slots_in_use"] and time.perf_counter() < deadline:
            time.sleep(0.01)
        eng._pages.check_consistent()
        assert eng._pages.pages_in_use() == 0
    finally:
        eng.shutdown()


# ------------------------------------------------------------ capacity
def test_4x_concurrency_on_contiguous_hbm_budget(gpt_model):
    """The acceptance demo: a pool holding EXACTLY the contiguous
    4-slot x 32-token footprint (16 pages x 8) serves 16 concurrent
    requests — 4x the slots — with zero recompiles after warmup and
    token-exact output."""
    from mxnet_tpu.analysis import guards
    from mxnet_tpu import metrics
    was = metrics.enabled()
    metrics.enable()
    contiguous_rows = 4 * 32
    prompts = _prompts(16, lo=3, hi=6, seed=7)
    eng = InferenceEngine(gpt_model, max_batch_size=16, max_len=32,
                          page_size=8,
                          num_pages=contiguous_rows // 8,
                          prefix_cache=False, max_queue_depth=32).start()
    try:
        assert eng.stats()["kv_bytes"] == (
            # pool bytes == contiguous bytes + one sink page
            (contiguous_rows + 8) * 2 * 2 * 32 * 4)
        eng.warmup()
        with guards.no_recompile(block="serve"):
            # submit ALL 16 before waiting (client threads would stagger
            # admissions under an unlucky scheduler and flake max_active)
            handles = [eng.submit(prompts[i], 3, seed=i)
                       for i in range(16)]
            results = [h.result(300) for h in handles]
        stats = eng.stats()
    finally:
        eng.shutdown()
        if not was:
            metrics.disable()
    assert all(r.status == "ok" for r in results)
    assert stats["max_active"] >= 12        # ~4x the 4 contiguous slots
    for p, r in zip(prompts, results):
        assert list(r.generated_ids) == _reference(gpt_model, p, 3)


# ------------------------------------------------------------ drain + router
def test_http_drain_endpoint_and_healthz_pages(gpt_model):
    """POST /drain stops admission immediately (503 for new submits, the
    router's failover signal) while in-flight requests finish; /healthz
    carries the page occupancy + load the router keys on."""
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32,
                          page_size=8).start()
    with HTTPFrontend(eng, port=0) as fe:
        doc = json.loads(urllib.request.urlopen(
            fe.url + "/healthz", timeout=10).read())
        assert doc["ok"] and doc["paged"]
        assert doc["pages"] == eng._pages.num_pages
        assert "pages_in_use" in doc and "load" in doc

        body = json.dumps({"input_ids": [1, 2, 3],
                           "max_new_tokens": 4}).encode()

        def post(path, data):
            req = urllib.request.Request(
                fe.url + path, data=data,
                headers={"Content-Type": "application/json"})
            return urllib.request.urlopen(req, timeout=60)

        def inflight_post():
            try:
                post("/generate", body)
            except urllib.error.HTTPError:
                pass                        # raced the drain: bounced

        inflight = threading.Thread(target=inflight_post)
        inflight.start()
        doc = json.loads(post("/drain", b"{}").read())
        assert doc["draining"]
        inflight.join(60)
        # new submissions bounce with 503 until the drain finishes
        deadline = time.perf_counter() + 30
        while time.perf_counter() < deadline:
            try:
                post("/generate", body)
            except urllib.error.HTTPError as e:
                assert e.code == 503
                break
            time.sleep(0.01)
        else:
            raise AssertionError("drain never rejected a new submit")
    eng.shutdown()


@pytest.mark.slow
def test_router_drain_rejoin_no_failed_requests(gpt_model):
    """The fleet smoke: 2 in-process replicas behind the router, traffic
    flowing, one replica drained and restarted mid-stream — every request
    completes ok (failover + rejoin), and the router counters record the
    eject and the rejoin."""
    def boot(port=0):
        e = InferenceEngine(gpt_model, max_batch_size=2, max_len=32,
                            page_size=8).start()
        f = HTTPFrontend(e, port=port).start()
        return e, f

    eng0, fe0 = boot()
    eng1, fe1 = boot()
    port0 = fe0.address[1]
    router = Router([fe0.url, fe1.url], health_interval=0.05).start()
    prompts = _prompts(24, lo=3, hi=8, seed=8)
    failures = []
    done = []
    lock = threading.Lock()

    def client(i):
        doc = router.generate({"input_ids": [int(t) for t in prompts[i]],
                               "max_new_tokens": 4, "seed": i})
        with lock:
            (done if doc.get("status") == "ok" else failures).append(doc)

    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(24)]
        for t in threads[:8]:
            t.start()
        # drain replica 0 mid-traffic: its in-flight requests finish,
        # everything else fails over to replica 1
        router.drain(fe0.url)
        for t in threads[8:16]:
            t.start()
        # restart replica 0 on the SAME port: the health loop re-admits
        fe0.stop()
        eng0.shutdown()
        eng0, fe0 = boot(port0)
        deadline = time.perf_counter() + 30
        while time.perf_counter() < deadline:
            if router.stats()["backends"][fe0.url]["healthy"]:
                break
            time.sleep(0.05)
        else:
            raise AssertionError("drained replica never rejoined")
        for t in threads[16:]:
            t.start()
        for t in threads:
            t.join(120)
        stats = router.stats()
    finally:
        router.stop()
        for f in (fe0, fe1):
            f.stop()
        for e in (eng0, eng1):
            e.shutdown()
    assert not failures, failures
    assert len(done) == 24
    assert stats["ejects"] >= 1
    assert stats["rejoins"] >= 1
    assert stats["dispatches"] >= 24


def test_router_failover_and_no_backend_error(gpt_model):
    """Transport failure ejects a replica and retries on the next one;
    an empty rotation raises NoBackendError."""
    from mxnet_tpu.serve import NoBackendError
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=32,
                          page_size=8).start()
    fe = HTTPFrontend(eng, port=0).start()
    # second backend: a port nothing listens on
    dead = "http://127.0.0.1:1"
    router = Router([fe.url, dead], health_interval=0.05).start()
    try:
        doc = router.generate({"input_ids": [1, 2, 3],
                               "max_new_tokens": 3})
        assert doc["status"] == "ok"
        st = router.stats()
        assert not st["backends"][dead]["healthy"]
        router.drain(fe.url)
        with pytest.raises(NoBackendError):
            router.generate({"input_ids": [1, 2, 3],
                             "max_new_tokens": 3})
    finally:
        router.stop()
        fe.stop()
        eng.shutdown()
