"""Grammar-constrained decoding (mxnet_tpu/serve/grammar — "mxgrammar"):
regex -> DFA -> token automaton, JSON-schema lowering, mask-composition
edge cases, the content-addressed cache tiers, and the engine's
constrained-decode contracts (conformance BY CONSTRUCTION, speculative
composition, zero steady-state recompiles)."""
import json
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import MXNetError
from mxnet_tpu.models import GPTModel
from mxnet_tpu.models.gpt import GPTConfig
from mxnet_tpu.serve import (InferenceEngine, TokenGrammar,
                             clear_grammar_cache, compile_grammar,
                             schema_regex)

V = 128
EOS = 0


def _toks(s):
    return [ord(c) for c in s]


@pytest.fixture(scope="module")
def gpt_model():
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=V, hidden_size=32, num_layers=2,
                             num_heads=2, max_position_embeddings=128,
                             dropout=0.0))
    net.initialize()
    return net


# --------------------------------------------------------- automaton compile
def test_regex_compile_and_matches():
    g = compile_grammar("(?:ab|a[0-9]{2})", V)
    assert g.matches(_toks("ab"))
    assert g.matches(_toks("a07"))
    assert not g.matches(_toks("a"))          # prefix, not a full match
    assert not g.matches(_toks("ax"))
    assert not g.matches(_toks("a077"))
    # EOS-terminated sequences strip the terminator before matching
    assert g.matches(_toks("ab") + [EOS], eos_token_id=EOS)
    assert not g.matches([EOS], eos_token_id=EOS)


def test_schema_regex_lowering():
    assert schema_regex({"type": "boolean"}) == "(?:true|false)"
    assert schema_regex({"const": "hi"}) == '"hi"'
    # object properties emit in DECLARATION order, compact separators
    rx = schema_regex({"type": "object",
                       "properties": {"b": {"type": "null"},
                                      "a": {"type": "boolean"}}})
    assert rx == '\\{"b":null,"a":(?:true|false)\\}'
    g = compile_grammar({"enum": ["on", "off", 3]}, V)
    assert g.matches(_toks('"on"')) and g.matches(_toks("3"))
    assert not g.matches(_toks("on"))          # strings keep their quotes
    with pytest.raises(MXNetError, match="unsupported schema"):
        schema_regex({"type": "tuple"})


def test_schema_integer_is_canonical_and_unbounded():
    # the documented caveat: {"type": "integer"} admits ARBITRARY-length
    # digit strings (no canonical upper bound), so a token budget can
    # truncate mid-number — bounded schemas (enum/const/boolean) are the
    # ones whose completions always fit a max_new_tokens budget
    g = compile_grammar({"type": "integer"}, V)
    assert g.matches(_toks("0")) and g.matches(_toks("-17"))
    assert g.matches(_toks("9" * 64))          # unbounded by design
    assert not g.matches(_toks("007"))         # canonical: no leading zeros
    assert not g.matches(_toks("--1"))


def test_every_reachable_state_is_live_or_accepting():
    """The by-construction guarantee: after the coaccessible trim, every
    automaton state either continues by some vocab token or accepts (EOS
    legal) — the constrained mask can never be empty."""
    for source in ({"type": "object",
                    "properties": {"ok": {"type": "boolean"},
                                   "n": {"type": "integer"}}},
                   "(?:abc|a[x-z]{1,3})d?"):
        g = compile_grammar(source, V)
        for q in range(g.n_states):
            assert g.has_live_token(q) or g.is_accept(q), \
                f"dead state {q} survived the trim for {source!r}"


def test_max_states_cap_raises_loudly():
    with pytest.raises(MXNetError, match="serve_grammar_max_states"):
        compile_grammar("a{200}", V, max_states=8)


# ------------------------------------------------------- mask edge cases
def test_all_masked_rows_raise_diagnosable_error():
    import jax.numpy as jnp
    from mxnet_tpu.models.generation import filter_logits, sample_tokens
    from mxnet_tpu.models.generation import _fold_keys
    logits = jnp.zeros((2, V), jnp.float32)
    mask = onp.ones((2, V), bool)
    mask[1, :] = False                         # row 1: automaton dead end
    with pytest.raises(MXNetError, match="allows NO token.*\\[1\\]"):
        filter_logits(logits, 0, 1.0, mask=jnp.asarray(mask))
    keys = _fold_keys(jnp.asarray([1, 2], jnp.uint32),
                      jnp.asarray([0, 0], jnp.int32))
    with pytest.raises(MXNetError, match="dead end"):
        sample_tokens(logits, keys, jnp.asarray([0.0, 1.0], jnp.float32),
                      jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.float32),
                      mask=jnp.asarray(mask))


def test_mask_composes_with_degenerate_topk_topp():
    """top_k >= V and top_p = 1.0 disable the filters — the mask must
    still be the only thing deciding legality, on both the greedy and
    the sampled path."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models.generation import _fold_keys, sample_tokens
    rng = onp.random.RandomState(0)
    logits = jnp.asarray(rng.randn(4, V), jnp.float32)
    allowed = {5, 9, 77}
    mask = onp.zeros((4, V), bool)
    mask[:, list(allowed)] = True
    keys = _fold_keys(jnp.arange(4, dtype=jnp.uint32),
                      jnp.zeros(4, jnp.int32))
    for trial in range(8):
        keys_t = _fold_keys(jnp.arange(4, dtype=jnp.uint32),
                            jnp.full(4, trial, jnp.int32))
        toks = onp.asarray(sample_tokens(
            logits, keys_t,
            jnp.asarray([0.0, 1.0, 2.0, 1.0], jnp.float32),  # greedy + hot
            jnp.full(4, V, jnp.int32),                        # top_k >= V
            jnp.ones(4, jnp.float32),                         # top_p = 1.0
            mask=jnp.asarray(mask)))
        assert set(toks.tolist()) <= allowed, toks
    # the greedy row picks the best LEGAL logit, not the raw argmax
    greedy = int(onp.asarray(sample_tokens(
        logits, keys, jnp.zeros(4, jnp.float32), jnp.zeros(4, jnp.int32),
        jnp.ones(4, jnp.float32), mask=jnp.asarray(mask)))[0])
    best_legal = max(allowed,
                     key=lambda t: float(onp.asarray(logits)[0, t]))
    assert greedy == best_legal


# ------------------------------------------------------------- cache tiers
def test_memory_cache_hit_returns_same_automaton():
    clear_grammar_cache()
    g1 = compile_grammar("abc+", V)
    g2 = compile_grammar("abc+", V)
    assert g2 is g1                            # LRU hit, no rebuild
    assert compile_grammar("abc+", V, cache=False) is not g1
    clear_grammar_cache()
    assert compile_grammar("abc+", V) is not g1  # cleared = recompiled


def test_disk_cache_roundtrip_and_corrupt_eviction(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_GRAMMAR_CACHE_DIR", str(tmp_path))
    clear_grammar_cache()
    g1 = compile_grammar("x[0-9]{2}", V)
    entries = [p for p in os.listdir(tmp_path) if p.endswith(".grammar")]
    assert len(entries) == 1
    clear_grammar_cache()                      # force the disk tier
    g2 = compile_grammar("x[0-9]{2}", V)
    assert g2.key == g1.key
    assert (g2.nxt == g1.nxt).all() and (g2.cls == g1.cls).all()
    # a corrupt entry is evicted with a warning and recompiled, never
    # allowed to poison the automaton
    path = tmp_path / entries[0]
    path.write_text("{ not json")
    clear_grammar_cache()
    with pytest.warns(UserWarning, match="corrupt"):
        g3 = compile_grammar("x[0-9]{2}", V)
    assert g3.matches(_toks("x42"))
    assert not path.exists() or \
        json.loads(path.read_text())["key"] == g1.key  # re-stored clean


def test_grammar_knob_defaults_pinned():
    from mxnet_tpu.tune import config as tuneconf
    assert tuneconf.KNOBS["serve_grammar_mask_cache"]["default"] == 64
    assert tuneconf.KNOBS["serve_grammar_max_states"]["default"] == 64
    assert tuneconf.KNOBS["serve_grammar_max_states"]["valid"](2)
    assert not tuneconf.KNOBS["serve_grammar_max_states"]["valid"](1)
    assert not tuneconf.KNOBS["serve_grammar_max_states"]["valid"](8192)


# ----------------------------------------------------------- engine contracts
SCHEMA = {"type": "object",
          "properties": {"ok": {"type": "boolean"},
                         "mode": {"enum": ["fast", "safe"]}}}


def test_submit_validation(gpt_model):
    plain = InferenceEngine(gpt_model, max_batch_size=1, max_len=64).start()
    try:
        with pytest.raises(MXNetError, match="without grammar support"):
            plain.submit([1, 2], 4, grammar=SCHEMA, eos_token_id=EOS)
    finally:
        plain.shutdown()
    with pytest.raises(MXNetError, match="mutually exclusive"):
        InferenceEngine(gpt_model, max_len=64, grammar=True, multi_token=2)
    eng = InferenceEngine(gpt_model, max_batch_size=1, max_len=64,
                          grammar=True).start()
    try:
        with pytest.raises(MXNetError, match="eos_token_id"):
            eng.submit([1, 2], 4, grammar=SCHEMA)
        with pytest.raises(MXNetError, match="vocab"):
            eng.submit([1, 2], 4, grammar=compile_grammar(SCHEMA, 64),
                       eos_token_id=EOS)
    finally:
        eng.shutdown()


def test_greedy_constrained_determinism_across_page_sizes(gpt_model):
    """The same constrained greedy request emits IDENTICAL tokens whatever
    the page size (the default 16, and 8), and both conform to the
    schema."""
    gram = compile_grammar(SCHEMA, V)
    prompt = onp.asarray([65, 66, 67, 68], onp.int32)
    outs = []
    for kw in ({}, {"page_size": 8}):
        eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=64,
                              grammar=True, **kw).start()
        try:
            res = eng.generate(prompt, 40, grammar=SCHEMA,
                               eos_token_id=EOS, seed=0)
        finally:
            eng.shutdown()
        assert res.status == "ok", res
        assert gram.matches(res.generated_ids, eos_token_id=EOS), \
            "".join(chr(t) for t in res.generated_ids)
        outs.append(list(res.generated_ids))
    assert outs[0] == outs[1]


def test_spec_passthrough_grammar_is_token_identical(gpt_model):
    """Constraining with the all-admitting grammar ".*" must not change
    a single token vs the unconstrained request on the SAME speculative
    engine — the mask machinery composes with draft-verify without
    touching accept/reject decisions."""
    prompt = onp.asarray([7, 8, 9, 7, 8, 9, 7], onp.int32)
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=64,
                          page_size=8, speculate=3,
                          grammar=True).start()
    try:
        free = eng.generate(prompt, 10, seed=0)
        cons = eng.generate(prompt, 10, grammar=".*", eos_token_id=EOS,
                            seed=0)
    finally:
        eng.shutdown()
    assert free.status == cons.status == "ok"
    assert list(free.generated_ids) == list(cons.generated_ids)


def test_grammar_stream_spec_zero_recompiles(gpt_model):
    """The acceptance smoke: grammar + streaming + speculation all on,
    warmup compiles everything, then steady-state constrained streaming
    requests run under no_recompile() with the token events matching the
    final result exactly."""
    from mxnet_tpu.analysis import guards
    eng = InferenceEngine(gpt_model, max_batch_size=2, max_len=64,
                          page_size=8, speculate=3,
                          grammar=True).start()
    eng.warmup()
    gram = compile_grammar(SCHEMA, V)
    try:
        with guards.no_recompile(block="serve"):
            for i in range(3):
                h = eng.submit([65 + i, 66, 67], 40, grammar=SCHEMA,
                               eos_token_id=EOS, seed=i, stream=True)
                events, toks = [], []
                while True:
                    kind, val = h._events.get(timeout=60)
                    events.append(kind)
                    if kind == "done":
                        res = val
                        break
                    toks.append(val)
                assert res.status == "ok", res
                assert toks == list(res.generated_ids)
                assert gram.matches(toks, eos_token_id=EOS)
    finally:
        eng.shutdown()
