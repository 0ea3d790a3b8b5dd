"""Autoregressive generation (single compiled decode loop; models/generation.py)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np, autograd
from mxnet_tpu.gluon import Trainer
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.models import GPTModel, GPT_TINY, generate
from mxnet_tpu.models.gpt import GPTConfig


def _train_pattern_model(period=4, steps=120):
    """Train a tiny GPT to continue the repeating sequence 0,1,2,3,0,1,..."""
    mx.random.seed(0)
    cfg = GPTConfig(vocab_size=8, hidden_size=32, num_layers=2, num_heads=2,
                    max_position_embeddings=32, dropout=0.0)
    net = GPTModel(cfg)
    net.initialize()
    T = 16
    seq = onp.arange(T + 1) % period
    ids = np.array(seq[None, :T].astype("int32"))
    labels = np.array(seq[None, 1:T + 1].astype("int32"))
    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 3e-3})
    loss_fn = SoftmaxCrossEntropyLoss(axis=-1)
    for _ in range(steps):
        with autograd.record():
            loss = loss_fn(net(ids), labels).mean()
        loss.backward()
        tr.step(1)
    return net


@pytest.mark.slow
def test_greedy_continues_pattern():
    net = _train_pattern_model()
    prompt = np.array(onp.array([[0, 1, 2, 3, 0, 1]], "int32"))
    out = generate(net, prompt, max_new_tokens=6)
    got = out.asnumpy()[0]
    onp.testing.assert_array_equal(got[:6], [0, 1, 2, 3, 0, 1])
    onp.testing.assert_array_equal(got[6:], [2, 3, 0, 1, 2, 3])
    # method form
    out2 = net.generate(prompt, 6)
    onp.testing.assert_array_equal(out2.asnumpy(), out.asnumpy())


def test_sampling_reproducible_and_topk():
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=16, hidden_size=32, num_layers=1,
                             num_heads=2, max_position_embeddings=32,
                             dropout=0.0))
    net.initialize()
    prompt = np.array(onp.ones((2, 3), "int32"))
    a = generate(net, prompt, 5, temperature=1.0, seed=7).asnumpy()
    b = generate(net, prompt, 5, temperature=1.0, seed=7).asnumpy()
    onp.testing.assert_array_equal(a, b)          # seeded determinism
    c = generate(net, prompt, 5, temperature=1.0, seed=8).asnumpy()
    assert not onp.array_equal(a, c)              # different seed differs
    d = generate(net, prompt, 5, temperature=1.0, top_k=1, seed=3).asnumpy()
    e = generate(net, prompt, 5).asnumpy()        # greedy
    onp.testing.assert_array_equal(d, e)          # top_k=1 == greedy


@pytest.mark.slow
def test_eos_latches():
    """Trained pattern model continues [0,1,2] with 3 deterministically, so
    eos=3 fires at the FIRST generated token and must latch."""
    net = _train_pattern_model(steps=120)
    prompt = np.array(onp.array([[0, 1, 2]], "int32"))
    out = generate(net, prompt, 8, eos_token_id=3).asnumpy()[0]
    assert out[3] == 3                            # eos emitted immediately
    assert (out[3:] == 3).all()                   # and latches


def test_generate_rejects_overlong():
    net = _train_pattern_model(steps=1)
    prompt = np.array(onp.zeros((1, 30), "int32"))
    with pytest.raises(mx.MXNetError, match="max_position_embeddings"):
        generate(net, prompt, 10)  # 40 > table size 32


def test_generate_compile_cache_reused():
    net = _train_pattern_model(steps=1)
    prompt = np.array(onp.array([[0, 1, 2, 3]], "int32"))
    import time
    generate(net, prompt, 4)                      # compile
    t0 = time.perf_counter()
    generate(net, prompt, 4)                      # cached
    assert time.perf_counter() - t0 < 1.0


@pytest.mark.slow
def test_kv_cache_matches_nocache_gpt():
    """Cached incremental decode must produce exactly the greedy tokens of
    the cache-free full re-forward path."""
    net = _train_pattern_model()
    prompt = np.array(onp.array([[0, 1, 2, 3, 0], [1, 2, 3, 0, 1]], "int32"))
    ref = generate(net, prompt, 7, use_cache=False).asnumpy()
    got = generate(net, prompt, 7, use_cache=True).asnumpy()
    onp.testing.assert_array_equal(got, ref)


def test_kv_cache_matches_nocache_llama():
    from mxnet_tpu.models import LlamaForCausalLM
    from mxnet_tpu.models.llama import LlamaConfig
    mx.random.seed(0)
    cfg = LlamaConfig(vocab_size=32, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      dtype=onp.float32)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    prompt = np.array(onp.array([[5, 9, 1, 7]], "int32"))
    ref = generate(net, prompt, 6, use_cache=False).asnumpy()
    got = generate(net, prompt, 6, use_cache=True).asnumpy()
    onp.testing.assert_array_equal(got, ref)


@pytest.mark.slow
def test_kv_cache_eos_and_sampling():
    net = _train_pattern_model()
    prompt = np.array(onp.array([[0, 1, 2]], "int32"))
    out = generate(net, prompt, 8, eos_token_id=3, use_cache=True).asnumpy()[0]
    assert out[3] == 3 and (out[3:] == 3).all()
    a = generate(net, prompt, 5, temperature=1.0, seed=7,
                 use_cache=True).asnumpy()
    b = generate(net, prompt, 5, temperature=1.0, seed=7,
                 use_cache=True).asnumpy()
    onp.testing.assert_array_equal(a, b)


def test_kv_cache_matches_nocache_stacked_llama():
    """Stacked decoders gained KV-cache decode in r3 (scan over stacked
    caches, llama.py LlamaStackedDecoder.forward_cached): cached and
    cache-free decode must emit identical tokens."""
    from mxnet_tpu.models import LlamaForCausalLM
    from mxnet_tpu.models.llama import LlamaConfig
    mx.random.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_layers=3, num_heads=4, num_kv_heads=2,
                      dtype=onp.float32, stacked=True)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    prompt = np.array(onp.random.RandomState(0).randint(0, 64, (2, 5))
                      .astype("int32"))
    with_cache = generate(net, prompt, 6, use_cache=True)
    without = generate(net, prompt, 6, use_cache=False)
    assert onp.array_equal(with_cache.asnumpy(), without.asnumpy())


def test_top_p_nucleus_sampling():
    """top_p added alongside temperature/top_k: a vanishing nucleus is
    greedy, sampling stays seeded-reproducible, bad args are rejected."""
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=16, hidden_size=32, num_layers=1,
                             num_heads=2, max_position_embeddings=32,
                             dropout=0.0))
    net.initialize()
    prompt = np.array(onp.ones((2, 3), "int32"))
    # nucleus that only ever holds the argmax == greedy
    tiny = generate(net, prompt, 5, temperature=1.0, top_p=1e-6,
                    seed=3).asnumpy()
    greedy = generate(net, prompt, 5).asnumpy()
    onp.testing.assert_array_equal(tiny, greedy)
    a = generate(net, prompt, 5, temperature=1.0, top_p=0.8, seed=7).asnumpy()
    b = generate(net, prompt, 5, temperature=1.0, top_p=0.8, seed=7).asnumpy()
    onp.testing.assert_array_equal(a, b)          # seeded determinism
    # combined top_k + top_p path compiles and runs
    c = generate(net, prompt, 5, temperature=1.0, top_k=4, top_p=0.9,
                 seed=7)
    assert c.shape == (2, 8)


def test_sampling_args_validated():
    net = GPTModel(GPTConfig(vocab_size=16, hidden_size=32, num_layers=1,
                             num_heads=2, max_position_embeddings=32,
                             dropout=0.0))
    net.initialize()
    prompt = np.array(onp.ones((1, 3), "int32"))
    with pytest.raises(mx.MXNetError, match="top_k"):
        generate(net, prompt, 4, top_k=-1)
    with pytest.raises(mx.MXNetError, match="top_p"):
        generate(net, prompt, 4, top_p=0.0)
    with pytest.raises(mx.MXNetError, match="top_p"):
        generate(net, prompt, 4, top_p=1.0001)
    with pytest.raises(mx.MXNetError, match="temperature"):
        generate(net, prompt, 4, temperature=-0.5)


def test_decode_cache_lru_and_thread_safety(monkeypatch):
    """_DECODE_CACHE is a real LRU (hits move to the end, eviction drops
    the least-recent) and concurrent generate() calls from server threads
    share one locked cache."""
    from mxnet_tpu.models import generation as gen
    net = GPTModel(GPTConfig(vocab_size=16, hidden_size=32, num_layers=1,
                             num_heads=2, max_position_embeddings=64,
                             dropout=0.0))
    net.initialize()
    gen.clear_cache()
    monkeypatch.setattr(gen, "_DECODE_CACHE_LIMIT", 2)
    pa = np.array(onp.ones((1, 3), "int32"))
    pb = np.array(onp.ones((1, 4), "int32"))
    pc = np.array(onp.ones((1, 5), "int32"))
    generate(net, pa, 3)
    key_a = next(iter(gen._DECODE_CACHE))
    generate(net, pb, 3)
    key_b = [k for k in gen._DECODE_CACHE if k != key_a][0]
    generate(net, pa, 3)                          # hit: A moves to the end
    generate(net, pc, 3)                          # evicts B, NOT A
    assert key_a in gen._DECODE_CACHE
    assert key_b not in gen._DECODE_CACHE
    assert len(gen._DECODE_CACHE) == 2

    # concurrent generate() on one model: same greedy tokens, no races
    import threading
    ref = generate(net, pa, 4).asnumpy()
    outs = [None] * 4
    errs = []

    def worker(i):
        try:
            outs[i] = generate(net, pa, 4).asnumpy()
        except Exception as e:  # noqa: BLE001 - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errs
    for o in outs:
        onp.testing.assert_array_equal(o, ref)
    gen.clear_cache()


def test_use_cache_rejected_for_unsupported_configs():
    """MoE / pipeline / sequence-parallel configs must refuse use_cache=True
    (capacity routing + sharded attention would silently diverge — ADVICE
    r2 #1/#2) and silently fall back when use_cache is left default."""
    from mxnet_tpu.models import LlamaForCausalLM
    from mxnet_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig(vocab_size=32, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      dtype=onp.float32, num_experts=2,
                      num_experts_per_tok=1)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    prompt = np.array(onp.zeros((1, 4), "int32"))
    with pytest.raises(mx.MXNetError, match="use_cache"):
        generate(net, prompt, 4, use_cache=True)
    # and the automatic default silently falls back to the cache-free path
    out = generate(net, prompt, 4)
    assert out.shape == (1, 8)


# ------------------------------------------- filter_logits without a sort
# the kept set against a float64 reference that sorts: every combination of
# top_k and top_p a case, as python scalars and as per-row arrays, over rows
# that are peaked, flat, tied at the k-th value and at the nucleus's edge,
# and masked. Logits lie on a grid, so ties are exact and a whole group of
# tokens enters the nucleus at once: each row is drawn until every group's
# edge is 1e-4 of mass or more away from every top_p (float32 sums cannot
# tell a nearer one from the float64 reference's)
_FILTER_V = {"gpt2": 50257, "small": 257}
_FILTER_ROWS = {"gpt2": 96, "small": 256}
_TOP_P = [1e-6, 0.5, 0.95, 1.0]


def _top_ks(V):
    return [0, 1, 40, V, V + 7]


def _mass_above(x, top_k):
    """float64, by sorting: for each token of the row the softmax mass,
    over what top-k leaves, of the tokens strictly greater than it (NaN for
    a token that top-k drops or the mask forbids)."""
    V = x.size
    order = onp.argsort(-x, kind="stable")
    s = x[order].astype(onp.float64)
    keep = onp.ones(V, bool) if top_k <= 0 else s >= s[min(top_k, V) - 1]
    e = onp.where(keep, onp.exp(s - s[0]), 0.0)
    before = onp.cumsum(e) - e                    # mass sorted before it
    first = onp.maximum.accumulate(               # where its tie group starts
        onp.where(onp.r_[True, s[1:] != s[:-1]], onp.arange(V), 0))
    above = onp.where(keep & onp.isfinite(s), before[first] / e.sum(),
                      onp.nan)
    out = onp.empty(V)
    out[order] = above
    return out


def _reference_kept(x, top_k, top_p):
    """The filter as it was written with a sort, in float64: the k-th of the
    descending row, the softmax of what is left, the exclusive cumulative
    sum under top_p, the last such value as threshold; ``>=`` keeps ties."""
    V = x.size
    x = x.astype(onp.float64)
    s = onp.sort(x)[::-1]
    keep = onp.ones(V, bool)
    if top_k > 0:
        kth = s[min(top_k, V) - 1]
        keep &= x >= kth
        s = onp.where(s >= kth, s, -onp.inf)
    if top_p < 1.0:
        e = onp.exp(s - s[0])
        probs = e / e.sum()
        ncut = int(((onp.cumsum(probs) - probs) < top_p).sum())
        keep &= x >= s[max(ncut, 1) - 1]
    return keep & onp.isfinite(x)


def _draw_row(rng, V, kind):
    if kind == 0:        # peaked: a few tokens hold the mass
        x = onp.round(rng.normal(size=V) * 6.0 * 2) / 2
    elif kind == 1:      # flat: thousands of tokens inside the nucleus
        x = onp.round(rng.normal(size=V) * 0.6 * 4) / 4
    elif kind == 2:      # one value for most of the row: ties at every edge
        x = onp.where(rng.random(V) < 0.7, 1.0, onp.round(
            rng.normal(size=V) * 2.0))
    else:                # masked: a third of the row forbidden
        x = onp.round(rng.normal(size=V) * 2.0 * 2) / 2
        x[rng.random(V) < 0.33] = -onp.inf
    return x.astype(onp.float32)


@pytest.fixture(scope="module", params=list(_FILTER_V))
def filter_rows(request):
    V, n = _FILTER_V[request.param], _FILTER_ROWS[request.param]
    rng = onp.random.default_rng(V)
    rows, tries = [], 0
    while len(rows) < n:
        assert tries < 8 * n
        x = _draw_row(rng, V, len(rows) % 4)
        tries += 1
        edges = onp.concatenate([_mass_above(x, k) for k in _top_ks(V)])
        edges = edges[edges > 0]                   # NaN and the argmax: no
        if all(onp.abs(edges - p).min() >= 1e-4 for p in _TOP_P[:-1]):
            rows.append(x)
    return onp.stack(rows)


@pytest.mark.parametrize("per_row", [False, True], ids=["scalar", "per_row"])
@pytest.mark.parametrize("top_p", _TOP_P)
@pytest.mark.parametrize("k_index", range(5),
                         ids=["k0", "k1", "k40", "kV", "k_beyond_V"])
def test_filter_logits_keeps_what_a_sort_keeps(filter_rows, k_index, top_p,
                                               per_row):
    import jax.numpy as jnp
    from mxnet_tpu.models.generation import filter_logits
    n, V = filter_rows.shape
    ks, top_k = _top_ks(V), _top_ks(V)[k_index]
    if per_row:
        # the case's pair on every other row, the other pairs between them
        i = onp.arange(n)
        k_rows = onp.where(i % 2 == 0, top_k,
                           onp.asarray(ks)[(i // 2) % 5]).astype(onp.int32)
        p_rows = onp.where(i % 2 == 0, top_p, onp.asarray(_TOP_P)[
            (i // 10) % 4]).astype(onp.float32)
        got = filter_logits(jnp.asarray(filter_rows), jnp.asarray(k_rows),
                            jnp.asarray(p_rows))
    else:
        k_rows, p_rows = [top_k] * n, [top_p] * n
        got = filter_logits(jnp.asarray(filter_rows), top_k, top_p)
    got = onp.asarray(got)
    want = onp.stack([_reference_kept(x, int(k), float(p))
                      for x, k, p in zip(filter_rows, k_rows, p_rows)])
    onp.testing.assert_array_equal(onp.isfinite(got), want)
    # what is kept is kept as it was
    onp.testing.assert_array_equal(got[want], filter_rows[want])
    assert want.any(axis=-1).all()


def test_filter_logits_mask_is_applied_first():
    """The grammar's mask before the filters: top-k counts and the nucleus
    hold legal tokens only, as if the forbidden ones were -inf."""
    import jax.numpy as jnp
    from mxnet_tpu.models.generation import filter_logits
    rng = onp.random.default_rng(5)
    x = (onp.round(rng.normal(size=(8, 257)) * 4) / 2).astype(onp.float32)
    mask = rng.random((8, 257)) < 0.5
    got = onp.asarray(filter_logits(jnp.asarray(x), 5, 0.9,
                                    mask=jnp.asarray(mask)))
    want = onp.asarray(filter_logits(
        jnp.asarray(onp.where(mask, x, -onp.inf)), 5, 0.9))
    onp.testing.assert_array_equal(got, want)
    assert not onp.isfinite(got[~mask]).any()


@pytest.mark.parametrize("V", [50257, 1031])
def test_sampled_token_depends_on_its_row_alone(V):
    """The engine's contract (``_slot_keys``): a row's token is bitwise the
    same alone, in a batch of 16 beside other rows' parameters, and beside
    15 greedy rows; a greedy row's beside rows that filter."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models.generation import _fold_keys, sample_tokens
    rng = onp.random.default_rng(V)
    B = 16
    logits = jnp.asarray(rng.normal(size=(B, V)).astype(onp.float32) * 3)
    temps = onp.array([0.7, 1.0, 0.0, 1.3] * 4, onp.float32)
    topks = onp.array([0, 40, 0, 5, 0, 0, 1, V] * 2, onp.int32)
    topps = onp.array([0.95, 1.0, 0.5, 0.9, 1.0] * 3 + [0.3], onp.float32)
    keys = _fold_keys(jnp.arange(B, dtype=jnp.uint32),
                      jnp.arange(B, dtype=jnp.int32) * 3)
    select = jax.jit(sample_tokens)
    mixed = onp.asarray(select(logits, keys, temps, topks, topps))
    for r in range(B):
        one = slice(r, r + 1)
        alone = onp.asarray(select(logits[one], keys[one], temps[one],
                                   topks[one], topps[one]))
        assert alone[0] == mixed[r]
        # beside 15 greedy rows that ask for nothing
        t, k, p = onp.zeros(B, onp.float32), onp.zeros(B, onp.int32), \
            onp.ones(B, onp.float32)
        t[r], k[r], p[r] = temps[r], topks[r], topps[r]
        assert onp.asarray(select(logits, keys, t, k, p))[r] == mixed[r]
    greedy = onp.asarray(jnp.argmax(logits, axis=-1))
    onp.testing.assert_array_equal(mixed[temps == 0], greedy[temps == 0])
