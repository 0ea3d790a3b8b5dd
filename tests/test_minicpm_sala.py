"""MiniCPM-SALA on the CPU at a tiny size (the same kinds of layer, 2 KV
heads, block 4, window 8, top-k 5 with the forced blocks inside, dense_len
24, so that the selection really drops blocks): the program against the
benchmark's plain float32 reference (``bench/reference/minicpm_sala.py``) and
the kernels against the equations they stand for.
"""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
import run as harness  # noqa: E402  (sets up the ``mxbench`` alias)

harness.alias_package(os.path.join(harness.BENCH, "tests"))

from mxbench.models import minicpm_sala as builder  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.ndarray import NDArray  # noqa: E402
from mxnet_tpu.ops import linear_attention as la  # noqa: E402
from mxnet_tpu.ops import sparse_attention as sa  # noqa: E402
from mxnet_tpu.serve import InferenceEngine  # noqa: E402

ref = builder.ref
CFG = json.load(open(os.path.join(harness.BENCH, "tests", "configs",
                                  "sala-tiny.json")))
SEED = 11
PS = CFG["sparse_config"]["block_size"]


@pytest.fixture(scope="module")
def net():
    return builder.build_net(CFG, SEED, train=False)


@pytest.fixture(scope="module")
def params():
    return builder.reference_weights(CFG, SEED)


def prompt(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG["vocab_size"], n).astype(np.int32)


def engine(net, **kw):
    args = dict(max_batch_size=4, max_len=64, page_size=PS, num_pages=64,
                prefill_chunk=8, min_prompt_bucket=2, prefix_cache=False)
    args.update(kw)
    return InferenceEngine(net, **args).start()


def ref_logits(params, seq):
    return np.asarray(ref.logits(params, jnp.asarray([seq]), CFG))[0]


# ------------------------------------------------------------- the kernels
def recurrence(q, k, v, slopes, S0):
    """Token by token, float64: S = lam S + k^T v, o = q S."""
    B, H, T, hd = q.shape
    lam = np.exp(-np.asarray(slopes, np.float64))[None, :, None, None]
    S = np.asarray(S0, np.float64).copy()
    out = np.zeros((B, H, T, hd))
    for t in range(T):
        S = lam * S + np.einsum("bhd,bhe->bhde", k[:, :, t], v[:, :, t])
        out[:, :, t] = np.einsum("bhd,bhde->bhe", q[:, :, t], S)
    return out, S


@pytest.mark.parametrize("T,valid", [(1, (1, 1)), (8, (8, 5)), (128, (128, 1)),
                                     (256, (256, 130)), (200, (200, 77))])
def test_chunked_scan_is_the_recurrence(T, valid):
    rng = np.random.RandomState(T)
    B, H, hd = 2, 4, 16
    q, k, v = (rng.standard_normal((B, H, T, hd)).astype(np.float32)
               for _ in range(3))
    S0 = rng.standard_normal((B, H, hd, hd)).astype(np.float32)
    slopes = la.decay_slopes(H, 3, 8)
    o, S = la.lightning_attention(q, k, v, jnp.asarray(S0), slopes,
                                  jnp.asarray(valid, jnp.int32))
    for b, n in enumerate(valid):
        want_o, want_S = recurrence(q[b:b + 1, :, :n], k[b:b + 1, :, :n],
                                    v[b:b + 1, :, :n], slopes, S0[b:b + 1])
        np.testing.assert_allclose(np.asarray(o)[b:b + 1, :, :n], want_o,
                                   rtol=2e-4, atol=2e-4)
        # the padding past ``valid`` neither decays the state nor adds to it
        np.testing.assert_allclose(np.asarray(S)[b:b + 1], want_S,
                                   rtol=2e-4, atol=2e-4)


def test_state_pool_zeroes_at_position_zero_and_carries_otherwise():
    rng = np.random.RandomState(5)
    H, hd, T = 4, 16, 8
    q, k, v = (rng.standard_normal((2, H, T, hd)).astype(np.float32)
               for _ in range(3))
    pool = jnp.asarray(rng.standard_normal((4, H, hd, hd)), jnp.float32)
    slopes = la.decay_slopes(H, 0, 8)
    slots = jnp.asarray([2, 0], jnp.int32)
    o, new = la.lightning_attention_slots(
        q, k, v, pool, slots, jnp.asarray([0, 8], jnp.int32), slopes,
        jnp.asarray([T, T], jnp.int32))
    zero, _ = recurrence(q[:1], k[:1], v[:1], slopes,
                         np.zeros((1, H, hd, hd)))
    kept, S1 = recurrence(q[1:], k[1:], v[1:], slopes, np.asarray(pool)[:1])
    np.testing.assert_allclose(np.asarray(o)[:1], zero, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(o)[1:], kept, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(new)[0], S1[0], rtol=2e-4,
                               atol=2e-4)
    # the slots no row names are as they were
    np.testing.assert_array_equal(np.asarray(new)[[1, 3]],
                                  np.asarray(pool)[[1, 3]])


def sparse_config():
    sp = CFG["sparse_config"]
    return sa.SparseConfig(block=sp["block_size"], kernel=sp["kernel_size"],
                           stride=sp["kernel_stride"],
                           init_blocks=sp["init_blocks"],
                           window=sp["window_size"], topk=sp["topk"],
                           dense_len=sp["dense_len"])


def test_selection_is_the_references():
    """The blocks the program selects (a chunk's mask and a decoding row's
    page list) are the reference's, at every depth up to 60 positions: under
    ``dense_len`` all live blocks, past it 5 of up to 15."""
    sc, z = sparse_config(), ref.sizes(CFG)
    rng = np.random.RandomState(2)
    T, H, G, hd = 60, z["H"], z["G"], z["hd"]
    maxp = 16
    q = rng.standard_normal((H, T, hd)).astype(np.float32)
    k = rng.standard_normal((G, T, hd)).astype(np.float32)
    win = np.minimum(sc.stride * np.arange(T - sc.kernel + 1)[:, None]
                     + np.arange(sc.kernel)[None, :], T - 1)
    kc = k[:, win].mean(axis=2)                              # [G, n_c, hd]
    t = np.arange(T)
    want = np.asarray(ref._selected(jnp.asarray(q), jnp.asarray(kc),
                                    jnp.asarray(t), z, -(-T // sc.block)))
    # the program's view: compressed keys in table order, padded to the table
    pb = sa.compressed_per_page(sc)
    kc_row = np.zeros((1, G, maxp * pb, hd), np.float32)
    kc_row[0, :, :kc.shape[1]] = kc
    qg = q.reshape(1, G, H // G, T, hd)
    mask = np.asarray(sa._select_mask(jnp.asarray(qg), jnp.asarray(kc_row),
                                      jnp.asarray(t[None]), sc, maxp))
    np.testing.assert_array_equal(mask[0, :, :, :want.shape[-1]], want)
    assert not mask[0, :, :, want.shape[-1]:].any()
    dropped = 0
    for pos in (3, 23, 24, 40, 59):
        blocks, ok = sa._select_pages(
            jnp.asarray(qg[:, :, :, pos:pos + 1]), jnp.asarray(kc_row),
            jnp.asarray([[pos]]), sc, maxp)
        for g in range(G):
            got = sorted(np.asarray(blocks)[0, g][np.asarray(ok)[0, g]])
            assert got == list(np.nonzero(want[g, pos])[0])
            read, live = sa.blocks_read(sc, pos, 1)
            assert (len(got), pos // sc.block + 1) == (read, live)
            dropped += live - read
    assert dropped > 0


# ---------------------------------------------- the model, by hand, on logits
def by_hand(net, seq, n_prompt, chunk):
    """Prefill ``seq[:n_prompt]`` in chunks of ``chunk`` (the last one padded
    to a whole chunk, as a bucket is), then decode the rest one position at a
    time, through ``forward_cached_paged`` over pools of this function's own:
    the logits of every position."""
    n_pages = -(-len(seq) // PS) + 1
    caches = [NDArray(jnp.zeros(s, d)) for s, d in
              net.cache_spec_paged(n_pages + 1, PS) + net.cache_spec_state(3)]
    table = np.full((1, n_pages), n_pages, np.int32)
    table[0, :n_pages - 1] = np.arange(n_pages - 1)[::-1]    # any order
    slot = NDArray(jnp.asarray([1], jnp.int32))
    out = []

    def run(ids, pos, valid):
        nonlocal caches
        logits, *caches = net.forward_cached_paged(
            NDArray(jnp.asarray([ids], jnp.int32)),
            NDArray(jnp.asarray([pos], jnp.int32)), NDArray(table), slot,
            NDArray(jnp.asarray([valid], jnp.int32)), *caches)
        out.extend(np.asarray(logits._data)[0, :valid])

    for lo in range(0, n_prompt, chunk):
        ids = list(seq[lo:min(lo + chunk, n_prompt)])
        run(ids + [0] * (chunk - len(ids)), lo, len(ids))
    for pos in range(n_prompt, len(seq)):
        run([seq[pos]], pos, 1)
    return np.asarray(out)


@pytest.mark.parametrize("n_prompt,chunk", [(19, 8), (37, 16)])
def test_chunked_prefill_then_decode_gives_the_references_logits(
        net, params, n_prompt, chunk):
    """19 + 21 positions cross ``dense_len`` = 24 while decoding; 37 start
    beyond it."""
    seq = list(prompt(n_prompt + 21, seed=n_prompt))
    got = by_hand(net, seq, n_prompt, chunk)
    want = ref_logits(params, seq)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    # the selection matters at this size: read densely the logits differ
    z = dict(CFG, sparse_config=dict(CFG["sparse_config"], dense_len=4096))
    dense = np.asarray(ref.logits(params, jnp.asarray([seq]), z))[0]
    assert np.abs(dense - want).max() > 2e-4


# ------------------------------------------------------- through the engine
def served(eng, prompts, n_new, **kw):
    hs = [eng.submit(p, n_new, temperature=0.0, **kw) for p in prompts]
    out = []
    for h in hs:
        r = h.result(timeout=600)
        assert r.ok, r.error
        out.append(list(r.generated_ids))
    return out


def assert_references_greedy(params, p, toks):
    seq = list(p) + toks
    logits = ref_logits(params, seq)[len(p) - 1:len(seq) - 1]
    gaps = logits.max(-1) - logits[np.arange(len(toks)), toks]
    assert gaps.max() <= 1e-5, gaps.max()


def test_engine_serves_the_references_tokens_and_counts_its_reads(net,
                                                                  params):
    """Chunked prefill, then decode across ``dense_len``, under continuous
    batching; ``sparse_blocks_read / sparse_blocks_live`` is what the
    dispatched programs read: every live block for a chunk, the reference's
    selection for a decoding row."""
    sc = sparse_config()
    eng = engine(net, lookahead=False)
    try:
        p = prompt(19, seed=3)
        toks, = served(eng, [p], 20)
        assert_references_greedy(params, p, toks)
        stats = eng.stats()
        read = live = 0
        for start, n in ((0, 8), (8, 8), (16, 4)):     # 2 chunks, bucket of 4
            r, l = sa.blocks_read(sc, start, n)
            read, live = read + r, live + l
        for pos in range(19, 19 + 19):                 # 19 decode steps
            r, l = sa.blocks_read(sc, pos, 1)
            read, live = read + r, live + l
        assert (stats["sparse_blocks_read"], stats["sparse_blocks_live"]) \
            == (read, live)
        assert read < live
        assert stats["state_bytes"] == 2 * 5 * 4 * 16 * 16 * 4
    finally:
        eng.shutdown()


def test_rows_of_one_batch_equal_each_alone_and_a_reused_slot_starts_clean(
        net, params):
    ps = [prompt(30, seed=7), prompt(5, seed=8), prompt(17, seed=9)]
    eng = engine(net, max_batch_size=2)
    try:
        together = served(eng, ps, 12)          # 3 requests over 2 slots
        alone = [served(eng, [p], 12)[0] for p in ps]   # the slots, again
    finally:
        eng.shutdown()
    assert together == alone
    for p, toks in zip(ps, together):
        assert_references_greedy(params, p, toks)


def test_preemption_and_resume_reproduce_the_tokens(net, params):
    ps = [prompt(22, seed=21), prompt(20, seed=22)]
    roomy = engine(net, max_batch_size=2, max_len=40)
    try:
        want = served(roomy, ps, 16)
    finally:
        roomy.shutdown()
    # 14 pages of 4: both prompts fit, their growth does not
    tight = engine(net, max_batch_size=2, max_len=40, num_pages=14)
    try:
        got = served(tight, ps, 16)
        assert tight.stats()["preemptions"] > 0
    finally:
        tight.shutdown()
    assert got == want
    for p, toks in zip(ps, got):
        assert_references_greedy(params, p, toks)


@pytest.mark.parametrize("kw,reason", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(speculate=2), "speculate"),
    (dict(multi_token=2), "multi_token"),
    (dict(paged=False), "paged"),
])
def test_what_is_refused_with_state_says_why(net, kw, reason):
    args = dict(max_batch_size=2, max_len=64, page_size=PS,
                prefix_cache=False)
    args.update(kw)
    with pytest.raises(MXNetError, match=reason):
        InferenceEngine(net, **args)


def test_pages_of_a_stateful_model_do_not_migrate(net):
    eng = InferenceEngine(net, max_batch_size=2, max_len=64, page_size=PS,
                          prefix_cache=False)
    for call in (lambda: eng.export_pages([1, 2, 3, 4, 5]),
                 lambda: eng.import_pages({})):
        with pytest.raises(MXNetError, match="recurrent state"):
            call()
    with pytest.raises(MXNetError, match="contiguous"):
        eng.score([1, 2, 3])
    with pytest.raises(MXNetError, match="page_size"):
        InferenceEngine(net, max_batch_size=2, max_len=64, page_size=8,
                        prefix_cache=False)


# ------------------------------------------------------------- the count
def test_the_count_follows_the_selection():
    work = builder.work
    z = work._sizes(CFG)
    # under dense_len a token attends every position; past it topk blocks
    assert work._attended(z, 10) == 11
    assert work._attended(z, 41) == 4 * 4 + 2
    assert work._scored(z, 10) == 0 and work._scored(z, 41) == 41
    deep, deeper = work.cache_bytes(CFG, 60), work.cache_bytes(CFG, 64)
    # past the selection's reach only the compressed keys grow
    assert deeper - deep == 2 * 2 * 16 * 2 * 4
    flops = work.forward_flops(CFG, 1, 40)
    assert flops == 2.0 * work.matmul_params(CFG) + 2 * 5 * 4 * 16 ** 2 \
        + 2 * 4 * 16 * (4 * work._attended(z, 40) + 2 * work._scored(z, 40))
    n_flops, n_bytes = work.kernel_count(CFG, "linear_attn", 8, 16)
    assert n_flops == 2 * 8 * 5 * 4 * 16 ** 2
    assert n_bytes == 2 * (4 * 8 * 4 * 16 * 2 + 2 * 4 * 16 * 16 * 4)
    with pytest.raises(ValueError):
        work.kernel_count(CFG, "flash", 1, 0)
