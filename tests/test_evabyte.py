"""EvaByte on the CPU at a tiny size (2 layers, hidden 64, 4 heads of 16,
windows of 32 positions in chunks of 4, so a page of 8 summaries, 3 prediction
heads): the program against the benchmark's plain float32 reference
(``bench/reference/evabyte.py``), the page pool's fold, and the engine's
folding of a request's table while the request lives.

Tolerances. Program and reference are both float32 here and compute the same
sums in different orders (the program a running softmax over pages, the
reference one softmax over a masked row): logits of size 1 agree to a few
1e-6, and 2e-5 holds them while a summary left out, a window misaligned or a
``phi``/``mu`` dropped moves them by 1e-3 and more (asserted below).
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

from mxbench.models import evabyte as builder  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.ndarray import NDArray  # noqa: E402
from mxnet_tpu.ops import eva_attention as eva  # noqa: E402
from mxnet_tpu.serve import InferenceEngine  # noqa: E402
from mxnet_tpu.serve.paging import OutOfPages, PagePool  # noqa: E402

ref = builder.ref
CFG = json.load(open(os.path.join(harness.BENCH, "tests", "configs",
                                  "evabyte-tiny.json")))
SEED = 11
W, C = CFG["window_size"], CFG["chunk_size"]
PS = W // C
FOLD = eva.FoldedPages(W, PS)
ATOL = 2e-5


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
    args = dict(max_batch_size=4, max_len=160, page_size=PS, num_pages=36,
                prefill_chunk=16, min_prompt_bucket=4, prefix_cache=False)
    args.update(kw)
    return InferenceEngine(net, **args).start()


def ref_logits(params, seq):
    return np.asarray(ref.logits(params, jnp.asarray([seq]), CFG))[0]


# ------------------------------------------------------------- the kernels
def test_summaries_are_the_references():
    rng = np.random.RandomState(1)
    H, hd, T = 4, 16, 3 * W
    k, v = (rng.standard_normal((H, T, hd)).astype(np.float32)
            for _ in range(2))
    phi, mu = (rng.standard_normal((H, hd)).astype(np.float32) / 4
               for _ in range(2))
    want_k, want_v = ref.summaries(jnp.asarray(k), jnp.asarray(v),
                                   jnp.asarray(phi), jnp.asarray(mu), C)
    got_k, got_v = eva.summarize(jnp.asarray(k.transpose(1, 0, 2)),
                                 jnp.asarray(v.transpose(1, 0, 2)),
                                 jnp.asarray(phi), jnp.asarray(mu), C)
    # the same sums of 4 products in float32: rounding only
    np.testing.assert_allclose(np.asarray(got_k).transpose(1, 0, 2), want_k,
                               rtol=0, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_v).transpose(1, 0, 2), want_v,
                               rtol=0, atol=2e-6)
    # a chunk's weights are no mean: phi matters at this size
    flat = ref.summaries(jnp.asarray(k), jnp.asarray(v),
                         jnp.zeros((H, hd)), jnp.asarray(mu), C)[0]
    assert np.abs(np.asarray(flat) - np.asarray(want_k)).max() > 1e-2


@pytest.mark.parametrize("T", [5, W, 2 * W + 7, 100])
def test_forward_is_the_references_on_every_head(net, params, T):
    ids = np.stack([prompt(T, seed=T), prompt(T, seed=T + 1)])
    got = np.asarray(net(NDArray(jnp.asarray(ids)))._data)
    want = np.asarray(ref.logits_all(params, jnp.asarray(ids), CFG))
    assert got.shape == (2, T, CFG["num_pred_heads"], CFG["vocab_size"])
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
    # the served head is the first
    np.testing.assert_allclose(
        want[:, :, 0], np.asarray(ref.logits(params, jnp.asarray(ids), CFG)),
        rtol=0, atol=2e-6)


@pytest.mark.parametrize("leaf", ["phi", "mu", "in_norm"])
def test_leaving_a_learned_vector_out_fails_the_comparison(params, leaf):
    """``phi``, ``mu`` and the norms' ``w`` are drawn far enough from 0 that
    a program without them would not pass: at 100 positions (three windows
    of summaries behind the last) zeroing one moves a logit by far more than
    the tolerance."""
    seq = prompt(100, seed=5)
    want = ref_logits(params, seq)
    without = dict(params, layers=dict(
        params["layers"],
        **{leaf: [jnp.zeros_like(x) for x in params["layers"][leaf]]}))
    assert np.abs(ref_logits(without, seq) - want).max() > 50 * ATOL


# ---------------------------------------------- the model, by hand, on logits
def by_hand(net, seq, n_prompt, chunk):
    """Prefill ``seq[:n_prompt]`` in chunks of ``chunk`` (the last one padded
    to a whole chunk, as a bucket is), then decode the
    rest one position at a time, through ``forward_cached_paged`` over pools
    and a folded table of this function's own: the logits of every position,
    and the most pages held."""
    n_pages = FOLD.peak(len(seq)) + 3
    caches = [NDArray(jnp.zeros(s, d))
              for s, d in net.cache_spec_paged(n_pages + 1, PS)]
    free = list(range(n_pages))[::-1]
    row, out, most = [], [], 0

    def run(ids, pos, valid):
        nonlocal caches, row, most
        while len(row) < FOLD.entries(pos + valid):
            row.append(free.pop())
        most = max(most, len(row))
        table = np.full((1, FOLD.peak(len(seq)) + 1), n_pages, np.int32)
        table[0, :len(row)] = row
        logits, *caches = net.forward_cached_paged(
            NDArray(jnp.asarray([ids], jnp.int32)),
            NDArray(jnp.asarray([pos], jnp.int32)), NDArray(table),
            NDArray(jnp.asarray([valid], jnp.int32)), *caches)
        assert logits.shape == (1, len(ids), CFG["vocab_size"])
        out.extend(np.asarray(logits._data)[0, :valid])
        if FOLD.ends_window(pos + valid):
            done = (pos + valid) // W - 1
            free.extend(row[done:done + FOLD.window_pages])
            row = row[:done] + [row[done + FOLD.window_pages]]

    lo = 0
    while lo < n_prompt:
        ids = list(seq[lo:min(lo + chunk, n_prompt)])
        run(ids + [0] * (chunk - len(ids)), lo, len(ids))
        lo += len(ids)
    for pos in range(n_prompt, len(seq)):
        run([seq[pos]], pos, 1)
    return np.asarray(out), most


@pytest.mark.parametrize("chunk", [1, 2, 4, 8, 16, 32])
def test_chunks_then_steps_across_three_window_ends(net, params, chunk):
    """A prompt of 70 (two window ends in prefill, the second chunk-aligned
    only for the chunks that divide 64 - all of these - and the last chunk
    padded) and 35 decoded steps (a third end, in decode): the logits of all
    105 positions are the reference's full forward's."""
    seq = list(prompt(105, seed=chunk))
    got, most = by_hand(net, seq, 70, chunk)
    np.testing.assert_allclose(got, ref_logits(params, seq), rtol=0,
                               atol=ATOL)
    # 3 summaries + 1 page of the fourth window; never more than 2 summaries,
    # a whole window and the page its summaries go to
    assert most <= 2 + FOLD.window_pages + 1


def test_heads_in_groups_of_pools_change_no_number(params, monkeypatch):
    """At the published widths a layer's 32 heads lie in two pools of 16
    (``POOL_LANES``: 2,048 lanes a row). Here: the 4 heads of 16 in two pools
    of 2, four pools a layer."""
    from mxnet_tpu.models import evabyte
    monkeypatch.setattr(evabyte, "POOL_LANES", 32)
    grouped = builder.build_net(CFG, SEED, train=False)
    spec = grouped.cache_spec_paged(5, PS)
    assert [s for s, _ in spec] == [(5, PS, 32)] * 8
    seq = list(prompt(105, seed=3))
    got, _ = by_hand(grouped, seq, 70, 16)
    np.testing.assert_allclose(got, ref_logits(params, seq), rtol=0,
                               atol=ATOL)


def test_padding_that_crosses_a_windows_end_neither_folds_nor_harms(net,
                                                                    params):
    """A last chunk of 8 at position 28 with 3 real positions: its padding
    runs over the window's end at 32. It must not fold (the window has 31
    positions), and the next step, which ends the window, must."""
    seq = list(prompt(60, seed=77))
    n_prompt = 31
    n_pages = 16
    caches = [NDArray(jnp.zeros(s, d))
              for s, d in net.cache_spec_paged(n_pages + 1, PS)]
    table = np.full((1, 8), n_pages, np.int32)
    table[0, :4] = [3, 1, 0, 2]
    out = []

    def run(ids, pos, valid):
        nonlocal caches
        logits, *caches = net.forward_cached_paged(
            NDArray(jnp.asarray([ids], jnp.int32)),
            NDArray(jnp.asarray([pos], jnp.int32)), NDArray(table.copy()),
            NDArray(jnp.asarray([valid], jnp.int32)), *caches)
        out.extend(np.asarray(logits._data)[0, :valid])

    run(seq[:28] + [0] * 4, 0, 28)
    run(seq[28:31] + [0] * 5, 28, 3)
    # the page behind the window is still the sink's: nothing was folded
    # into a page of the table, and page 5 (to come) is untouched
    assert not np.asarray(caches[0]._data)[5].any()
    table[0, 4] = 5                       # the page the summaries go to
    run([seq[31]], 31, 1)                 # ends the window
    assert np.asarray(caches[0]._data)[5].any()
    table[0, :] = n_pages
    table[0, :5] = [5, 7, 0, 9, 1]        # folded: the summaries, then new
    for pos in range(32, 60):
        run([seq[pos]], pos, 1)
    np.testing.assert_allclose(np.asarray(out), ref_logits(params, seq),
                               rtol=0, atol=ATOL)


# ------------------------------------------------------------ the page pool
def test_fold_returns_a_windows_pages_and_keeps_the_ledger_consistent():
    pool = PagePool(12, PS, 160, slots=2, prefix_cache=False, layout=FOLD)
    assert pool.max_pages == FOLD.peak(160) == 4 + 4 + 1
    assert pool.lease(0, W - 1) == 4               # a window's four pages
    assert pool.lease(0, W) == 1                   # the end brings the fifth
    before = pool.table(0).copy()
    assert pool.free_pages() == 7
    assert pool.fold(0) == 4
    assert pool.free_pages() == 11
    assert pool.table(0)[0] == before[4]
    assert (pool.table(0)[1:] == pool.sink).all()
    pool.check_consistent()
    # the freed pages serve another slot while slot 0 lives
    assert pool.lease(1, W - 1) == 4
    pool.lease(0, W + 3)
    pool.check_consistent()
    assert pool.stats()["windows_folded"] == 1
    assert pool.stats()["pages_folded"] == 4
    pool.release(0)
    pool.release(1)
    assert pool.free_pages() == 12
    pool.check_consistent()


def test_fold_and_its_lease_are_all_or_nothing():
    pool = PagePool(9, PS, 160, slots=2, prefix_cache=False, layout=FOLD)
    pool.lease(0, W - 1)
    pool.lease(1, W - 1)
    assert pool.free_pages() == 1
    before = pool.table(0).copy()
    # a fold of a table that lacks the summaries' page changes nothing
    with pytest.raises(MXNetError, match="fold"):
        pool.fold(0)
    np.testing.assert_array_equal(pool.table(0), before)
    pool.lease(0, W)                               # takes the last page
    before1 = pool.table(1).copy()
    with pytest.raises(OutOfPages):
        pool.lease(1, W)                           # none left for slot 1's
    np.testing.assert_array_equal(pool.table(1), before1)
    pool.check_consistent()
    pool.fold(0)                             # gives four back
    assert pool.lease(1, W) == 1
    pool.fold(1)
    pool.check_consistent()
    assert pool.pages_in_use() == 2


@pytest.mark.parametrize("window,ps,max_len", [(32, 8, 160), (32, 8, 256),
                                               (2048, 128, 32768)])
def test_a_request_of_max_len_never_holds_more_than_the_widest_table(
        window, ps, max_len):
    fold = eva.FoldedPages(window, ps)
    pool = PagePool(fold.peak(max_len), ps, max_len, slots=1,
                    prefix_cache=False, layout=fold)
    most = 0
    for depth in range(1, max_len + 1):
        pool.lease(0, depth)
        most = max(most, pool.pages_in_use())
        assert pool.pages_in_use() == fold.entries(depth)
        if fold.ends_window(depth):
            pool.fold(0)
            assert pool.pages_in_use() == depth // window  # summaries only
    for depth in range(window, max_len + 1, window):   # every window's end
        assert fold.entries(depth) <= pool.max_pages
    assert most <= pool.max_pages
    if max_len == 32768:
        assert pool.max_pages == 15 + 16 + 1
    with pytest.raises(MXNetError, match="cannot hold"):
        PagePool(pool.max_pages - 1, ps, max_len, slots=1,
                 prefix_cache=False, layout=fold)


# ------------------------------------------------------- through the engine
def served(eng, prompts, n_new, **kw):
    hs = [eng.submit(p, n, temperature=0.0, **kw)
          for p, n in zip(prompts, n_new)]
    out = []
    for h in hs:
        r = h.result(timeout=600)
        assert r.ok, r.error
        out.append(list(r.generated_ids))
    return out


def assert_references_greedy(params, p, toks, n):
    assert len(toks) == n
    seq = list(p) + toks
    logits = ref_logits(params, seq)[len(p) - 1:len(seq) - 1]
    gaps = logits.max(-1) - logits[np.arange(len(toks)), toks]
    # the served token is the reference's best, or its logit is within the
    # tolerance of the best's
    assert gaps.max() <= ATOL, gaps.max()


@pytest.mark.parametrize("lookahead", [True, False])
def test_engine_folds_in_prefill_and_in_decode(net, params, lookahead):
    """Four requests under continuous batching: windows end in a middle
    chunk (64 of 100), in a last chunk (a prompt of exactly 64), and while
    decoding (30 + 10 crosses 32; 7 + 70 crosses 32 and 64), with and
    without the lookahead."""
    ps = [prompt(30, 1), prompt(64, 2), prompt(100, 3), prompt(7, 4)]
    ns = [10, 40, 50, 70]
    eng = engine(net, lookahead=lookahead)
    try:
        got = served(eng, ps, ns)
        stats = eng.stats()
        eng._pages.check_consistent()
    finally:
        eng.shutdown()
    for p, toks, n in zip(ps, got, ns):
        assert_references_greedy(params, p, toks, n)
    ends = sum((len(p) + n - 1) // W for p, n in zip(ps, ns))
    assert stats["windows_folded"] == ends == 10
    assert stats["pages_folded"] == ends * FOLD.window_pages
    assert 0 < stats["pages_held"] < 0.6 * stats["pages_unfolded"]
    assert stats["pages"]["pages_in_use"] == 0
    assert stats["preemptions"] == 0


def test_a_finished_windows_pages_are_free_while_the_request_lives(net):
    """One request of 40 + 100 positions in a pool of 9 pages, the widest
    table a request of ``max_len`` 160 has. Unfolded it would want 18. While
    it decodes past its fourth window it holds 4 summary pages and one page
    of window: the rest of the pool is free."""
    eng = engine(net, num_pages=9, max_batch_size=1)
    try:
        h = eng.submit(prompt(40, 9), 100, temperature=0.0, stream=True)
        seen = []
        while not h.done():
            ev = h._events.get(timeout=60)
            if ev[0] == "token":
                seen.append((len(seen), eng._pages.free_pages()))
        assert h.result().ok and len(h.result().generated_ids) == 100
    finally:
        eng.shutdown()
    most_held = 9 - min(free for _, free in seen)
    assert most_held <= FOLD.peak(160)
    # at depth 40 + 92 = 132 the request holds 4 summaries and 1 window page
    # (the lookahead may be one step ahead of the token that was read)
    late = [free for i, free in seen if 90 <= i <= 92]
    assert min(late) >= 9 - 5 - 1


def test_preemption_in_the_step_after_a_fold_rebuilds_the_summaries(net,
                                                                    params):
    """Two requests whose growth does not fit 11 pages: the younger is
    preempted, requeued, and prefilled again from 0 (which folds its first
    window again). Tokens and lengths are those of a roomy pool."""
    ps = [prompt(30, 21), prompt(28, 22)]
    ns = [60, 60]
    roomy = engine(net, max_batch_size=2)
    try:
        want = served(roomy, ps, ns)
    finally:
        roomy.shutdown()
    tight = engine(net, max_batch_size=2, num_pages=9)
    try:
        got = served(tight, ps, ns)
        stats = tight.stats()
        tight._pages.check_consistent()
    finally:
        tight.shutdown()
    assert stats["preemptions"] > 0
    assert got == want
    for p, toks, n in zip(ps, got, ns):
        assert_references_greedy(params, p, toks, n)
    # the resumed request folded its windows twice
    assert stats["windows_folded"] > sum((len(p) + n - 1) // W
                                         for p, n in zip(ps, ns))


def test_dispatch_spans_carry_what_the_tables_held(net, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    eng = engine(net, max_batch_size=1, lookahead=False)
    eng.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        served(eng, [prompt(40, 31)], [30])
    finally:
        jax.profiler.stop_trace()
        eng.shutdown()
    path = sorted(glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb"))[-1]
    spans = [dict(e.stats)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name in ("mx.serve.prefill_dispatch",
                           "mx.serve.decode_dispatch")]
    assert spans and all("held" in a for a in spans)
    assert sum(int(a["folded"]) for a in spans) == (40 + 30 - 1) // W == 2
    assert all(int(a["held"]) <= FOLD.peak(160) for a in spans)
    # the last step brought the request to 69 positions: two summary pages
    # and one of window, where 9 pages would hold them unfolded
    deepest = max(spans, key=lambda a: int(a["depth_pages"]))
    assert (int(deepest["held"]), int(deepest["depth_pages"])) \
        == (FOLD.entries(69), 9)


# ------------------------------------------------------------ what is refused
@pytest.mark.parametrize("kw,reason", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(speculate=2), "speculate"),
    (dict(multi_token=2), "multi_token"),
    (dict(prefill_chunk=24), "prefill_chunk"),
    (dict(page_size=4), "page_size"),
    (dict(num_pages=8), "cannot hold"),
])
def test_what_is_refused_with_folding_says_why(net, kw, reason):
    args = dict(max_batch_size=2, max_len=160, page_size=PS,
                prefix_cache=False)
    args.update(kw)
    with pytest.raises(MXNetError, match=reason):
        InferenceEngine(net, **args)


def test_folded_pages_do_not_migrate_and_nothing_is_scored(net):
    eng = InferenceEngine(net, max_batch_size=2, max_len=160, page_size=PS,
                          prefix_cache=False)
    for call in (lambda: eng.export_pages([1, 2, 3, 4, 5]),
                 lambda: eng.import_pages({})):
        with pytest.raises(MXNetError, match="whole window"):
            call()
    with pytest.raises(MXNetError, match="contiguous"):
        eng.score([1, 2, 3])
    # no engine argument of its own: a default pool holds every slot's
    # widest table
    assert eng.stats()["pages"]["pages"] == 2 * FOLD.peak(160)


# ------------------------------------------------------------- the count
def test_the_count_follows_the_folding():
    work = builder.work
    D, L = 64, 2
    assert work.matmul_params(CFG) == L * (4 * D * D + 3 * D * 128) + D * 320
    assert work.weight_bytes(CFG, 1) == 2 * (work.matmul_params(CFG) + D)
    assert list(work.rows_read(CFG, [0, 31, 32, 100])) == [1, 32, 9, 29]
    # a token at depth 101 reads 29 rows of a key and a value, every layer
    assert work.cache_bytes(CFG, 101) == 29 * 2 * D * 2 * L
    # past the first window the cache grows by 1 / chunk a position
    assert work.cache_bytes(CFG, 32 * 9) - work.cache_bytes(CFG, 32 * 8) \
        == 8 * 2 * D * 2 * L
    flops = work.forward_flops(CFG, 8, 56)         # ends the second window
    attn = L * 4 * 16 * 4 * sum(t % 32 + 1 + 8 for t in range(56, 64))
    fold = L * 32 * 4 * 6 * 16
    assert flops == 2.0 * work.matmul_params(CFG) * 8 + attn + fold
    assert work.forward_flops(CFG, 8, 48) \
        == 2.0 * work.matmul_params(CFG) * 8 \
        + L * 4 * 16 * 4 * sum(t % 32 + 1 + 8 for t in range(48, 56))
    s_flops, s_bytes = work.kernel_count(CFG, "eva_summarize", 8, 56)
    assert s_flops == fold and s_bytes == L * 2 * D * 2 * (32 + 8)
    assert work.kernel_count(CFG, "eva_summarize", 8, 48) == (0.0, 0)
    a_flops, a_bytes = work.kernel_count(CFG, "eva_attn", 8, 56)
    assert a_flops == attn + fold
    assert a_bytes == L * D * 2 * (4 * 8 + 2 * 40) + s_bytes
    with pytest.raises(ValueError):
        work.kernel_count(CFG, "flash", 1, 0)
