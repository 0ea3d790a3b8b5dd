"""Cohere2-MoE (Command A+'s language model) on the CPU at a tiny size (4
layers: sliding x3 and full, hidden 64, 8 heads over 2 KV heads of 16, a
window of 24 positions, a router over 16 experts of width 32 of which the
experts 4-7 are held, 4 a token, 2 shared): the program against the
benchmark's plain float32 reference (``bench/reference/cohere2_moe.py``), the
dropless expert layer and its share, the paged read under a window, the page
pool's windowed kind, and the engine's two ledgers while requests live.

Tolerances. Program and reference are both float32 here and compute the same
sums in different orders (the program a running softmax over pages and a
grouped product a tile at a time, the reference one softmax over a masked row
and every expert over every row): logits of size 1 agree to a few 1e-6, and
2e-5 holds them while a gain left out, the shared experts summed instead of
averaged or a window one key too wide moves them by 1e-3 and more (asserted
below).
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
import run as harness  # noqa: E402  (sets up the ``mxbench`` alias)

harness.alias_package(os.path.join(harness.BENCH, "tests"))

from mxbench.models import cohere2_moe as builder  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.models import llama  # noqa: E402
from mxnet_tpu.ndarray import NDArray  # noqa: E402
from mxnet_tpu.ops import moe  # noqa: E402
from mxnet_tpu.serve import InferenceEngine  # noqa: E402
from mxnet_tpu.serve.paging import (OutOfPages, PagePool,  # noqa: E402
                                    WindowedPages, pages_for)

ref, work = builder.ref, builder.work
CFG = json.load(open(os.path.join(harness.BENCH, "tests", "configs",
                                  "cohere2-moe-tiny.json")))
BIG = json.load(open(os.path.join(harness.BENCH, "configs",
                                  "command-a-plus.json")))
SEED = 36
Z = ref.sizes(CFG)
W, K, E, NL = Z["window"], Z["k"], Z["E"], Z["L"]
PS = 8
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
    args = dict(max_batch_size=4, max_len=192, page_size=PS,
                prefill_chunk=16, min_prompt_bucket=4, prefix_cache=False)
    args.update(kw)
    return InferenceEngine(net, **args).start()


def ref_logits(params, seq, cfg=CFG):
    return np.asarray(ref.logits(params, jnp.asarray([seq]), cfg))[0]


# ------------------------------------------------------------- the forward
@pytest.mark.parametrize("T", [5, W, 2 * W + 7, 100])
def test_forward_is_the_references(net, params, T):
    seq = prompt(T, seed=T)
    got = np.asarray(net(NDArray(jnp.asarray([seq])))._data)[0]
    np.testing.assert_allclose(got, ref_logits(params, seq), rtol=0,
                               atol=ATOL)


@pytest.mark.parametrize("fault", ["gain", "shared_sum", "window", "rope"])
def test_a_fault_in_the_reference_fails_the_comparison(net, params, fault):
    """What the tolerance is tight enough to see: a layer's gain left at 1,
    the shared experts summed and not averaged, a window one key wider, the
    pairs of the rotary embedding taken as halves."""
    seq = prompt(3 * W, seed=7)
    cfg, p = dict(CFG), params
    if fault == "gain":
        layers = dict(params["layers"])
        layers["norm"] = [jnp.ones_like(g) for g in layers["norm"]]
        p = {**params, "layers": layers}
    elif fault == "shared_sum":
        layers = dict(params["layers"])
        layers["sdown_w"] = [w * Z["S"] for w in layers["sdown_w"]]
        p = {**params, "layers": layers}
    elif fault == "window":
        cfg["sliding_window"] = W + 1
    want = ref_logits(p, seq, cfg)
    if fault == "rope":
        old, ref._rope_pairs = ref._rope_pairs, _rope_halves
        try:
            want = ref_logits(params, seq)
        finally:
            ref._rope_pairs = old
    got = np.asarray(net(NDArray(jnp.asarray([seq])))._data)[0]
    assert np.abs(got - want).max() > 1e-3


def _rope_halves(x, t, theta):
    """The other convention (``models/llama._rope``'s), on the reference's
    ``[..., T, hd]``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = t.astype(jnp.float32)[:, None] * inv
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


# ----------------------------------------------------- the experts' share
def _layer_weights(seed=3, T=40):
    """One layer's weights over ALL 16 experts and some normed rows."""
    rng = np.random.RandomState(seed)
    z = dict(Z, held=(0, E))
    p = {leaf: jnp.asarray(rng.normal(
        1.0 if leaf == "norm" else 0.0, 0.15, shape).astype(np.float32))
        for leaf, shape in ref.layer_shapes(z).items()}
    x = jnp.asarray(rng.standard_normal((T, Z["D"])).astype(np.float32))
    return z, p, x


def _share(p, first, count):
    return {**p, **{leaf: p[leaf][first:first + count]
                    for leaf in ("gate_w", "up_w", "down_w")}}


def test_the_shares_add_up_to_the_uncut_layer():
    """The parts that the four shares of 4 experts give, with what every
    chip computes alike (attention, the shared experts) counted once, are
    the uncut reference layer."""
    z, p, x = _layer_weights()
    h = ref._ln(x, p["norm"], z["eps"])
    whole = ref.layer(x, p, z, ref.SLIDING)
    parts = sum(ref.routed_part(h, _share(p, f, 4), z, (f, 4))
                for f in range(0, E, 4))
    again = (x + ref.attention(h, p, z, ref.SLIDING) + parts
             + ref.shared_part(h, p, z))
    np.testing.assert_allclose(np.asarray(again), np.asarray(whole), rtol=0,
                               atol=ATOL)
    # and a share alone is not the layer: the others' part is real
    one = ref.routed_part(h, _share(p, 4, 4), z, (4, 4))
    assert np.abs(np.asarray(parts - one)).max() > 1e-2


@pytest.mark.parametrize("first,count", [(0, 4), (4, 4), (12, 4), (0, 16)])
def test_routed_experts_is_the_references_share(first, count):
    """The program's dropless layer, told which experts it holds, against
    the reference given the same share; the counts are the router's."""
    z, p, x = _layer_weights(seed=5, T=70)
    h = ref._ln(x, p["norm"], z["eps"])
    sh = _share(p, first, count)
    got, tokens = moe.routed_experts(
        h, sh["router_w"], sh["gate_w"], sh["up_w"], sh["down_w"],
        held=(first, count), k=K)
    want = ref.routed_part(h, sh, z, (first, count))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=ATOL)
    w = np.asarray(ref.route(h, p["router_w"], z))
    np.testing.assert_array_equal(
        np.asarray(tokens), (w[:, first:first + count] > 0).sum(0))
    assert int(tokens.sum()) <= 70 * K


def test_a_rows_result_is_bitwise_the_same_alone_and_in_a_batch():
    """Dropless: nothing a row gets depends on who else is routed. One
    program, the other rows real or padding (which is routed nowhere)."""
    z, p, x = _layer_weights(seed=9, T=16)
    h = ref._ln(x, p["norm"], z["eps"])
    sh = _share(p, 4, 8)
    call = lambda valid: moe.routed_experts(            # noqa: E731
        h, sh["router_w"], sh["gate_w"], sh["up_w"], sh["down_w"],
        held=(4, 8), k=K, valid=jnp.asarray(valid))
    full, n_full = call(np.ones(16, bool))
    for r in (0, 7, 15):
        alone, n_alone = call(np.arange(16) == r)
        assert np.array_equal(np.asarray(alone[r]), np.asarray(full[r]))
        assert not np.asarray(alone)[np.arange(16) != r].any()
        assert int(n_alone.sum()) <= K < int(n_full.sum())


# ------------------------------------------------- the read under a window
def _dense_window(q, k, v, pos, window):
    """Plain attention of query rows at ``pos + t`` over keys ``0 ..``."""
    B, H, T, hd = q.shape
    G = k.shape[1]
    qf = q.reshape(B, G, H // G, T, hd) / np.sqrt(hd)
    s = np.einsum("bgrtd,bgjd->bgrtj", qf, k)
    i = (pos[:, None] + np.arange(T)[None])[:, None, None, :, None]
    j = np.arange(k.shape[2])[None, None, None, None, :]
    s = np.where((j <= i) & (j > i - window), s, -np.inf)
    a = np.exp(s - s.max(-1, keepdims=True))
    a = a / a.sum(-1, keepdims=True)
    return np.einsum("bgrtj,bgjd->bgrtd", a, v).reshape(B, H, T, hd)


@pytest.mark.parametrize("T,form", [(1, "lanes"), (32, "heads")])
@pytest.mark.parametrize("depths", [
    (300, 410, 350),        # every row deep: the first block walked is past 0
    (5, 460, 200),          # a shallow row: the deep row's window has not
                            # begun in the first blocks walked
    (0, 130, -1),           # a row at its first position; an inactive row
])
def test_paged_attention_under_a_window(T, form, depths):
    """Both forms of the walk with ``window``, against dense masked
    attention, over a table whose pages behind each row's window point at
    the sink as ``PagePool.slide`` leaves them."""
    H, G, hd, ps, maxp, window = 8, 2, 16, 16, 32, 100
    assert llama.walk_form(H, T) == form
    assert llama.kv_block(ps, maxp) == 128 < 300
    rng = np.random.RandomState(T + len(depths))
    B, L = len(depths), ps * maxp
    k_all, v_all = (rng.standard_normal((B, G, L, hd)).astype(np.float32)
                    for _ in range(2))
    q = rng.standard_normal((B, H, T, hd)).astype(np.float32)
    n_pages = B * maxp
    sink = n_pages
    kp = np.zeros((n_pages + 1, ps, G * hd), np.float32)
    vp = np.zeros_like(kp)
    # garbage in the sink: what a dereferenced entry reads
    kp[sink], vp[sink] = 1e3, -1e3
    table = np.full((B, maxp), sink, np.int32)
    pos = np.asarray([max(d, 0) for d in depths], np.int32)
    for b, d in enumerate(depths):
        if d < 0:
            continue                                     # serves no request
        lay = WindowedPages(ps, window, T)
        for i in range(lay.first_live(d), pages_for(d + T, ps)):
            table[b, i] = b * maxp + i
            rows = slice(i * ps, (i + 1) * ps)
            kp[b * maxp + i] = k_all[b, :, rows].transpose(1, 0, 2) \
                .reshape(ps, G * hd)
            vp[b * maxp + i] = v_all[b, :, rows].transpose(1, 0, 2) \
                .reshape(ps, G * hd)
    new_k = np.stack([k_all[b, :, p:p + T] for b, p in enumerate(pos)])
    new_v = np.stack([v_all[b, :, p:p + T] for b, p in enumerate(pos)])
    out, kp2, _ = llama._paged_attention(
        jnp.asarray(q), jnp.asarray(new_k), jnp.asarray(new_v),
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(pos), H // G, window=window)
    out = np.asarray(out)
    assert np.isfinite(out).all()
    want = _dense_window(q, k_all, v_all, pos, window)
    for b, d in enumerate(depths):
        if d >= 0:          # (a row that serves no request: finite, unread)
            np.testing.assert_allclose(out[b], want[b], rtol=0, atol=ATOL)
    # without the window the same call reads the sink's garbage behind it
    plain, _, _ = llama._paged_attention(
        jnp.asarray(q), jnp.asarray(new_k), jnp.asarray(new_v),
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(pos), H // G)
    if max(depths) > window + ps:
        assert np.abs(np.asarray(plain) - want).max() > 1e-2


def test_without_a_window_the_traced_read_is_unchanged():
    """``window=None`` traces none of the window's operations: no lower
    bound in the mask, no guard, a loop from 0."""
    H, G, hd, ps, maxp = 8, 2, 16, 16, 8
    args = (jnp.zeros((2, H, 1, hd)), jnp.zeros((2, G, 1, hd)),
            jnp.zeros((2, G, 1, hd)), jnp.zeros((9, ps, G * hd)),
            jnp.zeros((9, ps, G * hd)), jnp.zeros((2, maxp), jnp.int32),
            jnp.zeros(2, jnp.int32))
    plain = str(jax.make_jaxpr(
        lambda *a: llama._paged_attention(*a, H // G))(*args))
    windowed = str(jax.make_jaxpr(
        lambda *a: llama._paged_attention(*a, H // G, window=40))(*args))
    assert "is_finite" not in plain and "isneginf" not in plain
    assert plain.count(" ge ") == 0 < windowed.count(" ge ")
    assert plain.count("select_n") < windowed.count("select_n")


# ------------------------------------------------------- the windowed kind
@pytest.mark.parametrize("window,chunk,ps", [(24, 16, 8), (4096, 1024, 128),
                                             (100, 7, 16)])
def test_a_windowed_slot_never_holds_more_than_its_bound(window, chunk, ps):
    """Prefill in chunks, then decode to ``max_len``: slide and lease as the
    engine does; the slot never holds more than ``ceil((window + chunk) /
    page_size) + 1`` pages, and the ledger stays consistent."""
    max_len = ps * 20 * max(1, window // (ps * 4))
    lay = WindowedPages(ps, window, chunk)
    assert lay.held_bound == pages_for(window + chunk, ps) + 1
    pool = PagePool(lay.held_bound + 2, ps, max_len, 2, prefix_cache=False,
                    layout=lay)
    pos, most = 0, 0
    P = max_len // 2 + 3
    while pos < max_len:
        end = min(pos + chunk, P) if pos < P else pos + 1
        pool.slide(0, pos)
        pool.lease(0, end)
        most = max(most, pool.held(0))
        # every column the dispatch reads or writes is leased
        row = pool.table(0)
        for col in range(max(pos - window + 1, 0), end):
            assert row[col // ps] != pool.sink
        pos = end
    pool.check_consistent()
    assert most <= lay.held_bound
    assert pool.pages_in_use() == pool.held(0) <= lay.held_bound
    assert pool.pages_recycled == pages_for(max_len, ps) - pool.held(0)
    pool.release(0)
    pool.check_consistent()
    assert pool.pages_in_use() == 0 and pool.held(0) == 0


def test_a_windowed_lease_is_all_or_nothing_and_refuses_a_prefix_cache():
    lay = WindowedPages(8, 24, 16)
    pool = PagePool(lay.held_bound, 8, 192, 2, prefix_cache=False, layout=lay)
    pool.lease(0, 40)
    before = pool.table(1).copy()
    with pytest.raises(OutOfPages):
        pool.lease(1, 40)
    assert (pool.table(1) == before).all() and pool.held(1) == 0
    pool.check_consistent()
    with pytest.raises(MXNetError, match="windowed pool shares no prefix"):
        PagePool(8, 8, 192, 2, prefix_cache=True, layout=lay)
    with pytest.raises(MXNetError, match="cannot hold"):
        PagePool(lay.held_bound - 1, 8, 192, 2, prefix_cache=False,
                 layout=lay)


# ------------------------------------------------ the cache, row by row
def by_hand(net, seqs, n_prompts, chunk):
    """Prefill each ``seqs[b][:n_prompts[b]]`` in chunks of ``chunk`` (the
    last one padded, as a bucket is), a row at a time, then decode ALL rows
    in one batch a position at a time, each at its own depth, through
    ``forward_cached_paged`` over pools and two tables of this function's
    own, sliding the windowed one as the engine does: every position's
    logits, the most windowed pages a row held, the counts' sum."""
    B = len(seqs)
    maxp = pages_for(max(len(s) for s in seqs), PS) + 1
    lay = WindowedPages(PS, W, chunk)
    n_full, n_win = B * maxp, B * lay.held_bound
    caches = [NDArray(jnp.zeros(s, d)) for s, d in
              net.cache_spec_paged((n_full + 1, n_win + 1), PS)]
    free = [list(range(n_full))[::-1], list(range(n_win))[::-1]]
    sinks = (n_full, n_win)
    table = np.stack([np.full((B, maxp), sinks[0], np.int32),
                      np.full((B, maxp), sinks[1], np.int32)], axis=1)
    out = [[] for _ in seqs]
    most, routed = 0, 0

    def lease(b, pos, end):
        nonlocal most
        for i in range(lay.first_live(pos)):
            if table[b, 1, i] != sinks[1]:
                free[1].append(int(table[b, 1, i]))
                table[b, 1, i] = sinks[1]
        for kind in (0, 1):
            for i in range(pages_for(end, PS)):
                if table[b, kind, i] == sinks[kind] and \
                        (kind == 0 or i >= lay.first_live(pos)):
                    table[b, kind, i] = free[kind].pop()
        most = max(most, int((table[b, 1] != sinks[1]).sum()))

    def run(rows, ids, pos, valid):
        nonlocal caches, routed
        tbl = np.stack([np.full((len(rows), maxp), sinks[0], np.int32),
                        np.full((len(rows), maxp), sinks[1], np.int32)], 1)
        for r, b in enumerate(rows):
            if b is not None:
                tbl[r] = table[b]
        logits, *caches, counts = net.forward_cached_paged(
            NDArray(jnp.asarray(ids, jnp.int32)),
            NDArray(jnp.asarray(pos, jnp.int32)), NDArray(tbl),
            NDArray(jnp.asarray(valid, jnp.int32)), *caches)
        assert counts.shape == net.expert_counts()[:2]
        routed += int(np.asarray(counts._data).sum())
        return np.asarray(logits._data)

    for b, (seq, n) in enumerate(zip(seqs, n_prompts)):
        lo = 0
        while lo < n:
            ids = list(seq[lo:min(lo + chunk, n)])
            lease(b, lo, lo + len(ids))
            got = run([b], [ids + [0] * (chunk - len(ids))], [lo],
                      [len(ids)])
            out[b].extend(got[0, :len(ids)])
            lo += len(ids)
    pos = list(n_prompts)
    while any(p < len(s) for p, s in zip(pos, seqs)):
        rows = [b if pos[b] < len(seqs[b]) else None for b in range(B)]
        for b in rows:
            if b is not None:
                lease(b, pos[b], pos[b] + 1)
        got = run(rows, [[seqs[b][pos[b]]] if b is not None else [0]
                         for b in rows],
                  [pos[b] if b is not None else 0 for b in rows], [1] * B)
        for b in range(B):
            if rows[b] is not None:
                out[b].append(got[b, 0])
                pos[b] += 1
    return [np.asarray(o) for o in out], most, routed


@pytest.mark.parametrize("chunk", [4, 16, 32])
def test_chunks_then_steps_several_windows_deep(net, params, chunk):
    """Three rows decoding in one batch at different depths, to five
    windows deep: prompts of 70, 9 and 41 positions (the last chunks padded)
    and 50, 111 and 30 decoded steps. The logits of every position are the
    reference's full forward's; no row ever holds more windowed pages than
    its bound; padding and rows that serve no request are routed nowhere."""
    seqs = [list(prompt(n, seed=chunk + n)) for n in (120, 120, 71)]
    got, most, routed = by_hand(net, seqs, [70, 9, 41], chunk)
    for seq, g in zip(seqs, got):
        np.testing.assert_allclose(g, ref_logits(params, seq), rtol=0,
                                   atol=ATOL)
    assert most <= WindowedPages(PS, W, chunk).held_bound
    assert most < pages_for(120, PS)
    assert routed == sum(_held_assignments(params, s) for s in seqs)


def _held_assignments(params, seq):
    """Assignments to held experts over every layer, by the reference's own
    router on the reference's own hidden states."""
    x = params["embed"].astype(jnp.float32)[jnp.asarray(seq)]
    first, count = Z["held"]
    n = 0
    for i, kind in enumerate(Z["kinds"]):
        p = {leaf: a[i] for leaf, a in params["layers"].items()}
        h = ref._ln(x, p["norm"], Z["eps"])
        w = np.asarray(ref.route(h, p["router_w"], Z))
        n += int((w[:, first:first + count] > 0).sum())
        x = ref.layer(x, p, Z, kind)
    return n


# ------------------------------------------------------------- the engine
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
    assert gaps.max() <= ATOL, gaps.max()


@pytest.mark.parametrize("lookahead", [True, False])
def test_engine_slides_in_prefill_and_in_decode(net, params, lookahead):
    """Four requests under continuous batching, prompts shorter and longer
    than the window, decoding several windows on: every served token is the
    reference's, pages come back while the requests live, both ledgers end
    empty and consistent, and the counts are the reference router's."""
    ps = [prompt(30, 1), prompt(64, 2), prompt(100, 3), prompt(7, 4)]
    ns = [10, 40, 50, 90]
    eng = engine(net, lookahead=lookahead)
    bound = eng._wpages.layout.held_bound
    try:
        got = served(eng, ps, ns)
        stats = eng.stats()
        eng._pages.check_consistent()
        eng._wpages.check_consistent()
    finally:
        eng.shutdown()
    for p, toks, n in zip(ps, got, ns):
        assert_references_greedy(params, p, toks, n)
    assert stats["preemptions"] == 0
    assert stats["pages"]["pages_in_use"] == 0
    assert stats["window_pages"]["pages_in_use"] == 0
    assert stats["window_pages_recycled"] > 0
    assert 0 < stats["window_pages_held"] < \
        0.7 * stats["window_pages_unwindowed"]
    assert 0 < stats["window_walk_blocks"] <= stats["kv_walk_blocks"]
    assert bound == pages_for(W + 16, PS) + 1
    # every position but a request's last token went through the layers
    # once; under the lookahead a row that retires has one more step in
    # flight, whose tokens are dropped and whose routing was real
    tokens = sum(len(p) + n - 1 for p, n in zip(ps, ns))
    here = sum(_held_assignments(params, (list(p) + toks)[:-1])
               for p, toks in zip(ps, got))
    if lookahead:
        assert 0 <= stats["moe_assignments"] - tokens * K * NL \
            <= len(ps) * K * NL
        assert 0 <= stats["moe_assignments_here"] - here <= len(ps) * K * NL
    else:
        assert stats["moe_assignments"] == tokens * K * NL
        assert stats["moe_assignments_here"] == here
    assert 1 / (NL * Z["held"][1]) <= stats["moe_expert_tokens_max"] < 0.5


def test_preemption_gives_back_both_kinds_and_both_ledgers_stay_consistent(
        net, params):
    """A full pool too small for three long requests at once: the youngest
    is preempted, its pages of BOTH kinds go back, it prefills again from 0
    (its window slides again) and every token is still the reference's."""
    ps = [prompt(90, 11), prompt(80, 12), prompt(70, 13)]
    ns = [30, 30, 30]
    eng = engine(net, max_batch_size=3, num_pages=34)
    try:
        got = served(eng, ps, ns)
        stats = eng.stats()
        eng._pages.check_consistent()
        eng._wpages.check_consistent()
    finally:
        eng.shutdown()
    assert stats["preemptions"] >= 1
    assert stats["pages"]["pages_in_use"] == 0
    assert stats["window_pages"]["pages_in_use"] == 0
    assert stats["window_pages"]["pages"] == 3 * (pages_for(W + 16, PS) + 1)
    for p, toks, n in zip(ps, got, ns):
        assert_references_greedy(params, p, toks, n)


def test_spans_carry_the_windowed_kind_and_the_experts(net, tmp_path):
    import glob

    from jax.profiler import ProfileData
    eng = engine(net, max_batch_size=1, lookahead=False)
    eng.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        served(eng, [prompt(60, 31)], [40])
    finally:
        jax.profiler.stop_trace()
        eng.shutdown()
    path = sorted(glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb"))[-1]
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("mx.serve.")]
    disp = [a for n, a in events if n in ("mx.serve.prefill_dispatch",
                                          "mx.serve.decode_dispatch")]
    assert disp and all("wheld" in a and "wwalk" in a for a in disp)
    bound = pages_for(W + 16, PS) + 1
    assert all(int(a["wheld"]) <= bound for a in disp)
    deepest = max(disp, key=lambda a: int(a["wdepth_pages"]))
    # the last step brought the request to 99 positions: 13 pages unwindowed,
    # of which the window's 24 positions lie in 4
    assert int(deepest["wdepth_pages"]) == 13 and int(deepest["wheld"]) == 4
    assert all(int(a["wwalk"]) <= int(a["walk"]) for a in disp)
    emits = [a for n, a in events if n == "mx.serve.emit" and "moe_hit" in a]
    assert len(emits) == 39
    for a in emits:
        assert int(a["moe_held"]) == NL * Z["held"][1]
        assert int(a["moe_routed"]) == K * NL
        assert 0 <= int(a["moe_hit"]) <= int(a["moe_here"]) <= K * NL


# ------------------------------------------------------------ what is refused
@pytest.mark.parametrize("kw,reason", [
    (dict(prefix_cache=True), "prefix_cache=True cannot serve a model with "
                              "a windowed kind"),
    (dict(speculate=2), "speculate cannot serve a model with a windowed"),
    (dict(multi_token=2), "multi_token > 1 cannot serve a model with a "
                          "windowed"),
    (dict(num_pages=8), "cannot hold"),
])
def test_what_is_refused_with_a_windowed_kind_says_why(net, kw, reason):
    args = dict(max_batch_size=2, max_len=192, page_size=PS,
                prefix_cache=False)
    args.update(kw)
    with pytest.raises(MXNetError, match=reason):
        InferenceEngine(net, **args)


def test_windowed_pages_do_not_migrate_and_nothing_is_scored(net):
    eng = engine(net)
    try:
        with pytest.raises(MXNetError, match="windowed kind of pool"):
            eng.export_pages(prompt(20))
        with pytest.raises(MXNetError, match="windowed kind of pool"):
            eng.import_pages({"tokens": [], "pages": []})
        with pytest.raises(MXNetError):
            eng.score(prompt(10))
    finally:
        eng.shutdown()


# ------------------------------------------------------------------ the count
def test_the_count_follows_the_experts_and_the_window():
    c = BIG
    D, F = c["hidden_size"], c["intermediate_size"]
    expert = 3 * D * F * 2
    # bytes grow with the rows, from one expert a layer towards the 16 held
    one, eight, many = (work.weight_bytes(c, r) for r in (1, 8, 4096))
    assert one < eight < many
    assert many - one == pytest.approx(4 * 15 * expert, rel=1e-3)
    assert work.experts_touched(c, 16) == pytest.approx(10.3, abs=0.05)
    fixed = 4 * (142_606_336 + 4 * 50_331_648 + 524_288 + 4096) \
        + 134_217_728 + 4096
    assert one == int((fixed + 4 * 50_331_648) * 2)
    # 9.47 GB of weights as the issue counts them
    assert fixed + 4 * 16 * 50_331_648 == 4_733_292_544
    # the cache a token reads: one full layer's rows, three windows
    row = 2 * 8 * 128 * 2
    assert work.cache_bytes(c, 100) == 4 * 100 * row
    assert work.cache_bytes(c, 4096) == 4 * 4096 * row
    assert work.cache_bytes(c, 30000) == (30000 + 3 * 4096) * row
    # operations: a token's 8 experts times 16 / 128 held, the four shared
    tok = work.forward_flops(c, 1, 0)
    assert tok == pytest.approx(
        2 * (4 * (142_606_336 + 524_288 + 5 * 50_331_648) + 134_217_728)
        + 4 * 128 * 128 * 4, rel=1e-9)
    deep = work.forward_flops(c, 1, 20000) - tok
    assert deep == 128 * 128 * 4 * (20000 + 3 * 4095)
    ops, nbytes = work.kernel_count(c, "moe_experts", 1024, 8192)
    assert ops == 2.0 * 4 * 1024 * 50_331_648
    assert nbytes == pytest.approx(4 * 16 * expert, rel=0.02)
    a_ops, a_bytes = work.kernel_count(c, "paged_attn", 1024, 8192)
    assert a_ops == work.forward_flops(c, 1024, 8192) \
        - work.forward_flops(c, 1024, 0) + work._attn_flops(
            work._sizes(c), 1024, 0)
    assert a_bytes > work.cache_bytes(c, 9216)
    with pytest.raises(ValueError):
        work.kernel_count(c, "eva_attn", 1, 0)


def test_the_configuration_is_the_sources_and_says_its_cut():
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if "command-a-plus" in line) \
        if os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl") else None
    c = BIG
    assert c["reduced"] == ["num_hidden_layers", "layer_types",
                            "num_experts", "vocab_size"]
    if row is not None:
        for key, value in row["config"].items():
            assert key in c, key
            if key not in c["reduced"]:
                assert c[key] == value, key
    assert c["published"]["num_experts"] == 128
    assert c["experts_held"] == [0, 16] and c["num_experts"] == 16
    assert c["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert c["deployment"]["chips_sharing_a_layer"] == 8
    assert (c["hidden_size"], c["intermediate_size"], c["head_dim"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["num_experts_per_tok"], c["num_shared_experts"],
            c["sliding_window"]) == (4096, 4096, 128, 128, 8, 8, 4, 4096)
