"""The read side of ``models/llama._paged_attention``: a loop over blocks of
logical pages whose trip count is the deepest active row's, under a running
float32 softmax, in two forms (``llama.walk_form``): few query columns meet
each block as the pool stores it, heads on the lanes; a chunk's meet it split
into heads.

Held against ``_attend`` over the gathered contiguous view (what the read
was before the loop) with queries as served, in bfloat16, to a tolerance set
from that dtype which a single hidden column breaks; the two forms against
each other on one input; and the walk against itself bit for bit where the
contract says so: a row alone and beside a deeper row, ``T = 1`` against
column j of a ``T = K`` of the same form.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import GPTModel
from mxnet_tpu.models import llama
from mxnet_tpu.models.gpt import GPTConfig
from mxnet_tpu.models.llama import (_attend, _paged_attention, _walk_pages,
                                    kv_block, walk_form)
from mxnet_tpu.serve import InferenceEngine

G, HD = 3, 16


def gathered_reference(qh, kh, vh, k_pages, v_pages, table, pos, rep,
                       hide=None):
    """The same write, then ``_attend`` over all ``max_pages`` pages of
    every row as one float32 ``[B, G, L, hd]`` view. ``hide`` ``[B]``: one
    column a row that its queries do not see (the wrong answer that the
    tolerance has to tell from the right one)."""
    B, H, T, hd = qh.shape
    G = H // rep
    ps, maxp = k_pages.shape[1], table.shape[1]
    L = maxp * ps
    cols = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    pg = jnp.take_along_axis(table, jnp.minimum(cols // ps, maxp - 1), axis=1)
    pg = jnp.where(cols < L, pg, k_pages.shape[0] - 1)
    # a pool is [pages, ps, G * hd]: one row a token, its heads side by side
    k_pages = k_pages.at[pg, cols % ps].set(
        kh.transpose(0, 2, 1, 3).reshape(B, T, G * hd).astype(k_pages.dtype))
    v_pages = v_pages.at[pg, cols % ps].set(
        vh.transpose(0, 2, 1, 3).reshape(B, T, G * hd).astype(v_pages.dtype))
    kf = k_pages[table].reshape(B, L, G, hd).transpose(0, 2, 1, 3)
    vf = v_pages[table].reshape(B, L, G, hd).transpose(0, 2, 1, 3)
    mask = jnp.arange(L)[None, None, :] <= cols[:, :, None]
    if hide is not None:
        mask &= jnp.arange(L)[None, None, :] != hide[:, None, None]
    out = _attend(qh, kf.astype(jnp.float32), vf.astype(jnp.float32), mask,
                  rep)
    return out, k_pages, v_pages


def make(ps, maxp, depths, T, rep, seed=0, g=G, hd=HD):
    """Random pools, a table of distinct pages per row, and the T new rows
    of q/k/v for rows at ``depths``, all in bfloat16, as served."""
    rng = onp.random.default_rng(seed)
    B = len(depths)
    n = B * maxp
    pools = [jnp.asarray(rng.standard_normal((n + 1, ps, g * hd)),
                         jnp.bfloat16) for _ in range(2)]
    table = jnp.asarray(rng.permutation(n).reshape(B, maxp), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, g * rep, T, hd)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((B, g, T, hd)), jnp.bfloat16)
            for _ in range(2))
    return q, k, v, pools[0], pools[1], table, jnp.asarray(depths, jnp.int32)


paged = jax.jit(_paged_attention, static_argnums=7)
reference = jax.jit(gathered_reference, static_argnums=7)


def f32(*arrays):
    return [a.astype(jnp.float32) for a in arrays]


def within(out, want):
    """Largest error of ``out`` (bfloat16, from queries as served) against
    the float32 reference ``want`` over each batch row, in units of the
    tolerance: three roundings to bfloat16 (half of ``eps`` each: the
    output's, and the softmax weights' on their way into the second
    product, which the chip's matrix unit takes in the values' dtype) of
    the largest output of the query's row. NaN counts as outside."""
    out, want = (onp.asarray(a, onp.float32) for a in (out, want))
    tol = 1.5 * float(jnp.finfo(jnp.bfloat16).eps) \
        * onp.abs(want).max(axis=-1, keepdims=True)
    units = onp.nan_to_num(onp.abs(out - want) / tol, nan=onp.inf)
    return units.reshape(len(units), -1).max(axis=1)


def held_to_the_reference(args, rep):
    """The walk agrees with the gathered reference in every batch row, and
    the reference with one column hidden from each row (the middle one of
    those it sees) does not, in any: the tolerance tells a wrong column from
    a right one, however deep the row."""
    q, k, v, kp, vp, table, pos = args
    out, kp_new, vp_new = paged(*args, rep)
    assert out.dtype == q.dtype == jnp.bfloat16
    exact = (*f32(q, k, v), kp, vp, table, pos)
    want, kp_w, vp_w = reference(*exact, rep)
    assert (within(out, want) <= 1).all(), within(out, want)
    wrong, _, _ = reference(*exact, rep, pos // 2)
    assert (within(wrong, want) > 2).all(), within(wrong, want)
    assert bool((kp_new == kp_w).all()) and bool((vp_new == vp_w).all())
    return out, kp_new

# (page size, pages a row, T, depths): the block is 128 tokens
GEOMETRIES = {
    "decode-rows-end-in-different-blocks": (16, 16, 1, [5, 127, 128, 250]),
    "verify-T4-across-a-block-edge": (16, 16, 4, [0, 126, 200]),
    "chunk-with-pad-columns-past-L": (16, 16, 32, [240, 3]),
    "L-smaller-than-the-block": (8, 4, 1, [3, 20, 31]),
    "table-no-multiple-of-the-block": (16, 12, 1, [5, 130, 190]),
    "table-no-multiple-chunk-past-L": (16, 12, 16, [180, 60]),
}


@pytest.mark.parametrize("rep", [1, 2])
@pytest.mark.parametrize("name", list(GEOMETRIES))
def test_walk_equals_attend_over_the_gathered_view(name, rep):
    ps, maxp, T, depths = GEOMETRIES[name]
    held_to_the_reference(make(ps, maxp, depths, T, rep), rep)


@pytest.mark.parametrize("g, hd, rep, T", [(5, 40, 3, 1), (5, 40, 3, 4),
                                           (25, 64, 1, 1), (2, 128, 4, 16)],
                         ids=["grouped-200-lanes", "grouped-200-lanes-T4",
                              "xl-1600-lanes", "grouped-256-lanes-chunk"])
def test_row_width_need_not_be_whole_lane_tiles(g, hd, rep, T):
    """A pool's row is ``G * hd`` lanes whatever that comes to: grouped
    heads whose product is no multiple of 128 (200), GPT-2 XL's 1,600, and
    one that is (256)."""
    args = make(16, 16, [7, 129, 230], T, rep, seed=3, g=g, hd=hd)
    assert args[3].shape == (3 * 16 + 1, 16, g * hd)
    out, kp = held_to_the_reference(args, rep)
    assert kp.shape == args[3].shape and out.shape == args[0].shape


# (kv heads, head width, query heads a kv head, T, form): what a decode step
# and a verify of 4 bring, of EvaByte's group of heads, GPT-2 XL and a llama
# of 8 kv heads x 4; N = heads x T on either side of one lane tile
SERVED = {
    "evabyte-group-16x128-decode": (16, 128, 1, 1, "lanes"),
    "evabyte-group-16x128-verify-4": (16, 128, 1, 4, "lanes"),
    "xl-25x64-decode": (25, 64, 1, 1, "lanes"),
    "xl-25x64-verify-4": (25, 64, 1, 4, "lanes"),
    "xl-25x64-bucket-8": (25, 64, 1, 8, "heads"),
    "gqa-8x4-decode": (8, 64, 4, 1, "lanes"),
    "gqa-8x4-N-128": (8, 64, 4, 4, "lanes"),
    "gqa-8x4-N-160-first-above": (8, 64, 4, 5, "heads"),
}


@pytest.mark.parametrize("name", list(SERVED))
def test_served_geometries_in_the_form_they_take(name):
    g, hd, rep, T, form = SERVED[name]
    assert walk_form(g * rep, T) == form
    held_to_the_reference(
        make(16, 16, [3, 126, 250], T, rep, seed=5, g=g, hd=hd), rep)


@pytest.mark.parametrize("name", [n for n in SERVED if SERVED[n][4] == "lanes"])
def test_the_two_forms_agree_on_one_input(name, monkeypatch):
    """The same queries over the same pools, multiplied as the pool stores
    a block and (no column being few enough) split into heads: two roundings
    apart, the weights' and the output's."""
    g, hd, rep, T, _ = SERVED[name]
    q, k, v, kp, vp, table, pos = make(16, 16, [3, 126, 250], T, rep, seed=5,
                                       g=g, hd=hd)
    _, kp, vp = paged(q, k, v, kp, vp, table, pos, rep)
    cols = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    # a new function each, so that the second is traced anew
    lanes = jax.jit(lambda *a: _walk_pages(*a, rep))(q, kp, vp, table, cols)
    monkeypatch.setattr(llama, "WALK_LANES", 0)
    assert walk_form(g * rep, T) == "heads"
    heads = jax.jit(lambda *a: _walk_pages(*a, rep))(q, kp, vp, table, cols)
    assert not onp.array_equal(*f32(lanes, heads))
    assert (within(lanes, heads.astype(jnp.float32)) <= 1).all()


def test_forms_on_either_side_of_a_lane_tile_agree():
    """8 x 4 heads over T = 4 is N = 128, the last that is multiplied on
    the lanes; T = 5 is the first split into heads. Their first four columns
    are the same queries over the same pages."""
    g, hd, rep = 8, 64, 4
    q, k, v, kp, vp, table, pos = make(16, 16, [40, 200], 5, rep, seed=7,
                                       g=g, hd=hd)
    assert llama.WALK_LANES == 128 == g * rep * 4
    five, kp, vp = paged(q, k, v, kp, vp, table, pos, rep)
    four, _, _ = paged(q[:, :, :4], k[:, :, :4], v[:, :, :4], kp, vp, table,
                       pos, rep)
    assert (within(four, five[:, :, :4].astype(jnp.float32)) <= 1).all()


@pytest.mark.parametrize("rep", [1, 2])
def test_row_is_bitwise_alone_and_beside_a_deeper_row(rep):
    """The trip count is the deepest row's; a block the mask hides is an
    exact no-op, so the shallow row does not see who else is there."""
    q, k, v, kp, vp, table, pos = make(16, 16, [20, 250], 1, rep)
    both, _, _ = paged(q, k, v, kp, vp, table, pos, rep)
    alone, _, _ = paged(q[:1], k[:1], v[:1], kp, vp, table[:1], pos[:1], rep)
    assert onp.array_equal(onp.asarray(both[0].astype(jnp.float32)),
                           onp.asarray(alone[0].astype(jnp.float32)))


@pytest.mark.parametrize("rep", [1, 2])
@pytest.mark.parametrize("j", [0, 1, 2, 3])
def test_decode_is_bitwise_column_j_of_verify(j, rep):
    """Column j of one T = K forward is what the sequential T = 1 decode
    at ``pos + j`` computes (rows past it are written already, as after a
    rejected draft, and the causal mask hides them)."""
    K = 4
    q, k, v, kp, vp, table, pos = make(16, 16, [126, 40], K, rep)
    assert walk_form(q.shape[1], K) == walk_form(q.shape[1], 1) == "lanes"
    wide, kp, vp = paged(q, k, v, kp, vp, table, pos, rep)
    one, _, _ = paged(q[:, :, j:j + 1], k[:, :, j:j + 1], v[:, :, j:j + 1],
                      kp, vp, table, pos + j, rep)
    assert onp.array_equal(onp.asarray(one[:, :, 0].astype(jnp.float32)),
                           onp.asarray(wide[:, :, j].astype(jnp.float32)))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("what", ["one-while", "no-f32-of-width-max_len",
                                  "no-pool-sized-value-in-the-loop",
                                  "no-f32-of-a-block's-size-in-the-loop",
                                  "no-transpose-in-the-loop"])
def test_jaxpr_of_the_walk(what):
    ps, maxp = 16, 16                       # L = 256, unlike any other size
    args = make(ps, maxp, [5, 200, 90], 1, 1)
    jaxpr = jax.make_jaxpr(_paged_attention, static_argnums=7)(*args, 1).jaxpr
    whiles = [e for e in _eqns(jaxpr) if e.primitive.name == "while"]
    if what == "one-while":
        assert len(whiles) == 1
    elif what == "no-f32-of-width-max_len":
        wide = [v.aval for e in _eqns(jaxpr) for v in e.outvars
                if v.aval.dtype == jnp.float32 and ps * maxp in v.aval.shape]
        assert not wide
        return
    body = whiles[0].params["body_jaxpr"].jaxpr
    if what == "no-pool-sized-value-in-the-loop":
        pages = args[3].shape[0]
        big = [v.aval for e in _eqns(body) for v in e.outvars
               if v.aval.shape and v.aval.shape[0] == pages]
        assert not big
    elif what == "no-f32-of-a-block's-size-in-the-loop":
        # T = 1: a block is multiplied as gathered, [3, 128, G * hd] bf16
        block = 3 * kv_block(ps, maxp) * G * HD
        large = [v.aval for e in _eqns(body) for v in e.outvars
                 if v.aval.size >= block]
        assert large and all(a.dtype == jnp.bfloat16 for a in large)
    else:
        assert not [e for e in _eqns(body) if e.primitive.name == "transpose"]


@pytest.mark.parametrize("second_row", ["inactive-stale-pos", "active"])
def test_inactive_row_with_a_stale_pos_does_not_lengthen_the_walk(second_row):
    """Row 0 is 20 deep; its pages past block 0 hold NaN. A masked block is
    0 x NaN = NaN in the accumulator, so row 0 stays finite exactly when
    the walk ends after block 0. Row 1 sits at 200: as an inactive row (its
    table all sink) that is a stale ``pos`` and must not count."""
    ps, maxp, rep = 16, 16, 1
    q, k, v, kp, vp, table, _ = make(ps, maxp, [20, 200], 1, rep)
    sink = kp.shape[0] - 1
    poisoned = table[0, kv_block(ps, maxp) // ps:]
    vp = vp.at[poisoned].set(jnp.nan)
    if second_row == "inactive-stale-pos":
        table = table.at[1].set(sink)
    out, _, _ = paged(q, k, v, kp, vp, table, jnp.asarray([20, 200]), rep)
    finite = bool(jnp.isfinite(out[0]).all())
    assert finite == (second_row == "inactive-stale-pos")


@pytest.mark.parametrize("geometry, want", [
    ((16, 64), 128), ((8, 4), 32), ((16, 12), 128), ((256, 4), 256)])
def test_block_is_whole_pages_clipped_to_the_table(geometry, want):
    assert kv_block(*geometry) == want
    assert want % geometry[0] == 0 and llama.KV_BLOCK == 128


@pytest.fixture(scope="module")
def engine():
    mx.random.seed(0)
    net = GPTModel(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                             num_heads=4, max_position_embeddings=256,
                             dropout=0.0))
    net.initialize()
    # four heads over 64 tokens are 256 query columns: a chunk and a bucket
    # split a block into heads, a step multiplies it on the lanes
    eng = InferenceEngine(net, max_batch_size=2, max_len=256, page_size=16,
                          prefill_chunk=64, min_prompt_bucket=64)
    eng.start()
    yield eng
    eng.shutdown()


@pytest.mark.parametrize("prompt, deep", [(5, False), (150, True)])
def test_stats_sum_the_walk_and_the_table(engine, prompt, deep):
    """max_len 256 is two blocks. A short request walks one of them in
    every dispatch; a 150-token prompt reaches the second."""
    before = engine.stats()
    # no prefix in common with the other case's prompt
    r = engine.submit((onp.arange(prompt) + prompt) % 64, 6).result(120)
    assert r.ok
    after = engine.stats()
    walked = after["kv_walk_blocks"] - before["kv_walk_blocks"]
    table = after["kv_table_blocks"] - before["kv_table_blocks"]
    assert table >= 2 * 6 and table % 2 == 0
    if deep:
        assert table // 2 < walked <= table
    else:
        assert walked == table // 2
    # of the walked, the blocks of the decode dispatches were multiplied as
    # the pool stores them, and none of a chunk's or a bucket's: 64 + 64 and
    # a bucket that ends at 192 walk 1 + 1 + 2 blocks, a prompt of 5 one
    prefills, prefill_walk = (3, 4) if deep else (1, 1)
    on_lanes = after["kv_walk_blocks_on_lanes"] \
        - before["kv_walk_blocks_on_lanes"]
    assert on_lanes == walked - prefill_walk
    assert on_lanes == (table // 2 - prefills) * (2 if deep else 1)
