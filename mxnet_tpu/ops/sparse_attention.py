"""Block-sparse softmax attention over a paged cache (InfLLM v2, the sparse
layers of MiniCPM4, arXiv:2506.07900): a page of the pool is a selection
block, and a query attends only the blocks it selects.

Beside the K and V pages a layer keeps *compressed keys*: the mean of
``kernel`` consecutive keys every ``stride`` positions, ``block // stride`` of
them a page (compressed key ``j`` of a row covers positions
``[stride * j, stride * j + kernel)`` and lives in the page of position
``stride * j``). A query at position ``t`` scores the compressed keys that are
complete at ``t`` (``softmax(q K_c^T / sqrt(hd))`` a head, summed over the
query heads that share the key/value head), a block's score is the largest of
the compressed keys that overlap it, and the blocks read are

    the first ``init_blocks``, the blocks that hold the last ``window``
    positions, and the best-scored others up to ``topk`` blocks in all;

below ``dense_len`` positions of context every live block is read. The
selection is data: page ids gathered through the block table. One program
serves every depth, as the paged walk of ``models/llama.py`` does.

**Decode** (one new position a row) gathers the selected pages only, ``bp``
pages an iteration under a running float32 softmax, and the trip count is the
most blocks any active row selected. **A prefill chunk** (``T > 1``) walks
every live page once for all its queries and applies the selection as a mask,
so its cost is the dense walk's; a gathered read per query tile is not built
(``PERF.md`` section 7).

``blocks_read`` is the same arithmetic on the host, for the engine's
counters.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["SparseConfig", "sparse_paged_attention", "blocks_read",
           "compressed_per_page"]

_NEG = -1e30
#: pages one iteration of the decode read gathers for each (row, kv head)
READ_PAGES = 16
#: tokens one iteration of a prefill chunk's walk holds
WALK_TOKENS = 256
#: queries scored against the compressed keys at a time (a [heads, tile,
#: compressed keys] float32 array is the largest the scoring makes)
SCORE_TILE = 128


@dataclasses.dataclass(frozen=True)
class SparseConfig:
    """MiniCPM4's ``sparse_config``. ``block`` is the page size."""
    block: int = 64
    kernel: int = 32
    stride: int = 16
    init_blocks: int = 1
    window: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        if self.kernel != 2 * self.stride or self.block % self.stride:
            raise ValueError(
                "sparse attention: kernel must be twice the stride and the "
                "block a multiple of it (a block's score pools its own "
                "compressed keys and the last one of the block before)")
        if self.init_blocks < 1:
            raise ValueError("sparse attention: init_blocks must be >= 1 "
                             "(position 0 anchors the running softmax)")


def compressed_per_page(sc: SparseConfig) -> int:
    return sc.block // sc.stride


def _max_selected(sc: SparseConfig, maxp: int) -> int:
    """The most blocks a query reads: ``topk``, or every block of a context
    still under ``dense_len``."""
    return min(maxp, max(sc.topk, -(-sc.dense_len // sc.block)))


def blocks_read(sc: SparseConfig, pos: int, T: int):
    """``(read, live)``: blocks of one layer's cache that the program reads
    for a row whose ``T`` new positions start at ``pos``, of the blocks the
    row holds then. One position reads its selection; a chunk walks every
    live block once."""
    last = pos + T - 1
    live = last // sc.block + 1
    if T > 1 or last + 1 <= sc.dense_len:
        return live, live
    first_window = max((last - sc.window + 1) // sc.block, 0)
    forced = live - first_window + min(sc.init_blocks, first_window)
    return min(live, max(sc.topk, forced)), live


# ------------------------------------------------------------------ writes
def _write(kh, vh, k_pages, v_pages, block_table, cols):
    """Scatter the T new K/V rows through the table; columns past the
    table's reach go to the sink page (the last)."""
    ps, maxp = k_pages.shape[2], block_table.shape[1]
    sink = k_pages.shape[0] - 1
    pg = jnp.take_along_axis(block_table,
                             jnp.minimum(cols // ps, maxp - 1), axis=1)
    pg = jnp.where(cols < maxp * ps, pg, jnp.int32(sink))
    off = cols % ps
    k_pages = k_pages.at[pg, :, off, :].set(
        kh.transpose(0, 2, 1, 3).astype(k_pages.dtype))
    v_pages = v_pages.at[pg, :, off, :].set(
        vh.transpose(0, 2, 1, 3).astype(v_pages.dtype))
    return k_pages, v_pages


def _compress(k_pages, kc_pages, block_table, pos, valid, T, sc):
    """Write the compressed keys whose last position lies among the row's
    ``valid`` new positions ``[pos, pos + valid)``: each the float32 mean of
    its ``kernel`` keys, read back from the pages (the first of them may be
    positions of an earlier chunk)."""
    ps, maxp = sc.block, block_table.shape[1]
    pb = compressed_per_page(sc)
    sink = k_pages.shape[0] - 1
    n_cand = -(-T // sc.stride)
    j = (pos[:, None] - sc.kernel + sc.stride) // sc.stride \
        + jnp.arange(n_cand, dtype=jnp.int32)[None, :]             # [B, n]
    done = (j >= 0) & (sc.stride * j + sc.kernel - 1
                       < (pos + valid)[:, None])
    tok = sc.stride * j[:, :, None] + jnp.arange(sc.kernel,
                                                 dtype=jnp.int32)
    tok = jnp.clip(tok, 0, maxp * ps - 1)                          # [B,n,k]
    B = tok.shape[0]
    pg = jnp.take_along_axis(block_table, (tok // ps).reshape(B, -1),
                             axis=1).reshape(tok.shape)
    rows = k_pages[pg, :, tok % ps, :].astype(jnp.float32)       # [B,n,k,G,hd]
    mean = rows.mean(axis=2)                                       # [B,n,G,hd]
    jp = jnp.clip(j // pb, 0, maxp - 1)
    dst = jnp.where(done, jnp.take_along_axis(block_table, jp, axis=1),
                    jnp.int32(sink))
    return kc_pages.at[dst, :, j % pb, :].set(mean.astype(kc_pages.dtype))


# --------------------------------------------------------------- selection
def _selection_scores(q, kc, t, sc, maxp):
    """``q`` [B, G, rep, Tq, hd], ``kc`` [B, G, maxp * pb, hd] the row's
    compressed keys in table order, ``t`` [B, Tq] the queries' positions.
    Returns [B, G, Tq, maxp]: +inf for a block that is read whatever its
    score (the first ones, the window, every live block under ``dense_len``),
    the pooled score for the other live blocks, -inf beyond the row's
    depth."""
    hd = q.shape[-1]
    pb = compressed_per_page(sc)
    s = jnp.einsum("bgrtd,bgjd->bgrtj", q, kc,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    j = jnp.arange(maxp * pb, dtype=jnp.int32)
    whole = (sc.stride * j + sc.kernel - 1)[None, None, :] \
        <= t[:, :, None]                                           # [B,Tq,J]
    whole = whole[:, None, None]
    s = jnp.where(whole, s, _NEG)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True)) * whole
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    p = p.sum(axis=2)                                              # [B,G,Tq,J]
    p = p.reshape(p.shape[:-1] + (maxp, pb))
    own = p.max(axis=-1)
    before = jnp.pad(p[..., -1], ((0, 0),) * 3 + ((1, 0),))[..., :-1]
    score = jnp.maximum(own, before)                               # [B,G,Tq,P]
    b = jnp.arange(maxp, dtype=jnp.int32)
    tq = t[:, None, :, None]
    live = b <= tq // sc.block
    always = (b < sc.init_blocks) | (b >= (tq - sc.window + 1) // sc.block) \
        | (tq + 1 <= sc.dense_len)
    return jnp.where(live, jnp.where(always, jnp.inf, score), -jnp.inf)


def _top_blocks(sel, t, sc):
    """``sel`` [..., Tq, maxp] selection scores, ``t`` [B, Tq] -> ``(blocks
    [..., Tq, n], ok)``: the blocks read, best first, and which of the ``n``
    entries count. Exactly ``topk`` past ``dense_len``: neighbouring blocks
    share a compressed key, so equal scores are common, and of equals the
    block that comes first in the sequence wins (``lax.top_k``'s order)."""
    n_max = _max_selected(sc, sel.shape[-1])
    top, blocks = jax.lax.top_k(sel, n_max)
    limit = jnp.where(t + 1 <= sc.dense_len, n_max, min(sc.topk, n_max))
    rank = jnp.arange(n_max, dtype=jnp.int32)
    ok = (top > -jnp.inf) & (rank < limit[:, None, :, None])
    return blocks, ok


@jax.named_scope("mx.sparse_select")
def _select_mask(q, kc, t, sc, maxp):
    """[B, G, T, maxp] bool: the blocks each of a chunk's queries reads."""
    B, G, rep, T, hd = q.shape

    def tile(args):
        qt, tt = args
        blocks, ok = _top_blocks(_selection_scores(qt, kc, tt, sc, maxp),
                                 tt, sc)
        return jnp.put_along_axis(
            jnp.zeros(ok.shape[:-1] + (maxp,), bool), blocks, ok, axis=-1,
            inplace=False)

    if T <= SCORE_TILE or T % SCORE_TILE:
        return tile((q, t))
    nt = T // SCORE_TILE
    qs = q.reshape(B, G, rep, nt, SCORE_TILE, hd).transpose(3, 0, 1, 2, 4, 5)
    ts = t.reshape(B, nt, SCORE_TILE).transpose(1, 0, 2)
    out = jax.lax.map(tile, (qs, ts))                      # [nt,B,G,tile,P]
    return out.transpose(1, 2, 0, 3, 4).reshape(B, G, T, maxp)


@jax.named_scope("mx.sparse_select")
def _select_pages(q, kc, t, sc, maxp):
    """One query a row: ``(blocks [B, G, n], ok [B, G, n])``, the blocks it
    reads, best first, and which entries count."""
    blocks, ok = _top_blocks(_selection_scores(q, kc, t, sc, maxp), t, sc)
    return blocks[:, :, 0], ok[:, :, 0]


# ------------------------------------------------------------------- reads
def _softmax_step(carry, s, mask, vb, eq):
    """One block of a running float32 softmax: ``s`` scores, ``mask`` what
    counts of them, ``vb`` the block's values."""
    m, l, acc = carry
    s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
    scale = jnp.exp(m - m_new)
    l = l * scale + p.sum(axis=-1)
    acc = acc * scale[..., None] + jnp.einsum(
        eq, p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
    return m_new, l, acc


def _read_selected(qh, k_pages, v_pages, block_table, blocks, ok, t, rep):
    """Decode: ``qh`` [B, H, 1, hd] attends the tokens of its selected
    pages, ``READ_PAGES`` pages an iteration, as many iterations as the
    row that selected most needs."""
    B, H, _, hd = qh.shape
    P1, G, ps, _ = k_pages.shape
    sink = P1 - 1
    n_max = blocks.shape[-1]
    bp = min(READ_PAGES, n_max)
    pad = -n_max % bp
    blocks = jnp.pad(blocks, ((0, 0), (0, 0), (0, pad)))
    ok = jnp.pad(ok, ((0, 0), (0, 0), (0, pad)))
    active = block_table[:, 0] != sink
    ok = ok & active[:, None, None]
    phys = jnp.take_along_axis(
        jnp.broadcast_to(block_table[:, None, :], (B, G) + block_table.shape[1:]),
        blocks, axis=-1)
    # [P * G, ps, hd]: a (page, kv head) pair is one gathered slice
    flat = jnp.where(ok, phys, sink) * G \
        + jnp.arange(G, dtype=jnp.int32)[None, :, None]
    kf = k_pages.reshape(P1 * G, ps, hd)
    vf = v_pages.reshape(P1 * G, ps, hd)
    n_it = (jnp.max(ok.sum(axis=-1)) + bp - 1) // bp
    q = qh.reshape(B, G, rep, hd).astype(k_pages.dtype)
    scale = 1.0 / math.sqrt(hd)

    def step(i, carry):
        idx = jax.lax.dynamic_slice_in_dim(flat, i * bp, bp, axis=2)
        blk = jax.lax.dynamic_slice_in_dim(blocks, i * bp, bp, axis=2)
        good = jax.lax.dynamic_slice_in_dim(ok, i * bp, bp, axis=2)
        kb = kf[idx].reshape(B, G, bp * ps, hd)
        vb = vf[idx].reshape(B, G, bp * ps, hd)
        col = (blk[..., None] * ps
               + jnp.arange(ps, dtype=jnp.int32)).reshape(B, G, bp * ps)
        mask = jnp.repeat(good, ps, axis=-1) & (col <= t[:, None, None])
        s = jnp.einsum("bgrd,bgjd->bgrj", q, kb,
                       preferred_element_type=jnp.float32) * scale
        return _softmax_step(carry, s, mask[:, :, None], vb,
                             "bgrj,bgjd->bgrd")

    m0 = jnp.full((B, G, rep), _NEG, jnp.float32)
    l0 = jnp.zeros((B, G, rep), jnp.float32)
    acc0 = jnp.zeros((B, G, rep, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_it, step, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, 1, hd).astype(qh.dtype)


def _walk_masked(qh, k_pages, v_pages, block_table, cols, selected, rep):
    """A prefill chunk: query row t of batch row b (at column ``cols[b,
    t]``) attends the columns ``j <= cols[b, t]`` of the blocks
    ``selected[b, g, t]`` holds, one span of pages an iteration over every
    live page."""
    B, H, T, hd = qh.shape
    P1, G, ps, _ = k_pages.shape
    maxp = block_table.shape[1]
    sink = P1 - 1
    bp = min(max(WALK_TOKENS // ps, 1), maxp)
    span = bp * ps
    pad = -maxp % bp
    table = jnp.pad(block_table, ((0, 0), (0, pad)), constant_values=sink)
    selected = jnp.pad(selected, ((0, 0),) * 3 + ((0, pad),))
    last = jnp.minimum(cols, maxp * ps - 1)
    active = block_table[:, 0] != sink
    live = jnp.max(jnp.where(active, last[:, -1], 0)) + 1
    n_it = (live + span - 1) // span
    q = qh.reshape(B, G, rep, T, hd).astype(k_pages.dtype)
    scale = 1.0 / math.sqrt(hd)
    kt = k_pages.transpose(0, 2, 1, 3)
    vt = v_pages.transpose(0, 2, 1, 3)

    def step(i, carry):
        pages = jax.lax.dynamic_slice_in_dim(table, i * bp, bp, axis=1)
        kb = kt[pages].reshape(B, span, G, hd)
        vb = vt[pages].reshape(B, span, G, hd)
        col = i * span + jnp.arange(span, dtype=jnp.int32)
        causal = col[None, None, :] <= last[:, :, None]           # [B,T,span]
        sel = jnp.repeat(jax.lax.dynamic_slice_in_dim(
            selected, i * bp, bp, axis=3), ps, axis=-1)           # [B,G,T,span]
        mask = (sel & causal[:, None])[:, :, None]
        s = jnp.einsum("bgrtd,bjgd->bgrtj", q, kb,
                       preferred_element_type=jnp.float32) * scale
        return _softmax_step(carry, s, mask, vb, "bgrtj,bjgd->bgrtd")

    m0 = jnp.full((B, G, rep, T), _NEG, jnp.float32)
    l0 = jnp.zeros((B, G, rep, T), jnp.float32)
    acc0 = jnp.zeros((B, G, rep, T, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_it, step, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, T, hd).astype(qh.dtype)


# jitted by itself, like llama's paged attention: a program of many layers
# traces and lowers it once
@jax.named_scope("mx.sparse_attn")
@functools.partial(jax.jit, static_argnames=("rep", "sc"))
def sparse_paged_attention(qh, kh, vh, k_pages, v_pages, kc_pages,
                           block_table, pos, valid, rep, sc: SparseConfig):
    """``qh`` [B, H, T, hd], ``kh``/``vh`` [B, G, T, hd]: the row's T new
    positions from ``pos`` [B], ``valid`` [B] of them real. Pools
    ``[pages + 1, G, block, hd]`` (K, V) and ``[pages + 1, G, block //
    stride, hd]`` (compressed keys); the last page of each is the sink.
    Returns ``(out [B, H, T, hd], k_pages, v_pages, kc_pages)``."""
    B, H, T, hd = qh.shape
    G = k_pages.shape[1]
    maxp = block_table.shape[1]
    pb = compressed_per_page(sc)
    pos = jnp.asarray(pos, jnp.int32)
    cols = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    k_pages, v_pages = _write(kh, vh, k_pages, v_pages, block_table, cols)
    with jax.named_scope("mx.kv_compress"):
        kc_pages = _compress(k_pages, kc_pages, block_table, pos,
                             jnp.asarray(valid, jnp.int32), T, sc)
    # the row's compressed keys in table order: [B, G, maxp * pb, hd]
    kc = kc_pages[block_table].transpose(0, 2, 1, 3, 4).reshape(
        B, G, maxp * pb, hd)
    t = jnp.minimum(cols, maxp * sc.block - 1)
    q = qh.reshape(B, G, rep, T, hd).astype(kc.dtype)
    if T == 1:
        blocks, ok = _select_pages(q, kc, t, sc, maxp)
        out = _read_selected(qh, k_pages, v_pages, block_table, blocks, ok,
                             t[:, 0], rep)
    else:
        selected = _select_mask(q, kc, t, sc, maxp)
        out = _walk_masked(qh, k_pages, v_pages, block_table, cols,
                           selected, rep)
    return out, k_pages, v_pages, kc_pages
