"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
ICLR 2023, arXiv:2302.04542), as EvaByte's ``attention_class: "eva"`` uses it:
exact softmax attention inside the query's own window of ``window``
positions, and outside it one pooled key/value pair for every chunk of
``chunk`` positions, all under one normaliser.

With ``s = hd ** -0.5``, per head two learned vectors ``phi`` and ``mu``, and
``q``, ``k`` already rotated:

- the summary of chunk ``j`` (positions ``j c .. j c + c - 1``):
  ``a_ji = softmax_i(s phi . k_i)`` over the chunk's positions,
  ``k~_j = sum_i a_ji k_i + mu``, ``v~_j = sum_i a_ji v_i``;
- the output at ``t``, ``W(t) = t // window``: ONE softmax over the positions
  ``i <= t`` of window ``W(t)`` (score ``s q_t . k_i``, value ``v_i``) and over
  every chunk of every earlier window (score ``s q_t . k~_j``, value
  ``v~_j``).

Three things live here:

- :func:`eva_attention`, the definition over a whole sequence with no cache;
- :class:`FoldedPages`, the arithmetic of a paged cache that *folds*: a
  window's summaries are ``window / chunk`` rows, which is one page when
  ``page_size == window / chunk``, so a request's table is its finished
  windows' summary pages, one each, followed by the pages of the window it is
  in. Position ``t`` lies at the folded column ``page_size * (t // window) +
  t % window``, every summary column before every window column, so the
  causal walk over the folded columns (``models/llama._walk_pages``) *is*
  EVA's softmax. The model hands this object to the serving engine
  (``cache_fold``); the page pool counts a request's need with it;
- :func:`fold_windows`, the device side of a fold: for each row whose real
  positions end a window, read the window's pages, write their summaries
  into the page that follows them in the row's table.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

__all__ = ["FoldedPages", "summarize", "eva_attention", "fold_windows"]


@dataclasses.dataclass(frozen=True)
class FoldedPages:
    """A folded page table's arithmetic, for the host (ints) and the device
    (arrays) alike. ``window``: positions a window; ``page_size``: rows a
    page, which is also the summaries a window leaves."""
    window: int
    page_size: int

    @property
    def window_pages(self) -> int:
        return self.window // self.page_size

    def column(self, pos):
        """The folded column of position ``pos``."""
        return pos // self.window * self.page_size + pos % self.window

    def entries(self, depth: int) -> int:
        """Table entries of the dispatch that brings a request to ``depth``:
        the summaries before the window of position ``depth - 1``, that
        window's pages so far and, where ``depth`` ends the window, the page
        its summaries go to."""
        if depth <= 0:
            return 0
        done, last = divmod(int(depth) - 1, self.window)
        return (done + -(-(last + 1) // self.page_size)
                + (last + 1 == self.window))

    def peak(self, depth: int) -> int:
        """The widest table on the way from 0 to ``depth``: the last
        dispatch's, or that of the last window's end before it."""
        return max(self.entries(depth),
                   self.entries(depth - depth % self.window))

    def ends_window(self, depth: int) -> bool:
        """Whether the dispatch that brings a request to ``depth`` ends a
        window (and so folds it)."""
        return depth > 0 and depth % self.window == 0


def summarize(k, v, phi, mu, chunk: int):
    """``k``, ``v`` ``[..., n * chunk, H, hd]`` -> the chunks' summaries
    ``(k~, v~)`` ``[..., n, H, hd]`` in float32; ``phi``, ``mu`` ``[H,
    hd]``."""
    *lead, T, H, hd = k.shape
    kc = k.astype(jnp.float32).reshape(*lead, T // chunk, chunk, H, hd)
    vc = v.astype(jnp.float32).reshape(*lead, T // chunk, chunk, H, hd)
    logits = jnp.einsum("...chd,hd->...ch", kc,
                        phi.astype(jnp.float32)) / math.sqrt(hd)
    a = jax.nn.softmax(logits, axis=-2)[..., None]
    return ((a * kc).sum(axis=-3) + mu.astype(jnp.float32),
            (a * vc).sum(axis=-3))


def eva_attention(q, k, v, phi, mu, chunk: int, window: int):
    """The definition, no cache: ``q``, ``k``, ``v`` ``[B, H, T, hd]`` (rotary
    positions applied) -> ``[B, H, T, hd]``. A window of queries at a time:
    its own keys under the causal mask beside the summaries of every chunk
    of the windows before it, one softmax."""
    B, H, T, hd = q.shape
    pad = -T % window
    qf, kf, vf = (jnp.pad(x.astype(jnp.float32),
                          ((0, 0), (0, 0), (0, pad), (0, 0)))
                  for x in (q, k, v))
    ks, vs = summarize(kf.transpose(0, 2, 1, 3), vf.transpose(0, 2, 1, 3),
                       phi, mu, chunk)                      # [B, n, H, hd]
    ks, vs = ks.transpose(0, 2, 1, 3), vs.transpose(0, 2, 1, 3)
    causal = jnp.tril(jnp.ones((window, window), bool))
    outs = []
    for w in range((T + pad) // window):
        lo, n = w * window, w * (window // chunk)
        keys = jnp.concatenate([ks[:, :, :n], kf[:, :, lo:lo + window]], 2)
        vals = jnp.concatenate([vs[:, :, :n], vf[:, :, lo:lo + window]], 2)
        mask = jnp.concatenate([jnp.ones((window, n), bool), causal], 1)
        s = jnp.einsum("bhtd,bhjd->bhtj", qf[:, :, lo:lo + window],
                       keys) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhtj,bhjd->bhtd", p, vals))
    return jnp.concatenate(outs, axis=2)[:, :, :T].astype(q.dtype)


def fold_windows(k_pages, v_pages, block_table, pos, valid, phi, mu,
                 fold: FoldedPages, chunk: int):
    """The device side of a fold. Pools ``[pages + 1, page_size, H * hd]``
    (``models/llama._paged_attention``'s), ``block_table`` ``[B, entries]``,
    ``pos`` ``[B]`` each row's true first new position, ``valid`` ``[B]``
    how many of its new positions are real. A row whose real positions end a
    window (and whose table is leased) has, in its table, the window's pages
    at entries ``W .. W + window_pages - 1`` (``W = pos // window``) and
    behind them the page the host leased for the summaries: the window's
    keys and values are read as they lie, summarised a chunk at a time, and
    the ``page_size`` summaries written to that page, whole.

    Only the rows that fold are visited (a loop whose trip count is their
    number: a dispatch that ends no window does none of this), one row a
    trip, so what is live at once is one window in float32."""
    B = block_table.shape[0]
    ps, width = k_pages.shape[1], k_pages.shape[2]
    H, hd = phi.shape
    wp = fold.window_pages
    sink = k_pages.shape[0] - 1
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (B,))
    ends = ((block_table[:, 0] != sink) & (valid > 0)
            & ((pos + valid) % fold.window == 0))
    order = jnp.argsort(~ends, stable=True)         # the rows that fold first
    # the table as wide as the widest read: entries W .. W + wp
    table = jnp.pad(block_table, ((0, 0), (0, wp + 1)), constant_values=sink)

    def one(i, pools):
        kp, vp = pools
        b = order[i].astype(jnp.int32)
        entries = jax.lax.dynamic_slice(
            table, (b, pos[b] // fold.window), (1, wp + 1))[0]
        pages, dst = entries[:wp], entries[wp]
        ks, vs = summarize(kp[pages].reshape(fold.window, H, hd),
                           vp[pages].reshape(fold.window, H, hd),
                           phi, mu, chunk)
        kp = jax.lax.dynamic_update_index_in_dim(
            kp, ks.reshape(ps, width).astype(kp.dtype), dst, 0)
        vp = jax.lax.dynamic_update_index_in_dim(
            vp, vs.reshape(ps, width).astype(vp.dtype), dst, 0)
        return kp, vp

    return jax.lax.fori_loop(0, jnp.sum(ends, dtype=jnp.int32), one,
                             (k_pages, v_pages))
