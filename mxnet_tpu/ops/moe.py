"""Routed experts over the experts *held here*, dropless.

An expert layer that is told which experts it holds: the router scores every
token over all ``E`` experts the model has, each token keeps its ``k`` best,
and this call computes the part of the result that the ``count`` experts
``[first, first + count)`` give, for the tokens routed to them. What the
other experts would add is left out (their chips compute it; on one chip
nothing stands in for them). Held experts ``(0, E)`` is the whole layer.

    s   = sigmoid(h Wr)                               # [N, E]
    top = the k largest of s;  w_e = s_e / sum_{e' in top} s_e'
    F_e(h) = (silu(h Wg_e) * (h Wu_e)) Wd_e
    y   = sum_{e in top, e held} w_e F_e(h)

**No capacity, nothing dropped, no ``[N, E, capacity]`` tensor.** The ``N x k``
assignments are ordered by expert (held ones first, one stable sort), the
tokens gathered in that order, and each held expert multiplies its own run of
rows in tiles of ``ROW_TILE`` under a loop whose trip count is data: an expert
that received nothing reads none of its weights (a decode step of a few rows
reads the few experts they chose, not all that are held), and one that
received many takes as many tiles as it needs. Every shape is static; only
trip counts follow the routing.

**A row's result does not depend on who else is in the batch**: it is the sum,
in the order of its own top-k, of its held experts' outputs for its own row,
each a product of that row alone with the expert's matrices; other rows only
decide where in a tile the row lies.

Scopes: ``mx.moe_route`` (router, top-k, ordering, combine) and
``mx.moe_experts`` (the grouped products); the caller wraps both, and its
shared experts (``mx.moe_shared``), in ``mx.moe``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["routed_experts", "ROW_TILE"]

#: rows of one tile of an expert's product. An expert of 3 x 4096 x 4096
#: bf16 is 100.7 MB, 123 us of HBM time on a v5e; 128 rows of it are 66 us of
#: MXU time, so a tile stays bound by the weights it must read anyway. A
#: constant, not a knob.
ROW_TILE = 128


def _scores(logits, score: str):
    if score == "sigmoid":
        return jax.nn.sigmoid(logits)
    if score == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    raise ValueError(f"unknown expert selection function {score!r}")


# jitted by itself so that a program of many layers traces and lowers the
# layer once (as models/llama._paged_attention is)
@functools.partial(jax.jit,
                   static_argnames=("held", "k", "score", "normalize"))
def routed_experts(h, Wr, Wg, Wu, Wd, held, k, score="sigmoid",
                   normalize=True, valid=None):
    """``h`` [N, D] tokens; ``Wr`` [D, E] the router over all experts;
    ``Wg``, ``Wu`` [count, D, F] and ``Wd`` [count, F, D] the held experts'
    matrices; ``held = (first, count)``; ``k`` experts a token; ``valid``
    [N] bool, tokens that are real (padding is routed nowhere).

    Returns ``(y [N, D] in h's dtype, tokens [count] int32)``: the held
    experts' part of the layer's output, and how many tokens each received.
    """
    N, D = h.shape
    first, count = held
    M = N * k
    tile = min(ROW_TILE, -(-M // 8) * 8)
    with jax.named_scope("mx.moe_route"):
        s = _scores(jnp.einsum("nd,de->ne", h, Wr,
                               preferred_element_type=jnp.float32), score)
        top, idx = jax.lax.top_k(s, k)                              # [N, k]
        w = top / top.sum(axis=-1, keepdims=True) if normalize else top
        local = idx - first
        here = (local >= 0) & (local < count)
        if valid is not None:
            here = here & valid[:, None]
        # held assignments first, by expert; the rest behind them
        key = jnp.where(here, local, count).reshape(M)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                        dtype=jnp.int32)                            # [count]
        ends = jnp.cumsum(sizes)
        # a tile may start up to its own length before the list's end
        xs = jnp.pad(h[order // k], ((0, tile), (0, 0)))
    with jax.named_scope("mx.moe_experts"):
        ys = jnp.zeros((M + tile, D), h.dtype)
        rows = jnp.arange(tile, dtype=jnp.int32)
        for e in range(count):
            end = ends[e]
            lo = end - sizes[e]

            def body(t, ys, e=e, lo=lo, end=end):
                start = lo + t * tile
                x = jax.lax.dynamic_slice_in_dim(xs, start, tile)
                a = jnp.einsum("td,df->tf", x, Wg[e],
                               preferred_element_type=jnp.float32)
                b = jnp.einsum("td,df->tf", x, Wu[e],
                               preferred_element_type=jnp.float32)
                y = jnp.einsum("tf,fd->td",
                               (jax.nn.silu(a) * b).astype(x.dtype), Wd[e],
                               preferred_element_type=jnp.float32)
                # the tile's tail lies in the next expert's run: keep what
                # is there
                old = jax.lax.dynamic_slice_in_dim(ys, start, tile)
                mine = (start + rows < end)[:, None]
                return jax.lax.dynamic_update_slice_in_dim(
                    ys, jnp.where(mine, y.astype(ys.dtype), old), start, 0)

            ys = jax.lax.fori_loop(0, (sizes[e] + tile - 1) // tile, body,
                                   ys)
    with jax.named_scope("mx.moe_route"):
        # where each assignment's row went, then a token's own k in its own
        # order (rows of assignments not held here were never written: zeros)
        at = jnp.argsort(order).reshape(N, k)
        y = jnp.einsum("nk,nkd->nd", jnp.where(here, w, 0.0),
                       ys[at].astype(jnp.float32))
    return y.astype(h.dtype), sizes
