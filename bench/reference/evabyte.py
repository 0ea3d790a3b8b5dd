"""Plain reference for EvaByte: weights from a seed and the forward pass in
straightforward float32 ``jax.numpy`` with ``precision="highest"``. No cache,
no pages, no folding: the summaries of *all* chunks and a window-level mask.
Imports nothing of the program under test.

Written from the model's published ``config.json`` (its keys are read under
their own names) and from the attention's paper (Zheng et al., "Efficient
Attention via Control Variates", ICLR 2023, arXiv:2302.04542):

- **the stack**: ``num_hidden_layers`` identical pre-norm residual blocks
  over an embedding of ``vocab_size`` bytes and specials; RMSNorm whose gain
  is ``1 + w`` (``norm_add_unit_offset``); the mixer below; a gated MLP
  ``down(silu(gate x) * up x)``; no bias; a final RMSNorm; an output head of
  its own (``tie_word_embeddings`` false) with ``num_pred_heads * vocab_size``
  columns, head ``i`` (columns ``i * vocab_size ..``) predicting the byte
  ``i + 1`` ahead.
- **the mixer** (``attention_class`` "eva"): ``num_attention_heads`` heads of
  ``hidden_size / num_attention_heads``, as many key/value heads, rotary
  positions on queries and keys (``rope_theta``). With ``c = chunk_size``,
  ``w = window_size``, ``s = hd ** -0.5`` and per layer and head two learned
  vectors ``phi``, ``mu``: the summary of chunk ``j`` is ``a_ji = softmax_i(s
  phi . k_i)`` over its ``c`` positions, ``k~_j = sum_i a_ji k_i + mu``,
  ``v~_j = sum_i a_ji v_i``; the output at ``t`` is ONE softmax over the
  positions ``i <= t`` of ``t``'s own window (score ``s q_t . k_i``, value
  ``v_i``) and over every chunk ``j`` of every earlier window (score ``s q_t
  . k~_j``, value ``v~_j``).

What the ``config.json`` does not fix is listed with what it was set from
under ``assumed`` in ``bench/configs/evabyte.json``: that the pooling logits
carry ``s``, that ``mu`` is added after the pooling, that the rotary embedding
precedes the pooling, that windows are aligned at multiples of ``w`` and do
not slide, that a query sees no summary of its own window, the two halves of
a head turned against each other, and the initialiser.

Departures, on purpose: everything is float32 (``fp32_skip_add`` and
``fp32_logits`` say where the release leaves bfloat16; here nothing is in
it); norm gains ``w`` are drawn at random around 0 (std 0.02) and ``phi``,
``mu`` away from 0, so that a fault in their paths shows.

Long sequences: the MLP runs over tiles of rows and attention over tiles of
queries (``jax.lax.map``), so that 32,768 positions fit beside the weights.
None of it changes a number.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from mxbench.reference import common
from mxbench.reference.common import (HI, cfg_key, draw, mm,  # noqa: F401
                                      round_to, seed_words)

LAYER_LEAVES = ("in_norm", "q_w", "k_w", "v_w", "o_w", "phi", "mu",
                "post_norm", "gate_w", "up_w", "down_w")
#: rows of a tile of the MLP, queries of a tile of attention
ROW_TILE, QUERY_TILE = 2048, 512


def sizes(cfg: dict):
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(
        V=int(cfg["vocab_size"]), D=D, I=int(cfg["intermediate_size"]), H=H,
        hd=D // H, L=int(cfg["num_hidden_layers"]),
        c=int(cfg["chunk_size"]), w=int(cfg["window_size"]),
        P=int(cfg["num_pred_heads"]), eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]), std=float(cfg["init_std"]))


def layer_shapes(z: dict):
    D, I, n = z["D"], z["I"], z["H"] * z["hd"]
    return {"in_norm": (D,), "q_w": (D, n), "k_w": (D, n), "v_w": (D, n),
            "o_w": (n, D), "phi": (z["H"], z["hd"]), "mu": (z["H"], z["hd"]),
            "post_norm": (D,), "gate_w": (D, I), "up_w": (D, I),
            "down_w": (I, D)}


def _seed_keys(seed):
    """(embedding, final norm, head, layers) keys of ``seed`` (a whole number
    or its ``seed_words``)."""
    lo, hi = seed_words(seed) if isinstance(seed, int) else seed
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                             hi)
    return jax.random.split(key, 4)


def init_top(cfg: dict, seed, dtype=jnp.bfloat16):
    """The leaves outside the layers: embedding, final norm's ``w``, head."""
    z = sizes(cfg)
    k_embed, k_norm, k_head, _ = _seed_keys(seed)
    return {"embed": draw(k_embed, (z["V"], z["D"]), z["std"], 0.0, dtype),
            "norm": draw(k_norm, (z["D"],), 0.02, 0.0, dtype),
            "head": draw(k_head, (z["D"], z["P"] * z["V"]), z["std"], 0.0,
                         dtype)}


def init_layer(cfg: dict, seed, i, dtype=jnp.bfloat16):
    """``{leaf: array}`` of layer ``i`` (which may be traced: one program
    makes every layer). Matrices normal(0, ``init_std``), the two that end a
    residual branch divided by ``sqrt(2 L)``; norm gains ``w`` normal(0,
    0.02); ``phi`` and ``mu`` normal(0, 1) clipped to +-1, times ``hd **
    -0.5``."""
    z = sizes(cfg)
    k = jax.random.split(_seed_keys(seed)[3], z["L"])[i]
    ks = dict(zip(LAYER_LEAVES, jax.random.split(k, len(LAYER_LEAVES))))
    out = {}
    for leaf, shape in layer_shapes(z).items():
        if leaf.endswith("_norm"):
            out[leaf] = draw(ks[leaf], shape, 0.02, 0.0, dtype)
        elif leaf in ("phi", "mu"):
            x = jnp.clip(jax.random.normal(ks[leaf], shape, jnp.float32),
                         -1.0, 1.0)
            out[leaf] = (x / math.sqrt(z["hd"])).astype(dtype)
        elif leaf in ("o_w", "down_w"):
            out[leaf] = draw(ks[leaf], shape,
                             z["std"] / math.sqrt(2 * z["L"]), 0.0, dtype)
        else:
            out[leaf] = draw(ks[leaf], shape, z["std"], 0.0, dtype)
    return out


def init_params(cfg: dict, seed, dtype=jnp.bfloat16):
    """All weights from ``seed``: the top leaves and ``"layers"``, ``{leaf:
    [one array a layer]}``. Matrices are stored ``[in, out]``. (The builder
    makes them a layer at a time: in one program the float32 draws of 3.25 G
    parameters are alive together.)"""
    L = sizes(cfg)["L"]
    layers = {leaf: [None] * L for leaf in LAYER_LEAVES}
    for i in range(L):
        for leaf, x in init_layer(cfg, seed, i, dtype).items():
            layers[leaf][i] = x
    return {**init_top(cfg, seed, dtype), "layers": layers}


# ---------------------------------------------------------------- pieces
def _rms(x, w, eps):
    """RMSNorm with the gain ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    """Rotary embedding of ``[heads, T, hd]`` at positions 0..T-1: the pair
    (x[i], x[i + hd/2]) turns by ``t * theta ** (-2 i / hd)``."""
    T, hd = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _tiled(fn, x, tile):
    """``fn`` over tiles of ``x``'s rows, where they divide."""
    T = x.shape[0]
    if T <= tile or T % tile:
        return fn(x)
    out = jax.lax.map(fn, x.reshape((T // tile, tile) + x.shape[1:]))
    return out.reshape((T,) + out.shape[2:])


def _heads(x, n, hd):
    return x.reshape(x.shape[0], n, hd).transpose(1, 0, 2)       # [n, T, hd]


def summaries(k, v, phi, mu, c: int):
    """``k``, ``v`` [H, n c, hd] -> the chunks' ``(k~, v~)`` [H, n, hd]."""
    H, T, hd = k.shape
    kc, vc = k.reshape(H, T // c, c, hd), v.reshape(H, T // c, c, hd)
    a = jax.nn.softmax(jnp.einsum("hjid,hd->hji", kc, phi, precision=HI)
                       / math.sqrt(hd), axis=-1)
    return (jnp.einsum("hji,hjid->hjd", a, kc, precision=HI) + mu[:, None],
            jnp.einsum("hji,hjid->hjd", a, vc, precision=HI))


def _eva(h, p, z, fake):
    """``h`` [T, D] -> the mixer's output [T, D]. The sequence is padded to
    whole windows (a padded position lies after every real one, and the
    last window's summaries are read by no query), and attention runs a tile
    of queries at a time, each tile inside one window: its keys are that
    window's, beside the summaries of every chunk."""
    H, hd, c, w = z["H"], z["hd"], z["c"], z["w"]
    T = h.shape[0]
    q = _rope(_heads(mm(h, p["q_w"], fake), H, hd), z["theta"])
    k = _rope(_heads(mm(h, p["k_w"], fake), H, hd), z["theta"])
    v = _heads(mm(h, p["v_w"], fake), H, hd)
    pad = ((0, 0), (0, -T % w), (0, 0))
    q, k, v = (jnp.pad(round_to(x, fake), pad) for x in (q, k, v))
    ks, vs = summaries(k, v, p["phi"].astype(jnp.float32),
                       p["mu"].astype(jnp.float32), c)
    ks, vs = round_to(ks, fake), round_to(vs, fake)
    n = ks.shape[1]
    chunk_window = (jnp.arange(n) * c) // w

    def tile(args):
        qt, t = args                                      # [H, tq, hd], [tq]
        first = t[0] // w * w                  # the tile's window starts here
        kw = jax.lax.dynamic_slice_in_dim(k, first, w, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(v, first, w, axis=1)
        own = first + jnp.arange(w)[None, :] <= t[:, None]        # [tq, w]
        earlier = chunk_window[None, :] < t[:, None] // w         # [tq, n]
        s = jnp.concatenate(
            [jnp.einsum("htd,hjd->htj", qt, ks, precision=HI),
             jnp.einsum("htd,hjd->htj", qt, kw, precision=HI)],
            axis=-1) / math.sqrt(hd)
        mask = jnp.concatenate([earlier, own], axis=-1)
        a = round_to(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1),
                     fake)
        return (jnp.einsum("htj,hjd->htd", a[..., :n], vs, precision=HI)
                + jnp.einsum("htj,hjd->htd", a[..., n:], vw, precision=HI))

    Tw = q.shape[1]
    tq = QUERY_TILE if w % QUERY_TILE == 0 else w
    o = jax.lax.map(tile, (q.reshape(H, Tw // tq, tq, hd).transpose(1, 0, 2, 3),
                           jnp.arange(Tw).reshape(Tw // tq, tq)))
    o = o.transpose(1, 0, 2, 3).reshape(H, Tw, hd)[:, :T].transpose(1, 0, 2)
    return mm(o.reshape(T, H * hd), p["o_w"], fake)


def hidden(params, ids, cfg: dict, fake=None):
    """``ids`` [T] -> the final normed hidden states [T, D] float32."""
    z = sizes(cfg)
    x = params["embed"].astype(jnp.float32)[ids]
    for i in range(z["L"]):
        p = {leaf: arrays[i] for leaf, arrays in params["layers"].items()}
        x = x + _eva(_rms(x, p["in_norm"], z["eps"]), p, z, fake)
        mlp = lambda r: mm(jax.nn.silu(mm(r, p["gate_w"], fake))   # noqa: E731
                           * mm(r, p["up_w"], fake), p["down_w"], fake)
        x = x + _tiled(mlp, _rms(x, p["post_norm"], z["eps"]), ROW_TILE)
    return _rms(x, params["norm"], z["eps"])


def logits_all(params, ids, cfg: dict, fake=None):
    """``[B, T, num_pred_heads, V]`` float32 logits of ``ids`` [B, T]: every
    prediction head."""
    z = sizes(cfg)
    out = jnp.stack([mm(hidden(params, row, cfg, fake), params["head"], fake)
                     for row in ids])
    return out.reshape(ids.shape + (z["P"], z["V"]))


def logits(params, ids, cfg: dict, fake=None):
    """``[B, T, V]``: the first prediction head, the next byte's, which is
    the one the model is served by."""
    V = sizes(cfg)["V"]
    return jnp.stack([mm(hidden(params, row, cfg, fake),
                         params["head"][:, :V], fake) for row in ids])


# ---------------------------------------------------------------- serving
def served_gaps(params, seqs, prompt_lens, cfg: dict, fake=None, pad_to=None):
    """The gaps of served tokens (``common.served_gaps``) under this
    family's ``logits``."""
    return common.served_gaps(logits, params, seqs, prompt_lens, cfg, fake,
                              pad_to)
