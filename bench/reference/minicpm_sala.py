"""Plain reference for MiniCPM-SALA: weights from a seed and the forward pass
in straightforward float32 ``jax.numpy`` with ``precision="highest"``. No
cache, no pages, no chunked scan. Imports nothing of the program under test.

Written from the model's published ``config.json`` (its keys are read under
their own names), from its family's papers and from the catalog's one-line
description ("sparse (block top-64) + lightning linear"):

- **the stack** (MiniCPM, arXiv:2404.06395, muP scalings): the embedding
  times ``scale_emb``; pre-RMSNorm residual blocks whose two branches (the
  mixer named by ``mixer_types``, then a gated MLP ``down(silu(gate x) * up
  x)``) are each scaled by ``scale_depth / sqrt(mup_denominator)``; a final
  RMSNorm; the hidden state divided by ``hidden_size / dim_model_base`` before
  an output head of its own. No bias anywhere.
- **``lightning-attn``** (Lightning Attention-2, arXiv:2401.04658):
  ``lightning_nh`` heads of ``lightning_head_dim``, keys and values for each
  (``lightning_nkv``). ``q, k`` = per-head RMSNorm of the projections
  (``qk_norm``), then rotary positions (``lightning_use_rope``; the two halves
  of a head turned against each other), ``q`` times ``lightning_scale`` = 1 /
  sqrt(d); the recurrence ``S_t = lam_h S_{t-1} + k_t^T v_t``, ``o_t = q_t
  S_t`` **as a scan over the tokens**, no normaliser; ``lam_h = exp(-slope_h
  (1 - l / (L - 1) + 1e-5))`` with ALiBi's slopes ``2 ** (-8 (h + 1) / H)``,
  ``l`` the layer's *published* index and ``L`` the published depth; then
  ``W_o (rmsnorm(o) * sigmoid(W_g x))`` (``use_output_norm`` over the joined
  heads, ``use_output_gate``).
- **``minicpm4``** (InfLLM v2 in MiniCPM4, arXiv:2506.07900):
  ``num_attention_heads`` query heads over ``num_key_value_heads`` key/value
  heads, no rotary (``attn_use_rope`` false), per-head RMSNorm of queries and
  keys. Compressed keys: the mean of ``kernel_size`` keys every
  ``kernel_stride``. A query at ``t`` scores the compressed keys that end at
  or before ``t``: ``softmax(q K_c^T / sqrt(d))`` a head, summed over the
  query heads of a key/value head; a block of ``block_size`` positions takes
  the largest score among the compressed keys that overlap it. The query reads
  the first ``init_blocks`` blocks, the blocks holding its last
  ``window_size`` positions and the best others, ``topk`` blocks in all (the
  forced ones count inside; of equal scores, which are common because
  neighbouring blocks share a compressed key, the earlier block wins); with
  at most ``dense_len`` positions of context it reads every block. Causal softmax attention over the positions of the
  blocks read; times ``sigmoid(W_g x)`` (``attn_use_output_gate``); ``W_o``.

Departures, on purpose: norm gains are drawn at random around 1 (std 0.02),
so that a fault in their path shows. What the published config does not state
(``sparse_config``, the decay rule, the pooling) is listed with its source
under ``assumed`` in ``bench/configs/minicpm-sala.json``.

Long sequences: the MLP runs over tiles of rows and attention over tiles of
queries (``jax.lax.map``), and the head only at the positions that are judged,
so that 33k positions fit beside the weights; and each kind of layer is one
jitted program that the layers of its kind share, so that a sequence length
costs two layer bodies to compile, not twelve. None of it changes a number.
"""
from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from mxbench.reference.common import (HI, draw, mm,  # noqa: F401
                                      round_to, seed_words)

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
TOP_LEAVES = ("embed", "norm", "head")
#: rows of a tile of the MLP, queries of a tile of attention
ROW_TILE, QUERY_TILE = 2080, 128
#: positions the head is taken at in one program (the most a request of the
#: long-document cell generates)
JUDGED = 512


def cfg_key(cfg: dict):
    """The configuration as a hashable static argument: its scalars, the
    list of mixers and the groups this family reads. ``dict(cfg_key(cfg))``
    gives back what ``sizes`` takes."""
    out = []
    for k, v in cfg.items():
        if isinstance(v, (int, float, str)):
            out.append((k, v))
        elif k == "mixer_types":
            out.append((k, tuple(v)))
        elif k in ("sparse_config", "deployment"):
            out.append((k, tuple(sorted(
                (a, b) for a, b in dict(v).items()
                if isinstance(b, (int, float, str))))))
    return tuple(sorted(out))


def sizes(cfg: dict):
    sp = dict(cfg["sparse_config"])
    dep = dict(cfg["deployment"])
    return dict(
        V=int(cfg["vocab_size"]), D=int(cfg["hidden_size"]),
        I=int(cfg["intermediate_size"]), H=int(cfg["num_attention_heads"]),
        G=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        LH=int(cfg["lightning_nh"]), lhd=int(cfg["lightning_head_dim"]),
        mixers=tuple(cfg["mixer_types"]), eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]), scale_emb=float(cfg["scale_emb"]),
        resid=float(cfg["scale_depth"]) / math.sqrt(
            float(cfg["mup_denominator"])),
        head_div=float(cfg["hidden_size"]) / float(cfg["dim_model_base"]),
        first_layer=int(dep["first_layer"]),
        published_layers=int(dep["published_layers"]),
        block=int(sp["block_size"]), kernel=int(sp["kernel_size"]),
        stride=int(sp["kernel_stride"]), init=int(sp["init_blocks"]),
        window=int(sp["window_size"]), topk=int(sp["topk"]),
        dense_len=int(sp["dense_len"]))


def layer_shapes(z: dict, kind: str):
    D, I = z["D"], z["I"]
    if kind == LIGHTNING:
        n, hd = z["LH"] * z["lhd"], z["lhd"]
        mixer = {"q_w": (D, n), "k_w": (D, n), "v_w": (D, n), "g_w": (D, n),
                 "o_w": (n, D), "q_norm": (hd,), "k_norm": (hd,),
                 "o_norm": (n,)}
    else:
        n, m, hd = z["H"] * z["hd"], z["G"] * z["hd"], z["hd"]
        mixer = {"q_w": (D, n), "k_w": (D, m), "v_w": (D, m), "g_w": (D, n),
                 "o_w": (n, D), "q_norm": (hd,), "k_norm": (hd,)}
    return {"in_norm": (D,), **mixer, "post_norm": (D,), "gate_w": (D, I),
            "up_w": (D, I), "down_w": (I, D)}


LAYER_LEAVES = tuple(layer_shapes(
    dict(D=1, I=1, LH=1, lhd=1, H=1, G=1, hd=1), LIGHTNING))


def _seed_keys(seed):
    """(embedding, final norm, head, layers) keys of ``seed`` (a whole number
    or its ``seed_words``)."""
    lo, hi = seed_words(seed) if isinstance(seed, int) else seed
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                             hi)
    return jax.random.split(key, 4)


def init_top(cfg: dict, seed, dtype=jnp.bfloat16):
    """The leaves outside the layers: embedding, final norm, head."""
    z = sizes(cfg)
    k_embed, k_norm, k_head, _ = _seed_keys(seed)
    return {"embed": draw(k_embed, (z["V"], z["D"]), 0.02, 0.0, dtype),
            "norm": draw(k_norm, (z["D"],), 0.02, 1.0, dtype),
            "head": draw(k_head, (z["D"], z["V"]), 0.02, 0.0, dtype)}


def init_layer(cfg: dict, seed, i, kind: str, dtype=jnp.bfloat16):
    """``{leaf: array}`` of layer ``i`` (which may be traced: one program
    makes every layer of a kind), whose mixer is ``kind``."""
    z = sizes(cfg)
    L = len(z["mixers"])
    k = jax.random.split(_seed_keys(seed)[3], L)[i]
    ks = dict(zip(LAYER_LEAVES, jax.random.split(k, len(LAYER_LEAVES))))
    resid_std = 0.02 / math.sqrt(2 * L)
    out = {}
    for leaf, shape in layer_shapes(z, kind).items():
        if leaf.endswith("_norm"):
            out[leaf] = draw(ks[leaf], shape, 0.02, 1.0, dtype)
        elif leaf in ("o_w", "down_w"):
            out[leaf] = draw(ks[leaf], shape, resid_std, 0.0, dtype)
        else:
            out[leaf] = draw(ks[leaf], shape, 0.02, 0.0, dtype)
    return out


def init_params(cfg: dict, seed, dtype=jnp.bfloat16):
    """All weights from ``seed``: the top leaves and ``"layers"``, ``{leaf:
    [one array a layer, None where the layer's kind has no such leaf]}``.
    Matrices are stored ``[in, out]``. (The builder makes them a layer at a
    time: in one program the float32 draws of 3.9 G parameters are alive
    together, 16 GB on the chip.)"""
    mixers = sizes(cfg)["mixers"]
    layers = {leaf: [None] * len(mixers) for leaf in LAYER_LEAVES}
    for i, kind in enumerate(mixers):
        for leaf, x in init_layer(cfg, seed, i, kind, dtype).items():
            layers[leaf][i] = x
    return {**init_top(cfg, seed, dtype), "layers": layers}


# ---------------------------------------------------------------- pieces
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * g.astype(jnp.float32)


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


def _slopes(heads: int, layer: int, layers: int):
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return (2.0 ** (-8.0 * h / heads)) * (1.0 - layer / (layers - 1) + 1e-5)


def _lightning(h, p, z, slopes, fake):
    """``h`` [T, D] -> the mixer's output [T, D]; ``slopes`` [heads] the
    layer's decay rates."""
    H, hd = z["LH"], z["lhd"]
    q = _rope(_rms(_heads(mm(h, p["q_w"], fake), H, hd), p["q_norm"],
                   z["eps"]), z["theta"]) / math.sqrt(hd)
    k = _rope(_rms(_heads(mm(h, p["k_w"], fake), H, hd), p["k_norm"],
                   z["eps"]), z["theta"])
    v = _heads(mm(h, p["v_w"], fake), H, hd)
    lam = jnp.exp(-slopes)[:, None, None]
    q, k, v = (round_to(x, fake).transpose(1, 0, 2) for x in (q, k, v))

    def token(S, qkv):
        qt, kt, vt = qkv                                          # [H, hd]
        S = lam * S + kt[:, :, None] * vt[:, None, :]
        return S, jnp.einsum("hd,hde->he", qt, S, precision=HI)

    _, o = jax.lax.scan(token, jnp.zeros((H, hd, hd), jnp.float32), (q, k, v))
    o = _rms(o.reshape(o.shape[0], H * hd), p["o_norm"], z["eps"])
    return mm(o * jax.nn.sigmoid(mm(h, p["g_w"], fake)), p["o_w"], fake)


def _selected(q, kc, t, z, n_blocks):
    """``q`` [H, tq, hd], ``kc`` [G, n_c, hd] compressed keys, ``t`` [tq]
    positions -> [G, tq, n_blocks] bool: the blocks each query reads."""
    H, G, hd = z["H"], z["G"], z["hd"]
    block, kernel, stride = z["block"], z["kernel"], z["stride"]
    n_c = kc.shape[1]
    qg = q.reshape(G, H // G, q.shape[1], hd)
    s = jnp.einsum("grtd,gjd->grtj", qg, kc, precision=HI) / math.sqrt(hd)
    ends = stride * jnp.arange(n_c) + kernel - 1
    whole = ends[None, :] <= t[:, None]                           # [tq, n_c]
    s = jnp.where(whole, s, -jnp.inf)
    p = jnp.where(whole.any(-1, keepdims=True), jax.nn.softmax(s, -1), 0.0)
    p = jnp.where(whole, p, 0.0).sum(axis=1)                      # [G,tq,n_c]
    # a block's score: the largest among the compressed keys that overlap it
    b = jnp.arange(n_blocks)
    lo = jnp.maximum(-(-(block * b - kernel + 1) // stride), 0)
    hi = (block * (b + 1) - 1) // stride
    width = int(np.max(np.asarray(
        (block * (np.arange(n_blocks) + 1) - 1) // stride
        - np.maximum(-(-(block * np.arange(n_blocks) - kernel + 1)
                       // stride), 0)))) + 1
    idx = lo[:, None] + jnp.arange(width)[None, :]                # [n_b, w]
    ok = (idx <= hi[:, None]) & (idx < n_c)
    score = jnp.where(ok, p[..., jnp.minimum(idx, n_c - 1)], 0.0).max(-1)
    live = b[None, :] <= (t // block)[:, None]                    # [tq, n_b]
    dense = (t + 1 <= z["dense_len"])[:, None]
    forced = (b[None, :] < z["init"]) | (
        b[None, :] >= ((t - z["window"] + 1) // block)[:, None]) | dense
    sel = jnp.where(live, jnp.where(forced, jnp.inf, score), -jnp.inf)
    # exactly ``topk`` blocks: neighbouring blocks share a compressed key, so
    # equal scores are common; of equals the earlier block is read
    k = jnp.where(dense, n_blocks, min(z["topk"], n_blocks))      # [tq, 1]
    order = jnp.argsort(-sel, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < k) & live


def _sparse(h, p, z, fake):
    """``h`` [T, D] -> (the mixer's output [T, D], (blocks selected, of them
    also selected with queries and compressed keys rounded to bfloat16,
    blocks live))."""
    H, G, hd, block = z["H"], z["G"], z["hd"], z["block"]
    T = h.shape[0]
    q = _rms(_heads(mm(h, p["q_w"], fake), H, hd), p["q_norm"], z["eps"])
    k = _rms(_heads(mm(h, p["k_w"], fake), G, hd), p["k_norm"], z["eps"])
    v = _heads(mm(h, p["v_w"], fake), G, hd)
    q, k, v = (round_to(x, fake) for x in (q, k, v))
    n_c = max((T - z["kernel"]) // z["stride"] + 1, 1)
    win = jnp.minimum(z["stride"] * jnp.arange(n_c)[:, None]
                      + jnp.arange(z["kernel"])[None, :], T - 1)
    kc = k[:, win].mean(axis=2)                                   # [G,n_c,hd]
    n_blocks = -(-T // block)
    key_block = jnp.arange(T) // block
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)

    def tile(args):
        qt, t = args                                              # [H,tq,hd]
        chosen = _selected(qt, kc, t, z, n_blocks)                # [G,tq,n_b]
        same = jnp.sum(chosen & _selected(bf(qt), bf(kc), t, z, n_blocks))
        mask = chosen[:, :, key_block] & (jnp.arange(T)[None, :]
                                          <= t[:, None])          # [G,tq,T]
        qg = qt.reshape(G, H // G, qt.shape[1], hd)
        s = jnp.einsum("grtd,gsd->grts", qg, k, precision=HI) / math.sqrt(hd)
        a = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("grts,gsd->grtd", round_to(a, fake), v, precision=HI)
        return (o.reshape(H, qt.shape[1], hd), jnp.sum(chosen), same,
                G * jnp.sum(t // block + 1))

    tq = QUERY_TILE if T > QUERY_TILE and T % QUERY_TILE == 0 else T
    qs = q.reshape(H, T // tq, tq, hd).transpose(1, 0, 2, 3)
    o, n_sel, n_same, n_live = jax.lax.map(
        tile, (qs, jnp.arange(T).reshape(T // tq, tq)))
    o = o.transpose(1, 0, 2, 3).reshape(H, T, hd).transpose(1, 0, 2)
    o = o.reshape(T, H * hd) * jax.nn.sigmoid(mm(h, p["g_w"], fake))
    return mm(o, p["o_w"], fake), jnp.stack(
        [n_sel.sum(), n_same.sum(), n_live.sum()])


@functools.partial(jax.jit, static_argnames=("key", "kind", "fake"))
def _layer(x, p, slopes, key, kind, fake):
    """One residual block of ``kind`` on ``x`` [T, D]: (new x, the sparse
    selection's three counts). A program of its own: the layers of one kind
    share it, so a sequence length compiles two layer bodies, not the
    stack's depth of them."""
    z = sizes(dict(key))
    h = _rms(x, p["in_norm"], z["eps"])
    if kind == LIGHTNING:
        y, counts = _lightning(h, p, z, slopes, fake), jnp.zeros(3, jnp.int32)
    else:
        y, counts = _sparse(h, p, z, fake)
    x = x + y * z["resid"]
    mlp = lambda r: mm(jax.nn.silu(mm(r, p["gate_w"], fake))
                       * mm(r, p["up_w"], fake), p["down_w"], fake)
    x = x + _tiled(mlp, _rms(x, p["post_norm"], z["eps"]),
                   ROW_TILE) * z["resid"]
    return x, counts.astype(jnp.int32)


def hidden(params, ids, cfg: dict, fake=None):
    """``ids`` [T] -> (final normed hidden states [T, D] float32, ready for
    the head; (blocks selected, of them also selected with queries and
    compressed keys rounded to bfloat16, blocks live) summed over the sparse
    layers)."""
    z, key = sizes(cfg), cfg_key(cfg)
    x = params["embed"].astype(jnp.float32)[ids] * z["scale_emb"]
    counts = jnp.zeros(3, jnp.int32)
    for i, kind in enumerate(z["mixers"]):
        p = {leaf: arrays[i] for leaf, arrays in params["layers"].items()
             if arrays[i] is not None}
        slopes = _slopes(z["LH"], z["first_layer"] + i, z["published_layers"])
        x, c = _layer(x, p, slopes, key, kind, fake)
        counts = counts + c
    return _rms(x, params["norm"], z["eps"]) / z["head_div"], counts


def logits(params, ids, cfg: dict, fake=None):
    """``[B, T, V]`` float32 logits of ``ids`` [B, T] (every position: for
    the tests' sizes; ``served_gaps`` takes the head only where it judges)."""
    def one(row):
        return mm(hidden(params, row, cfg, fake)[0], params["head"], fake)
    return jnp.stack([one(row) for row in ids])


@functools.partial(jax.jit, static_argnames=("fake",))
def _judge(h, h_low, head, ids, at, fake):
    """The gaps at the positions ``at`` of one sequence ``ids`` [T], from
    its hidden states ``h`` (and ``h_low``, computed in the lower precision
    ``fake``, for the control). The token judged at ``at[i]`` is ``ids[at[i]
    + 1]`` (the served one) or, with ``fake``, the one the lower precision
    puts first."""
    ref = mm(h[at], head, None)
    if fake is None:
        pick = ids[jnp.minimum(at + 1, ids.shape[0] - 1)]
    else:
        pick = jnp.argmax(mm(h_low[at], head, fake), axis=-1)
    got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - got


def _gap_rows(params, ids, at, cfg, fake):
    h, counts = hidden(params, ids, cfg, None)
    h_low = h if fake is None else hidden(params, ids, cfg, fake)[0]
    return _judge(h, h_low, params["head"], ids, at, fake), counts


def served_gaps(params, seqs, prompt_lens, cfg: dict, fake=None, pad_to=None):
    """For each sequence (prompt + served tokens) the gaps, one per served
    token, by which the served token's reference logit lies below the
    reference's best at that position (``reference/common.py`` says the same
    of any family). Each sequence is padded to the shortest of ``pad_to``,
    ``pad_to / 2`` and ``pad_to / 4`` that holds it (three programs at the
    most; causal mixers keep the padding out of what is read), and the head
    is taken at the judged positions only. The share of the
    selected blocks that stay selected when queries and compressed keys are
    rounded to bfloat16 goes to standard error as a note."""
    pad_to = pad_to or max(len(s) for s in seqs)
    ladder = sorted({pad_to // d for d in (4, 2, 1) if pad_to % d == 0})
    out, sel, same, live = [], 0, 0, 0
    for seq, n_prompt in zip(seqs, prompt_lens):
        size = next(n for n in ladder if n >= len(seq))
        ids = np.zeros(size, np.int32)
        ids[:len(seq)] = seq
        where = np.arange(n_prompt - 1, len(seq) - 1)
        # one shape for any number of served tokens up to JUDGED
        at = np.full(-(-len(where) // JUDGED) * JUDGED, where[-1], np.int32)
        at[:len(where)] = where
        g, counts = _gap_rows(params, jnp.asarray(ids), jnp.asarray(at), cfg,
                              fake)
        out.append([float(x) for x in np.asarray(g)[:len(where)]])
        sel, same, live = (a + int(b) for a, b in
                           zip((sel, same, live), counts))
    if sel:
        print("compared notes: " + str({
            "_selection": {"blocks_selected": sel, "blocks_live": live,
                           "agree_with_bf16_share": same / sel}}),
              file=sys.stderr, flush=True)
    return out
