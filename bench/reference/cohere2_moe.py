"""Plain reference for Cohere2-MoE (Command A+'s language model): weights from
a seed and the forward pass in straightforward float32 ``jax.numpy`` with
``precision="highest"``. No cache, no pages, no grouping of tokens by expert:
every held expert is applied to every token and weighted by the router's
(mostly zero) weight. Imports nothing of the program under test.

Written from the model's published ``config.json`` (its keys are read under
their own names) and from Cohere2's published modelling code, which this
family's attention is. ``x`` is ``[T, hidden_size]``:

    h   = LayerNorm(x) * g              # mean and variance, eps layer_norm_eps,
                                        # a gain, no offset (rms_norm_eps null)
    q, k, v = h Wq, h Wk, h Wv          # num_attention_heads over
                                        # num_key_value_heads of head_dim;
                                        # no bias, no q/k norm (use_qk_norm false)
    sliding layer: q, k rotated by rope_theta, rotary_pct 1, interleaved pairs
                   (position_embedding_type rope_gptj: lanes 2i and 2i + 1);
                   key j visible to query i iff  i - sliding_window < j <= i
    full layer:    no positional embedding; key j visible iff j <= i
    a   = softmax(q k^T / sqrt(head_dim)) v Wo      # query head n reads KV
                                                    # head n // (heads / kv heads)
    s   = sigmoid(h Wr)                 # expert_selection_fn sigmoid, [T, E]
    top = the num_experts_per_tok largest of s
    w_e = s_e / sum_{e' in top} s_e'    # norm_topk_prob, over all chosen
    F_e(h) = (silu(h Wg_e) * (h Wu_e)) Wd_e         # use_gated_activation
    m   = sum_{e in top, e held} w_e F_e(h) + (1 / S) sum_j F_shared_j(h)
    y   = x + a + m                     # use_parallel_block
    logits = (LayerNorm(y_last) * g) E^T * logit_scale      # tied embedding

``layer_types`` gives each layer's kind. **The share.** ``E`` is the published
number of routed experts (``published.num_experts``, the router's outputs);
``num_experts`` in a cut configuration is how many of them are *held*, the
range ``experts_held = [first, count]``. The sum over routed experts runs over
the held ones only: what the others would add is left out, here as in the
program. ``expert_branch`` takes the range and the held experts' matrices, so
the tests can add the shares up against the uncut layer.

What ``config.json`` does not fix is listed under ``assumed`` in
``bench/configs/command-a-plus.json``: what the shared experts' "average"
averages, the width of an expert, the window's edge, LayerNorm rather than
RMSNorm, the initialiser, random gains.

Long sequences: attention runs over tiles of queries and, inside a tile,
over one KV head's group at a time; the expert branch over tiles of rows,
one held expert at a time (``jax.lax.scan`` over their stacked matrices);
``served_gaps`` runs a program a layer and takes the head only where a token
is judged. None of it changes a number.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from mxbench.reference.common import (HI, draw, mm, round_to,  # noqa: F401
                                      seed_words)

SLIDING, FULL = "sliding_attention", "full_attention"
LAYER_LEAVES = ("norm", "q_w", "k_w", "v_w", "o_w", "router_w", "gate_w",
                "up_w", "down_w", "sgate_w", "sup_w", "sdown_w")
#: queries of a tile of attention, rows of a tile of the expert branch (at
#: the most: the largest divisor of the length under it), tokens judged by
#: one program
QUERY_TILE, ROW_TILE, JUDGED = 128, 2048, 512


def cfg_key(cfg: dict):
    """The configuration as a hashable static argument: its scalars, the
    layers' kinds, the held range and the published counts.
    ``dict(cfg_key(cfg))`` gives back what ``sizes`` takes."""
    out = []
    for k, v in cfg.items():
        if isinstance(v, (int, float, str)):
            out.append((k, v))
        elif k in ("layer_types", "experts_held"):
            out.append((k, tuple(v)))
        elif k == "published":
            out.append((k, tuple(sorted(
                (a, b) for a, b in dict(v).items()
                if isinstance(b, (int, float, str))))))
    return tuple(sorted(out))


def sizes(cfg: dict):
    held = int(cfg["num_experts"])
    E = int(dict(cfg.get("published", ())).get("num_experts", held))
    first, count = cfg.get("experts_held", (0, held))
    return dict(
        V=int(cfg["vocab_size"]), D=int(cfg["hidden_size"]),
        F=int(cfg["intermediate_size"]), H=int(cfg["num_attention_heads"]),
        G=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        L=int(cfg["num_hidden_layers"]), kinds=tuple(cfg["layer_types"]),
        E=E, held=(int(first), int(count)),
        k=int(cfg["num_experts_per_tok"]), S=int(cfg["num_shared_experts"]),
        window=int(cfg["sliding_window"]), theta=float(cfg["rope_theta"]),
        eps=float(cfg["layer_norm_eps"]), scale=float(cfg["logit_scale"]),
        std=float(cfg.get("initializer_range", 0.02)))


def layer_shapes(z: dict):
    D, F, n, m = z["D"], z["F"], z["H"] * z["hd"], z["G"] * z["hd"]
    c, S = z["held"][1], z["S"]
    return {"norm": (D,), "q_w": (D, n), "k_w": (D, m), "v_w": (D, m),
            "o_w": (n, D), "router_w": (D, z["E"]), "gate_w": (c, D, F),
            "up_w": (c, D, F), "down_w": (c, F, D), "sgate_w": (S, D, F),
            "sup_w": (S, D, F), "sdown_w": (S, F, D)}


def _seed_keys(seed):
    """(embedding, final norm, layers) keys of ``seed`` (a whole number or
    its ``seed_words``)."""
    lo, hi = seed_words(seed) if isinstance(seed, int) else seed
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                             hi)
    return jax.random.split(key, 3)


def init_top(cfg: dict, seed, dtype=jnp.bfloat16):
    """The leaves outside the layers: the tied embedding, the final gain."""
    z = sizes(cfg)
    k_embed, k_norm, _ = _seed_keys(seed)
    return {"embed": draw(k_embed, (z["V"], z["D"]), z["std"], 0.0, dtype),
            "final_norm": draw(k_norm, (z["D"],), 0.1, 1.0, dtype)}


def init_layer(cfg: dict, seed, i, dtype=jnp.bfloat16):
    """``{leaf: array}`` of layer ``i`` (which may be traced: one program
    makes every layer). Matrices normal(0, std), those that end a residual
    branch (o, down, shared down) divided by ``sqrt(2 L)``; gains normal(1,
    0.1). Matrices are stored ``[in, out]``, an expert's stacked in front."""
    z = sizes(cfg)
    k = jax.random.split(_seed_keys(seed)[2], z["L"])[i]
    ks = dict(zip(LAYER_LEAVES, jax.random.split(k, len(LAYER_LEAVES))))
    out = {}
    for leaf, shape in layer_shapes(z).items():
        if leaf == "norm":
            out[leaf] = draw(ks[leaf], shape, 0.1, 1.0, dtype)
        elif leaf in ("o_w", "down_w", "sdown_w"):
            out[leaf] = draw(ks[leaf], shape,
                             z["std"] / math.sqrt(2 * z["L"]), 0.0, dtype)
        else:
            out[leaf] = draw(ks[leaf], shape, z["std"], 0.0, dtype)
    return out


def init_params(cfg: dict, seed, dtype=jnp.bfloat16):
    """All weights from ``seed``: the top leaves and ``"layers"``, ``{leaf:
    [one array a layer]}``."""
    L = sizes(cfg)["L"]
    layers = {leaf: [None] * L for leaf in LAYER_LEAVES}
    for i in range(L):
        for leaf, x in init_layer(cfg, seed, i, dtype).items():
            layers[leaf][i] = x
    return {**init_top(cfg, seed, dtype), "layers": layers}


# ---------------------------------------------------------------- pieces
def _ln(x, g, eps):
    """LayerNorm with a gain and no offset."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope_pairs(x, t, theta):
    """Rotary embedding of ``[..., T, hd]`` at positions ``t`` [T]: the pair
    (x[2i], x[2i + 1]) turns by ``t * theta ** (-2 i / hd)``."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = t.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(x.shape[:-1] + (hd // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _divisor(T: int, most: int) -> int:
    """The largest divisor of ``T`` that is at most ``most``."""
    return next(d for d in range(min(T, most), 0, -1) if T % d == 0)


def _tiled(fn, x, tile):
    """``fn`` over tiles of ``x``'s rows."""
    T = x.shape[0]
    if T <= tile:
        return fn(x)
    out = jax.lax.map(fn, x.reshape((T // tile, tile) + x.shape[1:]))
    return out.reshape((T,) + out.shape[2:])


def attention(h, p, z, kind, fake=None):
    """``h`` [T, D] (normed) -> the attention branch's output [T, D]."""
    H, G, hd, W = z["H"], z["G"], z["hd"], z["window"]
    T, rep = h.shape[0], z["H"] // z["G"]
    sliding = kind == SLIDING
    pos = jnp.arange(T)
    k = mm(h, p["k_w"], fake).reshape(T, G, hd).transpose(1, 0, 2)  # [G,T,hd]
    v = mm(h, p["v_w"], fake).reshape(T, G, hd).transpose(1, 0, 2)
    if sliding:
        k = _rope_pairs(k, pos, z["theta"])
    k, v = round_to(k, fake), round_to(v, fake)
    tq = _divisor(T, QUERY_TILE)

    def tile(args):
        ht, t = args                                      # [tq, D], [tq]
        q = mm(ht, p["q_w"], fake).reshape(tq, G, rep, hd) \
            .transpose(1, 2, 0, 3)                        # [G, rep, tq, hd]
        if sliding:
            q = _rope_pairs(q, t, z["theta"])
        q = round_to(q, fake)
        mask = pos[None, :] <= t[:, None]                 # [tq, T]
        if sliding:
            mask = mask & (pos[None, :] > t[:, None] - W)

        def group(qkv):
            qg, kg, vg = qkv                  # [rep, tq, hd], [T, hd], [T, hd]
            s = jnp.einsum("rtd,jd->rtj", qg, kg, precision=HI) \
                / math.sqrt(hd)
            a = round_to(jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf),
                                        axis=-1), fake)
            return jnp.einsum("rtj,jd->rtd", a, vg, precision=HI)

        o = jax.lax.map(group, (q, k, v))                 # [G, rep, tq, hd]
        return mm(o.transpose(2, 0, 1, 3).reshape(tq, H * hd), p["o_w"],
                  fake)

    out = jax.lax.map(tile, (h.reshape(T // tq, tq, -1),
                             pos.reshape(T // tq, tq)))
    return out.reshape(T, -1)


def _expert(r, wg, wu, wd, fake):
    """``F(r) = (silu(r Wg) * (r Wu)) Wd``."""
    return mm(jax.nn.silu(mm(r, wg, fake)) * mm(r, wu, fake), wd, fake)


def route(h, router_w, z, fake=None):
    """``h`` [T, D] -> the router's weights over all ``E`` experts, [T, E]:
    ``w_e`` for the ``k`` chosen, 0 for the rest."""
    s = jax.nn.sigmoid(mm(h, router_w, fake))
    top, idx = jax.lax.top_k(s, z["k"])
    w = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(w)


def routed_part(h, p, z, held, fake=None):
    """The part of the routed sum that the experts ``held = (first, count)``
    give, [T, D]: ``p["gate_w"]``, ``up_w``, ``down_w`` are *their* matrices,
    stacked. Every held expert is applied to every row."""
    first, count = held

    def rows(r):
        w = route(r, p["router_w"], z, fake)[:, first:first + count]

        def one(acc, ew):
            we, wg, wu, wd = ew
            return acc + we[:, None] * _expert(r, wg, wu, wd, fake), None

        acc, _ = jax.lax.scan(one, jnp.zeros_like(r),
                              (w.T, p["gate_w"], p["up_w"], p["down_w"]))
        return acc

    return _tiled(rows, h, _divisor(h.shape[0], ROW_TILE))


def shared_part(h, p, z, fake=None):
    """The shared experts' outputs, averaged: ``(1 / S) sum_j F_j(h)``."""
    def rows(r):
        def one(acc, ws):
            return acc + _expert(r, *ws, fake), None

        acc, _ = jax.lax.scan(one, jnp.zeros_like(r),
                              (p["sgate_w"], p["sup_w"], p["sdown_w"]))
        return acc / z["S"]

    return _tiled(rows, h, _divisor(h.shape[0], ROW_TILE))


def expert_branch(h, p, z, held, fake=None):
    """``m``: the held experts' part of the routed sum, and the shared
    experts' average added to it."""
    return routed_part(h, p, z, held, fake) + shared_part(h, p, z, fake)


def layer(x, p, z, kind, fake=None):
    """One parallel block: one norm feeds both branches."""
    h = _ln(x, p["norm"], z["eps"])
    return (x + attention(h, p, z, kind, fake)
            + expert_branch(h, p, z, z["held"], fake))


def hidden(params, ids, cfg: dict, fake=None):
    """``ids`` [T] -> the final normed hidden states [T, D] float32."""
    z = sizes(cfg)
    x = params["embed"].astype(jnp.float32)[ids]
    for i, kind in enumerate(z["kinds"]):
        p = {leaf: arrays[i] for leaf, arrays in params["layers"].items()}
        x = layer(x, p, z, kind, fake)
    return _ln(x, params["final_norm"], z["eps"])


def logits(params, ids, cfg: dict, fake=None):
    """``[B, T, V]`` float32 logits of ``ids`` [B, T] (every position: for
    the tests' sizes; ``served_gaps`` takes the head only where it judges)."""
    scale = sizes(cfg)["scale"]
    return jnp.stack([mm(hidden(params, row, cfg, fake), params["embed"].T,
                         fake) * scale for row in ids])


# ---------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("key", "kind", "fake"))
def _layer(x, p, key, kind, fake):
    return layer(x, p, sizes(dict(key)), kind, fake)


def _hidden(params, ids, key, fake):
    """``hidden``, a program a layer: beside 9.5 GB of weights the chip
    holds one layer's temporaries at a time, at 33,280 positions too."""
    z = sizes(dict(key))
    x = params["embed"].astype(jnp.float32)[ids]
    for i, kind in enumerate(z["kinds"]):
        p = {leaf: arrays[i] for leaf, arrays in params["layers"].items()}
        x = _layer(x, p, key, kind, fake)
    return _ln(x, params["final_norm"], z["eps"])


@functools.partial(jax.jit, static_argnames=("scale", "fake"))
def _judge(h, h_low, embed, ids, at, scale, fake):
    """The gaps at the positions ``at`` of one sequence ``ids`` [T], from
    its hidden states ``h`` (and ``h_low``, computed in the lower precision
    ``fake``, for the control): the token judged at ``at[i]`` is ``ids[at[i]
    + 1]`` (the served one) or, with ``fake``, the one the lower precision
    puts first."""
    ref = mm(h[at], embed.T, None) * scale
    if fake is None:
        pick = ids[jnp.minimum(at + 1, ids.shape[0] - 1)]
    else:
        pick = jnp.argmax(mm(h_low[at], embed.T, fake), axis=-1)
    got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - got


def _gap_rows(params, ids, at, key, fake):
    """One program a pass (the control's two passes in one program would
    hold both passes' temporaries beside 9.5 GB of weights)."""
    h = _hidden(params, ids, key, None)
    h_low = h if fake is None else _hidden(params, ids, key, fake)
    return _judge(h, h_low, params["embed"], ids, at,
                  sizes(dict(key))["scale"], fake)


def served_gaps(params, seqs, prompt_lens, cfg: dict, fake=None, pad_to=None):
    """For each sequence (prompt + served tokens) the gaps, one per served
    token, by which the served token's reference logit lies below the
    reference's best at that position (``reference/common.py`` says the same
    of any family). Each sequence is padded to the shortest of ``pad_to``,
    ``pad_to / 2`` and ``pad_to / 4`` that holds it (three programs at the
    most; causal attention keeps the padding out of what is read), and the
    head is taken at the judged positions only."""
    key = cfg_key(cfg)
    pad_to = pad_to or max(len(s) for s in seqs)
    ladder = sorted({pad_to // d for d in (4, 2, 1) if pad_to % d == 0})
    out = []
    for seq, n_prompt in zip(seqs, prompt_lens):
        size = next(n for n in ladder if n >= len(seq))
        ids = np.zeros(size, np.int32)
        ids[:len(seq)] = seq
        where = np.arange(n_prompt - 1, len(seq) - 1)
        # one shape for any number of served tokens up to JUDGED
        at = np.full(-(-len(where) // JUDGED) * JUDGED, where[-1], np.int32)
        at[:len(where)] = where
        g = _gap_rows(params, jnp.asarray(ids), jnp.asarray(at), key, fake)
        out.append([float(x) for x in np.asarray(g)[:len(where)]])
    return out
