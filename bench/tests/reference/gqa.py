"""Plain reference for a grouped-query decoder: weights from a seed and the
forward pass in straightforward float32 ``jax.numpy``. Imports nothing of the
program under test and takes nothing the program made.

Written from the published description of the family (Touvron et al. 2023,
"LLaMA: Open and Efficient Foundation Language Models"; Ainslie et al. 2023,
"GQA"; Su et al. 2021, "RoFormer"; Shazeer 2020, "GLU Variants Improve
Transformer") under the key names of its released ``config.json`` files:
token embeddings and no position table; pre-RMSNorm blocks; causal attention
whose ``num_attention_heads`` query heads share ``num_key_value_heads`` key
and value heads in groups; rotary position embedding of queries and keys,
the two halves of a head rotated against each other (the layout of the
released checkpoints); a gated MLP, ``down(silu(gate x) * up x)``; a final
RMSNorm and an output head of its own. No bias anywhere.

Departure, on purpose: the norms' gains are drawn at random around 1 (std
0.02), so that a fault in their path shows.

A serving reference only: the fixture under ``bench/tests/`` that uses it has
no training cell. Layers are stacked on a leading axis and scanned.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from mxbench.reference import common
from mxbench.reference.common import (HI, cfg_key, draw, mm,  # noqa: F401
                                      round_to, seed_words)

LAYER_LEAVES = ("in_norm", "q_w", "k_w", "v_w", "o_w", "post_norm",
                "gate_w", "up_w", "down_w")
TOP_LEAVES = ("embed", "norm", "head")


def sizes(cfg: dict):
    """(V, D, L, H, G, hd, I, eps, theta) from the published keys."""
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return (int(cfg["vocab_size"]), D, int(cfg["num_hidden_layers"]), H,
            int(cfg["num_key_value_heads"]), D // H,
            int(cfg["intermediate_size"]), float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]))


def init_params(cfg: dict, seed, dtype=jnp.bfloat16):
    """All weights from ``seed`` (a whole number or its ``seed_words``):
    the top leaves and ``"layers"``, leaves stacked ``[L, ...]``. Matrices
    are stored ``[in, out]``; norm gains are float32."""
    V, D, L, H, G, hd, I, _, _ = sizes(cfg)
    lo, hi = seed_words(seed) if isinstance(seed, int) else seed
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                             hi)
    k_embed, k_norm, k_head, k_layers = jax.random.split(key, 4)
    shapes = {"in_norm": (D,), "q_w": (D, H * hd), "k_w": (D, G * hd),
              "v_w": (D, G * hd), "o_w": (H * hd, D), "post_norm": (D,),
              "gate_w": (D, I), "up_w": (D, I), "down_w": (I, D)}
    resid_std = 0.02 / math.sqrt(2 * L)

    def layer(k):
        ks = dict(zip(LAYER_LEAVES, jax.random.split(k, len(LAYER_LEAVES))))
        out = {}
        for name, shape in shapes.items():
            if name.endswith("_norm"):
                out[name] = draw(ks[name], shape, 0.02, 1.0, jnp.float32)
            elif name in ("o_w", "down_w"):
                out[name] = draw(ks[name], shape, resid_std, 0.0, dtype)
            else:
                out[name] = draw(ks[name], shape, 0.02, 0.0, dtype)
        return out

    return {"embed": draw(k_embed, (V, D), 0.02, 0.0, dtype),
            "norm": draw(k_norm, (D,), 0.02, 1.0, jnp.float32),
            "head": draw(k_head, (D, V), 0.02, 0.0, dtype),
            "layers": jax.vmap(layer)(jax.random.split(k_layers, L))}


# ---------------------------------------------------------------- forward
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """Rotary embedding of ``[B, heads, T, hd]`` at positions 0..T-1: the
    pair (x[i], x[i + hd/2]) turns by ``t * theta ** (-2 i / hd)``."""
    T, hd = x.shape[2], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _block(x, p, H, G, eps, theta, fake):
    B, T, D = x.shape
    hd = D // H
    h = _rms(x, p["in_norm"], eps)
    heads = lambda y, n: y.reshape(B, T, n, hd).transpose(0, 2, 1, 3)
    q = _rope(heads(mm(h, p["q_w"], fake), H), theta)
    k = _rope(heads(mm(h, p["k_w"], fake), G), theta)
    v = heads(mm(h, p["v_w"], fake), G)
    # query head i reads key/value head i // (H / G)
    q = q.reshape(B, G, H // G, T, hd)
    s = jnp.einsum("bgrtd,bgsd->bgrts", round_to(q, fake), round_to(k, fake),
                   precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bgrts,bgsd->bgrtd", round_to(a, fake), round_to(v, fake),
                   precision=HI)
    o = o.reshape(B, H, T, hd).transpose(0, 2, 1, 3).reshape(B, T, H * hd)
    x = x + mm(o, p["o_w"], fake)
    h = _rms(x, p["post_norm"], eps)
    h = jax.nn.silu(mm(h, p["gate_w"], fake)) * mm(h, p["up_w"], fake)
    return x + mm(h, p["down_w"], fake)


def logits(params, ids, cfg: dict, fake=None):
    """``[B, T, V]`` float32 logits."""
    _, _, _, H, G, _, _, eps, theta = sizes(cfg)
    x = params["embed"].astype(jnp.float32)[ids]
    x, _ = jax.lax.scan(
        lambda x, p: (_block(x, p, H, G, eps, theta, fake), None), x,
        params["layers"])
    return mm(_rms(x, params["norm"], eps), params["head"], fake)


def served_gaps(params, seqs, prompt_lens, cfg: dict, fake=None, pad_to=None):
    """The gaps of served tokens (``common.served_gaps``) under this
    family's ``logits``."""
    return common.served_gaps(logits, params, seqs, prompt_lens, cfg, fake,
                              pad_to)
