"""Plain reference for the GPT-2 family: weights from a seed, the forward
pass, the loss, its gradients and Adam, in straightforward ``jax.numpy``.

Written from the published description (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners", and the released ``config.json``
files): learned token and position embeddings, pre-LayerNorm blocks of causal
multi-head attention and a 4x GELU (tanh form) MLP, a final LayerNorm and an
output head tied to the token embedding. It imports nothing of the program
under test and takes nothing the program made.

Arithmetic is float32 with ``precision="highest"`` on every contraction
(a TPU otherwise multiplies float32 in one bfloat16 pass). ``fake`` names a
lower precision for the *control*: every matmul operand is rounded to that
type first, which is what serving or training "in fp8" would do.

Departures from the published description, each on purpose:

* biases and LayerNorm parameters are drawn at random (std 0.02 around 0
  and 1) and not set to 0 and 1, so that a fault in any parameter's path
  shows in the outputs; trained checkpoints have them non-zero too;
* weights are drawn in the type the configuration stores them in
  (bfloat16): the reference upcasts exactly those values.

Layers are stacked on a leading axis and scanned, so one layer's program
compiles once whatever the depth.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from mxbench.reference import common
from mxbench.reference.common import (HI, cfg_key, draw as _draw,  # noqa: F401
                                      mm as _mm, round_to as _round,
                                      seed_words)

#: leaf name -> (shape as a function of sizes, init kind); per layer
LAYER_LEAVES = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
                "ln2_g", "ln2_b", "fc_w", "fc_b", "proj_w", "proj_b")
TOP_LEAVES = ("wte", "wpe", "lnf_g", "lnf_b")


def sizes(cfg: dict):
    """(V, D, L, H, P, eps) from a published GPT-2 ``config.json``."""
    return (int(cfg["vocab_size"]), int(cfg["n_embd"]), int(cfg["n_layer"]),
            int(cfg["n_head"]), int(cfg["n_positions"]),
            float(cfg["layer_norm_epsilon"]))


def _layer_shapes(D):
    return {"ln1_g": (D,), "ln1_b": (D,), "qkv_w": (D, 3 * D),
            "qkv_b": (3 * D,), "out_w": (D, D), "out_b": (D,),
            "ln2_g": (D,), "ln2_b": (D,), "fc_w": (D, 4 * D),
            "fc_b": (4 * D,), "proj_w": (4 * D, D), "proj_b": (D,)}


def init_params(cfg: dict, seed, dtype=jnp.bfloat16):
    """All weights from ``seed`` on the default device: a dict of the top
    leaves and ``"layers"``, a dict of leaves stacked ``[L, ...]``. Matrices
    are stored ``[in, out]`` as the published Conv1D layers are. LayerNorm
    parameters are float32 whatever ``dtype`` is. ``seed`` is a whole number
    or its :func:`seed_words`; call it under ``jax.jit`` with the words
    traced, and one program serves every seed."""
    V, D, L, H, P, _ = sizes(cfg)
    lo, hi = seed_words(seed) if isinstance(seed, int) else seed
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                             hi)
    k_wte, k_wpe, k_lnf, k_layers = jax.random.split(key, 4)
    resid_std = 0.02 / math.sqrt(2 * L)

    def layer(k):
        ks = dict(zip(LAYER_LEAVES, jax.random.split(k, len(LAYER_LEAVES))))
        out = {}
        for name, shape in _layer_shapes(D).items():
            if name.endswith("_g"):
                out[name] = _draw(ks[name], shape, 0.02, 1.0, jnp.float32)
            elif name.startswith("ln"):
                out[name] = _draw(ks[name], shape, 0.02, 0.0, jnp.float32)
            elif name in ("out_w", "proj_w"):
                out[name] = _draw(ks[name], shape, resid_std, 0.0, dtype)
            else:
                out[name] = _draw(ks[name], shape, 0.02, 0.0, dtype)
        return out

    kg, kb = jax.random.split(k_lnf)
    return {
        "wte": _draw(k_wte, (V, D), 0.02, 0.0, dtype),
        "wpe": _draw(k_wpe, (P, D), 0.02, 0.0, dtype),
        "lnf_g": _draw(kg, (D,), 0.02, 1.0, jnp.float32),
        "lnf_b": _draw(kb, (D,), 0.02, 0.0, jnp.float32),
        "layers": jax.vmap(layer)(jax.random.split(k_layers, L)),
    }


# ---------------------------------------------------------------- forward
def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, H, eps, fake):
    B, T, D = x.shape
    hd = D // H
    h = _ln(x, p["ln1_g"], p["ln1_b"], eps)
    qkv = _mm(h, p["qkv_w"], fake) + p["qkv_b"].astype(jnp.float32)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhtd,bhsd->bhts", _round(q, fake), _round(k, fake),
                   precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhts,bhsd->bhtd", _round(a, fake), _round(v, fake),
                   precision=HI)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, D)
    x = x + _mm(o, p["out_w"], fake) + p["out_b"].astype(jnp.float32)
    h = _ln(x, p["ln2_g"], p["ln2_b"], eps)
    h = _gelu(_mm(h, p["fc_w"], fake) + p["fc_b"].astype(jnp.float32))
    return x + _mm(h, p["proj_w"], fake) + p["proj_b"].astype(jnp.float32)


def hidden(params, ids, cfg: dict, fake=None, remat=False):
    """Final hidden states ``[B, T, D]`` (after the last LayerNorm)."""
    _, _, _, H, _, eps = sizes(cfg)
    T = ids.shape[1]
    x = params["wte"].astype(jnp.float32)[ids] \
        + params["wpe"].astype(jnp.float32)[:T][None]

    def body(x, p):
        return _block(x, p, H, eps, fake), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return _ln(x, params["lnf_g"], params["lnf_b"], eps)


def logits(params, ids, cfg: dict, fake=None, remat=False):
    """``[B, T, V]`` float32 logits; the head is the token embedding."""
    x = hidden(params, ids, cfg, fake, remat)
    return _mm(x, params["wte"].astype(jnp.float32).T, fake)


def loss(params, ids, labels, cfg: dict, fake=None):
    """Mean over every token of the cross-entropy against ``labels``."""
    lg = logits(params, ids, cfg, fake, remat=True)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


# ------------------------------------------------------------ train steps
@functools.partial(jax.jit, static_argnames=("cfg_key", "fake"))
def _block_grad(params, ids, labels, cfg_key, fake):
    cfg = dict(cfg_key)
    return jax.value_and_grad(
        lambda p: loss(p, ids, labels, cfg, fake))(params)


def loss_and_grads(params, ids, labels, cfg: dict, fake=None, rows=2):
    """Loss and gradients of the whole batch, taken ``rows`` rows at a time
    and averaged, so that float32 activations fit beside the state."""
    B = ids.shape[0]
    if B % rows:
        raise ValueError(f"batch {B} is not a multiple of {rows} rows")
    key = cfg_key(cfg)
    total, acc = None, None
    n = B // rows
    for i in range(n):
        sl = slice(i * rows, (i + 1) * rows)
        l, g = _block_grad(params, ids[sl], labels[sl], key, fake)
        total = l if total is None else total + l
        acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
    scale = jnp.float32(1.0 / n)
    return total * scale, jax.tree.map(lambda x: x * scale, acc)


@jax.jit
def _adam(params, grads, m, v, t, lr, b1, b2, eps):
    t = t.astype(jnp.float32)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g), v,
                     grads)
    c1, c2 = 1 - jnp.power(b1, t), 1 - jnp.power(b2, t)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, m, v)
    return params, m, v


def leaf_norms(tree):
    """{leaf name: float32 norms} — one number for a top leaf, ``[L]`` for a
    stacked one (a leaf of the model is one layer's array). The fused
    query/key/value projection counts as three leaves: ``q_w, k_w, v_w`` and
    ``q_b, k_b, v_b``."""
    def norm(x, axes):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                axis=axes))

    out = {k: norm(tree[k], None) for k in TOP_LEAVES}
    for k in LAYER_LEAVES:
        x = tree["layers"][k]
        axes = tuple(range(1, x.ndim))
        if k in ("qkv_w", "qkv_b"):
            for p, piece in zip("qkv", jnp.split(x, 3, axis=-1)):
                out[f"{p}{k[3:]}"] = norm(piece, axes)
        else:
            out[k] = norm(x, axes)
    return out


def train_steps(params, batches, cfg: dict, opt: dict, fake=None, rows=2,
                store_dtype=None):
    """Follow ``len(batches)`` Adam steps from ``params``. Returns the
    losses, the per-leaf norms of the first gradient, and the per-leaf norms
    of the parameters' change over all the steps.

    The state is float32. ``store_dtype`` rounds the parameters to the type
    the configuration stores them in after every update (what a system
    without float32 master weights does); ``None`` keeps them float32."""
    p0 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    p = p0
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, g1 = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        l, g = loss_and_grads(p, ids, labels, cfg, fake, rows)
        losses.append(float(l))
        if g1 is None:
            g1 = leaf_norms(g)
        p, m, v = _adam(p, g, m, v, jnp.int32(t), jnp.float32(opt["lr"]),
                        jnp.float32(opt["beta1"]), jnp.float32(opt["beta2"]),
                        jnp.float32(opt["epsilon"]))
        if store_dtype is not None:
            p = jax.tree.map(
                lambda new, old: new.astype(old.dtype).astype(jnp.float32),
                p, params)
    delta = leaf_norms(jax.tree.map(jnp.subtract, p, p0))
    return losses, g1, delta


# ---------------------------------------------------------------- serving
def served_gaps(params, seqs, prompt_lens, cfg: dict, fake=None, pad_to=None):
    """The gaps of served tokens (``common.served_gaps``) under this
    family's ``logits``."""
    return common.served_gaps(logits, params, seqs, prompt_lens, cfg, fake,
                              pad_to)
