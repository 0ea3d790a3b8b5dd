"""Builds the program's GPT-2 (``mxnet_tpu.models.gpt.GPTModel``) for a
configuration file and fills it with the reference's weights for a seed.

This is the only file that knows both sides: the program's parameter names
and layouts, and the reference's (``bench/reference/gpt2.py``). A new model
family adds a module like this one, named by its configuration file's
``builder``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mxbench.reference import gpt2 as ref

#: program parameter suffix -> (reference leaf, transposed?). The program's
#: Dense stores [out, in]; the reference stores [in, out] as published.
_LAYER_MAP = {
    "ln_1.gamma": ("ln1_g", False), "ln_1.beta": ("ln1_b", False),
    "attn_qkv.weight": ("qkv_w", True), "attn_qkv.bias": ("qkv_b", False),
    "attn_out.weight": ("out_w", True), "attn_out.bias": ("out_b", False),
    "ln_2.gamma": ("ln2_g", False), "ln_2.beta": ("ln2_b", False),
    "mlp_fc.weight": ("fc_w", True), "mlp_fc.bias": ("fc_b", False),
    "mlp_proj.weight": ("proj_w", True), "mlp_proj.bias": ("proj_b", False),
}
_TOP_MAP = {"wte.weight": "wte", "wpe.weight": "wpe",
            "ln_f.gamma": "lnf_g", "ln_f.beta": "lnf_b"}


def leaf_of(name: str):
    """Program parameter name -> (reference leaf, layer index or None,
    transposed?)."""
    if name in _TOP_MAP:
        return _TOP_MAP[name], None, False
    _, layer, suffix = name.split(".", 2)
    leaf, transposed = _LAYER_MAP[suffix]
    return leaf, int(layer), transposed


def parts(name: str, array):
    """A program parameter as the pieces `correct` compares one by one:
    ``[(reference leaf, layer or None, piece)]``. The fused query/key/value
    projection is three leaves of the model (a key's bias has no gradient
    under softmax; fused, it would hide in its neighbours' norm)."""
    leaf, layer, _ = leaf_of(name)
    if leaf in ("qkv_w", "qkv_b"):
        third = array.shape[0] // 3
        return [(f"{p}{leaf[3:]}", layer, array[i * third:(i + 1) * third])
                for i, p in enumerate("qkv")]
    return [(leaf, layer, array)]


def program_names(cfg: dict):
    names = list(_TOP_MAP)[:2]
    for i in range(int(cfg["n_layer"])):
        names += [f"blocks.{i}.{s}" for s in _LAYER_MAP]
    return names + list(_TOP_MAP)[2:]


def dtype_of(cfg: dict):
    return jnp.dtype(cfg.get("torch_dtype_served", "bfloat16"))


@functools.lru_cache(maxsize=None)
def _weights_fn(cfg_key):
    cfg = dict(cfg_key)

    def make(words):
        tree = ref.init_params(cfg, (words[0], words[1]), dtype_of(cfg))
        out = {}
        for name in program_names(cfg):
            leaf, layer, transposed = leaf_of(name)
            x = tree[leaf] if layer is None else tree["layers"][leaf][layer]
            out[name] = x.T if transposed else x
        return out

    return jax.jit(make)


cfg_key = ref.cfg_key


def program_weights(cfg: dict, seed: int):
    """{program parameter name: array} on the default device, made in one
    jitted call from the seed, in the type they are served in."""
    import numpy as np
    return _weights_fn(cfg_key(cfg))(np.asarray(ref.seed_words(seed)))


def reference_weights(cfg: dict, seed: int):
    """The same values as the reference wants them (stacked layers)."""
    import numpy as np
    fn = jax.jit(lambda w: ref.init_params(cfg, (w[0], w[1]),
                                           dtype_of(cfg)))
    return fn(np.asarray(ref.seed_words(seed)))


def build_net(cfg: dict, seed: int, train: bool):
    """The program's model with the seed's weights installed. For serving
    the parameters carry no gradient buffer (``grad_req='null'``, MXNet's
    own idiom for inference): the eager buffer would double the weights'
    memory for nothing."""
    from mxnet_tpu.models.gpt import GPTConfig, GPTModel
    V, D, L, H, P, eps = ref.sizes(cfg)
    net = GPTModel(GPTConfig(
        vocab_size=V, hidden_size=D, num_layers=L, num_heads=H,
        max_position_embeddings=P, dropout=0.0, layer_norm_eps=eps,
        dtype=dtype_of(cfg)))
    weights = program_weights(cfg, seed)
    params = net.collect_params()
    missing = set(params) ^ set(weights)
    if missing:
        raise SystemExit(f"bench: parameter names differ between the "
                         f"program and the builder: {sorted(missing)[:6]}")
    for name, p in params.items():
        w = weights[name]
        if tuple(p.shape) != tuple(w.shape):
            raise SystemExit(f"bench: {name}: program shape {p.shape}, "
                             f"reference shape {w.shape}")
        if not train:
            p.grad_req = "null"
        p.set_data(w)
    return net
