"""Builds the program's Cohere2-MoE (``mxnet_tpu.models.cohere2_moe.
Cohere2MoEForCausalLM``) for a configuration file and fills it with the
reference's weights for a seed. A serving builder: ``parts`` has nothing to
split, and the reference has no training steps.

The program is given the same share as the reference: the configuration's
``num_experts`` experts of the published ``published.num_experts``, the range
``experts_held``; its router keeps every output."""
from __future__ import annotations

import functools

import jax
import numpy as np

from mxbench.models.common import dtype_of, install
from mxbench.reference import cohere2_moe as ref
from mxbench.work import cohere2_moe as work  # noqa: F401  (the count)

#: program parameter suffix -> reference leaf. The program's Dense stores
#: [out, in]; the reference stores [in, out]. The program holds the shared
#: experts as one gated MLP of their widths side by side.
_LAYER_MAP = {
    "input_layernorm.weight": "norm",
    "self_attn.q_proj.weight": "q_w", "self_attn.k_proj.weight": "k_w",
    "self_attn.v_proj.weight": "v_w", "self_attn.o_proj.weight": "o_w",
    "mlp.router": "router_w", "mlp.gate": "gate_w", "mlp.up": "up_w",
    "mlp.down": "down_w",
    "mlp.shared_gate_proj.weight": "sgate_w",
    "mlp.shared_up_proj.weight": "sup_w",
    "mlp.shared_down_proj.weight": "sdown_w"}
_TOP_MAP = {"model.embed_tokens.weight": "embed",
            "model.norm.weight": "final_norm"}
_DENSE = ("q_w", "k_w", "v_w", "o_w")

cfg_key = ref.cfg_key


def leaf_of(name: str):
    """Program parameter name -> (reference leaf, layer index or None,
    transposed?)."""
    if name in _TOP_MAP:
        return _TOP_MAP[name], None, False
    _, _, layer, suffix = name.split(".", 3)
    leaf = _LAYER_MAP[suffix]
    return leaf, int(layer), leaf in _DENSE


def parts(name: str, array):
    leaf, layer, _ = leaf_of(name)
    return [(leaf, layer, array)]


def as_program(leaf: str, x):
    """A reference leaf as the program stores it."""
    if leaf in _DENSE:
        return x.T
    if leaf in ("sgate_w", "sup_w"):            # [S, D, F] -> [S F, D]
        S, D, F = x.shape
        return x.transpose(0, 2, 1).reshape(S * F, D)
    if leaf == "sdown_w":                       # [S, F, D] -> [D, S F]
        S, F, D = x.shape
        return x.reshape(S * F, D).T
    return x


@functools.lru_cache(maxsize=None)
def _makers(key):
    """Jitted makers of the seed's weights, in the served type: the top
    leaves, and one layer (its index is an argument, so every layer comes
    from one program). A layer at a time keeps the float32 draws of one
    layer alive, not of the whole model."""
    cfg = dict(key)
    dtype = dtype_of(cfg)
    top = jax.jit(lambda words: ref.init_top(cfg, (words[0], words[1]),
                                             dtype))
    layer = jax.jit(lambda words, i: ref.init_layer(
        cfg, (words[0], words[1]), i, dtype))
    return top, layer


def reference_weights(cfg: dict, seed: int):
    """The reference's tree (``ref.init_params``'s), a layer at a time."""
    top, layer = _makers(cfg_key(cfg))
    words = np.asarray(ref.seed_words(seed))
    L = ref.sizes(cfg)["L"]
    layers = {leaf: [None] * L for leaf in ref.LAYER_LEAVES}
    for i in range(L):
        for leaf, x in layer(words, np.int32(i)).items():
            layers[leaf][i] = x
    return {**top(words), "layers": layers}


def program_weights(cfg: dict, seed: int):
    """{program parameter name: array} on the device, in the type they are
    served in: the reference's values as the program stores them."""
    tree = reference_weights(cfg, seed)
    out = {name: tree[leaf] for name, leaf in _TOP_MAP.items()}
    for suffix, leaf in _LAYER_MAP.items():
        for i, x in enumerate(tree["layers"][leaf]):
            tree["layers"][leaf][i] = None           # one copy alive
            out[f"model.layers.{i}.{suffix}"] = as_program(leaf, x)
    return out


def build_net(cfg: dict, seed: int, train: bool):
    """The program's model with the seed's weights installed."""
    from mxnet_tpu.models.cohere2_moe import (Cohere2MoEConfig,
                                              Cohere2MoEForCausalLM)
    z = ref.sizes(cfg)
    net = Cohere2MoEForCausalLM(Cohere2MoEConfig(
        vocab_size=z["V"], hidden_size=z["D"], intermediate_size=z["F"],
        num_layers=z["L"], num_heads=z["H"], num_kv_heads=z["G"],
        head_dim=z["hd"], num_experts=z["E"], num_experts_per_tok=z["k"],
        num_shared_experts=z["S"], experts_held=z["held"],
        layer_types=z["kinds"], sliding_window=z["window"],
        rope_theta=z["theta"], layer_norm_eps=z["eps"],
        logit_scale=z["scale"],
        max_position_embeddings=int(cfg["max_position_embeddings"]),
        dtype=dtype_of(cfg)))
    return install(net, program_weights(cfg, seed), train)
