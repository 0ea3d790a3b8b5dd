"""Builds the program's EvaByte (``mxnet_tpu.models.evabyte.
EvaByteForCausalLM``) for a configuration file and fills it with the
reference's weights for a seed. A serving builder: ``parts`` has nothing to
split, and the reference has no training steps."""
from __future__ import annotations

import functools

import jax
import numpy as np

from mxbench.models.common import dtype_of, install
from mxbench.reference import evabyte as ref
from mxbench.work import evabyte as work  # noqa: F401  (the count)

#: program parameter suffix -> reference leaf. The program's Dense stores
#: [out, in]; the reference stores [in, out].
_LAYER_MAP = {
    "input_layernorm.weight": "in_norm",
    "self_attn.q_proj.weight": "q_w", "self_attn.k_proj.weight": "k_w",
    "self_attn.v_proj.weight": "v_w", "self_attn.o_proj.weight": "o_w",
    "self_attn.phi": "phi", "self_attn.mu": "mu",
    "post_attention_layernorm.weight": "post_norm",
    "mlp.gate_proj.weight": "gate_w", "mlp.up_proj.weight": "up_w",
    "mlp.down_proj.weight": "down_w"}
_TOP_MAP = {"model.embed_tokens.weight": ("embed", False),
            "model.norm.weight": ("norm", False),
            "lm_head.weight": ("head", True)}

cfg_key = ref.cfg_key


def leaf_of(name: str):
    """Program parameter name -> (reference leaf, layer index or None,
    transposed?)."""
    if name in _TOP_MAP:
        leaf, transposed = _TOP_MAP[name]
        return leaf, None, transposed
    _, _, layer, suffix = name.split(".", 3)
    leaf = _LAYER_MAP[suffix]
    return leaf, int(layer), leaf.endswith("_w")


def parts(name: str, array):
    leaf, layer, _ = leaf_of(name)
    return [(leaf, layer, array)]


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
    served in: the reference's values, matrices as the program's Dense
    stores them ([out, in])."""
    tree = reference_weights(cfg, seed)
    out = {}
    for name, (leaf, transposed) in _TOP_MAP.items():
        out[name] = tree[leaf].T if transposed else tree[leaf]
    for suffix, leaf in _LAYER_MAP.items():
        for i, x in enumerate(tree["layers"][leaf]):
            tree["layers"][leaf][i] = None           # one copy alive
            out[f"model.layers.{i}.{suffix}"] = \
                x.T if leaf.endswith("_w") else x
    return out


def build_net(cfg: dict, seed: int, train: bool):
    """The program's model with the seed's weights installed."""
    from mxnet_tpu.models.evabyte import EvaByteConfig, EvaByteForCausalLM
    z = ref.sizes(cfg)
    net = EvaByteForCausalLM(EvaByteConfig(
        vocab_size=z["V"], hidden_size=z["D"], intermediate_size=z["I"],
        num_layers=z["L"], num_heads=z["H"], head_dim=z["hd"],
        chunk_size=z["c"], window_size=z["w"], num_pred_heads=z["P"],
        rope_theta=z["theta"], rms_eps=z["eps"],
        max_position_embeddings=int(cfg["max_position_embeddings"]),
        dtype=dtype_of(cfg)))
    return install(net, program_weights(cfg, seed), train)
