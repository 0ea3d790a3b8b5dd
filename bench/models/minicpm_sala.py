"""Builds the program's MiniCPM-SALA
(``mxnet_tpu.models.minicpm_sala.MiniCPMSALAForCausalLM``) for a configuration
file and fills it with the reference's weights for a seed. A serving builder:
``parts`` has nothing to split, and the reference has no training steps."""
from __future__ import annotations

import functools

import jax
import numpy as np

from mxbench.models.common import dtype_of, install
from mxbench.reference import minicpm_sala as ref
from mxbench.work import minicpm_sala as work  # noqa: F401  (the count)

#: program parameter suffix -> reference leaf. The program's Dense stores
#: [out, in]; the reference stores [in, out].
_LAYER_MAP = {
    "input_layernorm.gamma": "in_norm", "self_attn.q_norm": "q_norm",
    "self_attn.k_norm": "k_norm", "self_attn.o_norm": "o_norm",
    "self_attn.q_proj.weight": "q_w", "self_attn.k_proj.weight": "k_w",
    "self_attn.v_proj.weight": "v_w", "self_attn.g_proj.weight": "g_w",
    "self_attn.o_proj.weight": "o_w",
    "post_attention_layernorm.gamma": "post_norm",
    "mlp.gate_proj.weight": "gate_w", "mlp.up_proj.weight": "up_w",
    "mlp.down_proj.weight": "down_w"}
_TOP_MAP = {"model.embed_tokens.weight": ("embed", False),
            "model.norm.gamma": ("norm", False),
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
    leaves, and one layer of each kind (the layer's index is an argument, so
    every layer of a kind comes from one program). A layer at a time keeps
    the float32 draws of one layer alive, not of the whole model
    (``models/common.seeded`` makes a model in one program)."""
    cfg = dict(key)
    dtype = dtype_of(cfg)
    top = jax.jit(lambda words: ref.init_top(cfg, (words[0], words[1]),
                                             dtype))
    layer = {kind: jax.jit(functools.partial(
        lambda kind, words, i: ref.init_layer(cfg, (words[0], words[1]), i,
                                              kind, dtype), kind))
        for kind in set(dict(key)["mixer_types"])}
    return top, layer


def reference_weights(cfg: dict, seed: int):
    """The reference's tree (``ref.init_params``'s), a layer at a time."""
    top, layer = _makers(cfg_key(cfg))
    words = np.asarray(ref.seed_words(seed))
    mixers = ref.sizes(cfg)["mixers"]
    layers = {leaf: [None] * len(mixers) for leaf in ref.LAYER_LEAVES}
    for i, kind in enumerate(mixers):
        for leaf, x in layer[kind](words, np.int32(i)).items():
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
            if x is not None:
                tree["layers"][leaf][i] = None       # one copy alive
                out[f"model.layers.{i}.{suffix}"] = \
                    x.T if leaf.endswith("_w") else x
    return out


def build_net(cfg: dict, seed: int, train: bool):
    """The program's model with the seed's weights installed."""
    from mxnet_tpu.models.minicpm_sala import (MiniCPMSALAConfig,
                                               MiniCPMSALAForCausalLM)
    from mxnet_tpu.ops.sparse_attention import SparseConfig
    z = ref.sizes(cfg)
    net = MiniCPMSALAForCausalLM(MiniCPMSALAConfig(
        vocab_size=z["V"], hidden_size=z["D"], intermediate_size=z["I"],
        num_heads=z["H"], num_kv_heads=z["G"], head_dim=z["hd"],
        lightning_heads=z["LH"], lightning_head_dim=z["lhd"],
        mixer_types=z["mixers"], first_layer=z["first_layer"],
        published_layers=z["published_layers"], rope_theta=z["theta"],
        rms_eps=z["eps"], scale_emb=z["scale_emb"],
        scale_depth=float(cfg["scale_depth"]),
        mup_denominator=int(cfg["mup_denominator"]),
        dim_model_base=int(cfg["dim_model_base"]),
        max_position_embeddings=int(cfg["max_position_embeddings"]),
        sparse=SparseConfig(
            block=z["block"], kernel=z["kernel"], stride=z["stride"],
            init_blocks=z["init"], window=z["window"], topk=z["topk"],
            dense_len=z["dense_len"]),
        dtype=dtype_of(cfg)))
    return install(net, program_weights(cfg, seed), train)
