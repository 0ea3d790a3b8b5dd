"""Builds the program's grouped-query decoder
(``mxnet_tpu.models.llama.LlamaForCausalLM``) for a configuration file and
fills it with the reference's weights for a seed: the second family of the
harness, kept wholly beside the rehearsal cell that uses it. A serving
builder: ``parts`` has nothing to split, and the reference has no training
steps."""
from __future__ import annotations

from mxbench.models.common import dtype_of, install, seeded
from mxbench.reference import gqa as ref
from mxbench.work import gqa as work  # noqa: F401  (the family's count)

#: program parameter suffix -> reference leaf. The program's Dense stores
#: [out, in]; the reference stores [in, out].
_LAYER_MAP = {
    "input_layernorm.gamma": "in_norm", "self_attn.q_proj.weight": "q_w",
    "self_attn.k_proj.weight": "k_w", "self_attn.v_proj.weight": "v_w",
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


def program_names(cfg: dict):
    names = [f"model.layers.{i}.{s}"
             for i in range(int(cfg["num_hidden_layers"]))
             for s in _LAYER_MAP]
    return names + list(_TOP_MAP)


def program_weights(cfg: dict, seed: int):
    """{program parameter name: array}, made in one jitted call from the
    seed, in the type they are served in."""
    return seeded(ref, cfg, seed, program_names(cfg), leaf_of)


def reference_weights(cfg: dict, seed: int):
    return seeded(ref, cfg, seed)


def build_net(cfg: dict, seed: int, train: bool):
    """The program's model with the seed's weights installed."""
    from mxnet_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    V, D, L, H, G, hd, I, eps, theta = ref.sizes(cfg)
    net = LlamaForCausalLM(LlamaConfig(
        vocab_size=V, hidden_size=D, intermediate_size=I, num_layers=L,
        num_heads=H, num_kv_heads=G, rope_theta=theta, rms_eps=eps,
        dtype=dtype_of(cfg),
        tie_embeddings=bool(cfg["tie_word_embeddings"])))
    return install(net, program_weights(cfg, seed), train)
