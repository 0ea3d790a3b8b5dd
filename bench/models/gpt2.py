"""Builds the program's GPT-2 (``mxnet_tpu.models.gpt.GPTModel``) for a
configuration file and fills it with the reference's weights for a seed.

This is the only file that knows both sides: the program's parameter names
and layouts, and the reference's (``bench/reference/gpt2.py``). A new model
family adds a module like this one, named by its configuration file's
``builder``, with its reference as ``ref`` and its count of operations and
bytes (``bench/work/gpt2.py`` here) as ``work``: the harness and the traffic
modules reach all three through the builder alone.
"""
from __future__ import annotations

from mxbench.models.common import dtype_of, install, seeded
from mxbench.reference import gpt2 as ref
from mxbench.work import gpt2 as work  # noqa: F401  (the family's count)

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


cfg_key = ref.cfg_key


def program_weights(cfg: dict, seed: int):
    """{program parameter name: array} on the default device, made in one
    jitted call from the seed, in the type they are served in."""
    return seeded(ref, cfg, seed, program_names(cfg), leaf_of)


def reference_weights(cfg: dict, seed: int):
    """The same values as the reference wants them (stacked layers)."""
    return seeded(ref, cfg, seed)


def build_net(cfg: dict, seed: int, train: bool):
    """The program's model with the seed's weights installed."""
    from mxnet_tpu.models.gpt import GPTConfig, GPTModel
    V, D, L, H, P, eps = ref.sizes(cfg)
    net = GPTModel(GPTConfig(
        vocab_size=V, hidden_size=D, num_layers=L, num_heads=H,
        max_position_embeddings=P, dropout=0.0, layer_norm_eps=eps,
        dtype=dtype_of(cfg)))
    return install(net, program_weights(cfg, seed), train)
