"""The count of Cohere2-MoE (``reference/cohere2_moe.py``): operations and
bytes from the configuration's own keys, for ``bench/flops.py``'s sums and the
traffic modules' facts (``bench/README.md``, "Adding a model family"), and the
per-kernel count that ``readers/kernel_roofline.py`` takes.

What differs from a dense decoder's count:

- a token is multiplied by ``num_experts_per_tok`` routed experts of the
  published ``E``, of which this configuration holds ``num_experts``: under
  uniform routing ``k * held / E`` of them here, and by all
  ``num_shared_experts``; the router's ``hidden_size x E`` is read by every
  token;
- a pass over ``rows`` token rows touches, under uniform routing, ``held * (1
  - (1 - k / E) ** rows)`` held experts a layer, and must read only those: at
  one row the least of any step, which is what the fact ``weight_bytes``
  carries;
- a query at position ``t`` attends ``t + 1`` keys in a full layer and
  ``min(t + 1, sliding_window)`` in a sliding one, and the cache a new token
  reads is capped the same way;
- the vocabulary is the slice served here: the tied head's product is
  ``hidden_size x vocab_size`` of the configuration as it is run.
"""
from __future__ import annotations

import numpy as np

KERNELS = ("moe_experts", "paged_attn")
SLIDING = "sliding_attention"


def _sizes(cfg: dict):
    held = int(cfg["num_experts"])
    kinds = tuple(cfg["layer_types"])
    return dict(
        V=int(cfg["vocab_size"]), D=int(cfg["hidden_size"]),
        F=int(cfg["intermediate_size"]), H=int(cfg["num_attention_heads"]),
        G=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        L=len(kinds), n_sliding=sum(k == SLIDING for k in kinds),
        E=int(dict(cfg.get("published", ())).get("num_experts", held)),
        held=held, k=int(cfg["num_experts_per_tok"]),
        S=int(cfg["num_shared_experts"]), W=int(cfg["sliding_window"]))


def max_positions(cfg: dict) -> int:
    return int(cfg["max_position_embeddings"])


def _attn_params(z) -> int:
    return 2 * z["D"] * z["H"] * z["hd"] + 2 * z["D"] * z["G"] * z["hd"]


def _expert_params(z) -> int:
    return 3 * z["D"] * z["F"]


def experts_a_token(cfg: dict) -> float:
    """Held routed experts that multiply a token, under uniform routing."""
    z = _sizes(cfg)
    return z["k"] * z["held"] / z["E"]


def experts_touched(cfg: dict, rows: int) -> float:
    """Held experts a layer that ``rows`` tokens reach at all, under uniform
    routing: each token misses a given expert with probability ``1 - k / E``."""
    z = _sizes(cfg)
    return z["held"] * (1.0 - (1.0 - z["k"] / z["E"]) ** int(rows))


def _attended(z, t):
    """Keys a query at ``t`` (array) attends: ``(full layer, sliding)``."""
    t = np.asarray(t, np.int64)
    return t + 1, np.minimum(t + 1, z["W"])


def _attn_flops(z, n: int, start: int) -> float:
    """QK^T and PV over the keys attended, every layer."""
    full, sliding = _attended(z, np.arange(start, start + n))
    return float(z["H"] * z["hd"] * 4 * (
        (z["L"] - z["n_sliding"]) * full.sum()
        + z["n_sliding"] * sliding.sum()))


def _routed_flops(cfg: dict, n: int) -> float:
    z = _sizes(cfg)
    return 2.0 * z["L"] * experts_a_token(cfg) * _expert_params(z) * n


def forward_flops(cfg: dict, n: int, start: int) -> float:
    """``n`` new positions from cache depth ``start``: the projections, the
    router, ``k * held / E`` routed experts and the shared ones a token, the
    slice's head, and the attention over the keys each position sees."""
    z = _sizes(cfg)
    dense = z["L"] * (_attn_params(z) + z["D"] * z["E"]
                      + z["S"] * _expert_params(z)) + z["D"] * z["V"]
    return 2.0 * dense * n + _routed_flops(cfg, n) + _attn_flops(z, n, start)


def weight_bytes(cfg: dict, rows: int, itemsize: int = 2) -> int:
    """What a pass over ``rows`` token rows must read: attention, router,
    shared experts and the one gain a layer, the held experts the rows reach
    (uniform routing), the tied table once (the head's product) and the final
    gain."""
    z = _sizes(cfg)
    layer = (_attn_params(z) + z["D"] * z["E"] + z["S"] * _expert_params(z)
             + z["D"] + experts_touched(cfg, rows) * _expert_params(z))
    return int((z["L"] * layer + z["D"] * z["V"] + z["D"]) * itemsize)


def cache_bytes(cfg: dict, depth: int, itemsize: int = 2) -> int:
    """What one new token reads of cached state when ``depth`` positions are
    live, itself included: a key and a value row of ``kv_heads x head_dim``
    for every position in the full layers, for the window's in the sliding
    ones."""
    z = _sizes(cfg)
    full, sliding = _attended(z, depth - 1)
    return int(((z["L"] - z["n_sliding"]) * full + z["n_sliding"] * sliding)
               * 2 * z["G"] * z["hd"] * itemsize)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * forward_flops(cfg, seq, 0) / seq


def train_attention(cfg: dict):
    """``(heads, head_dim, layers)`` of the attention a training step would
    run (the benchmark trains no cell of this family)."""
    z = _sizes(cfg)
    return z["H"], z["hd"], z["L"]


def kernel_count(cfg: dict, kernel: str, n: int, start: int,
                 itemsize: int = 2):
    """``(operations, bytes)`` that ``kernel`` (one of ``KERNELS``) needs in
    all its layers for ``n`` new positions of one row from depth ``start``.

    ``moe_experts``: the grouped products of the held routed experts:
    ``k * held / E`` experts a token; the matrices of the experts the ``n``
    tokens reach, each token's row read and its output written once an
    assignment. ``paged_attn``: the scores and weighted sums over the keys
    each position sees, without the projections (queries read, outputs
    written, the new keys and values written, the cached rows that the last
    position sees read once)."""
    z = _sizes(cfg)
    if kernel == "moe_experts":
        io = 2 * n * experts_a_token(cfg) * z["D"] * itemsize * z["L"]
        held = experts_touched(cfg, n) * _expert_params(z) * itemsize * z["L"]
        return _routed_flops(cfg, n), io + held
    if kernel == "paged_attn":
        io = (2 * n * z["H"] * z["hd"] + 2 * n * z["G"] * z["hd"]) \
            * itemsize * z["L"]
        return _attn_flops(z, n, start), io + cache_bytes(cfg, start + n,
                                                          itemsize)
    raise ValueError(f"no kernel {kernel!r} in this family: {KERNELS}")
