"""The count of a grouped-query decoder (``reference/gqa.py``): operations
and bytes from the configuration's own keys, for ``bench/flops.py``'s sums
and the traffic modules' facts (``bench/README.md``, "Adding a model
family"). What differs from GPT-2's count: keys and values are stored for
``num_key_value_heads`` heads and not for every query head, so the cached
state a token reads is ``layers * 2 * kv_heads * head_dim`` values a position;
the MLP has three matrices; the head is a matrix of its own."""
from __future__ import annotations


def _sizes(cfg: dict):
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return (int(cfg["vocab_size"]), D, int(cfg["num_hidden_layers"]), H,
            int(cfg["num_key_value_heads"]), D // H,
            int(cfg["intermediate_size"]))


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product for every token: q and o
    (D x H*hd each), k and v (D x G*hd each), gate, up and down (D x I each),
    and the head (D x V; the embedding is a lookup)."""
    V, D, L, H, G, hd, I = _sizes(cfg)
    return L * (2 * D * H * hd + 2 * D * G * hd + 3 * D * I) + V * D


def max_positions(cfg: dict) -> int:
    return int(cfg["max_position_embeddings"])


def forward_flops(cfg: dict, n: int, start: int) -> float:
    """``n`` new positions from cache depth ``start``: every query head reads
    every row before it, 2*hd for QK^T and 2*hd for PV a row (sharing keys
    between query heads saves bytes, not operations)."""
    _, _, L, H, _, hd, _ = _sizes(cfg)
    context_sum = n * start + n * (n + 1) // 2
    return 2.0 * matmul_params(cfg) * n + 4.0 * L * H * hd * context_sum


def weight_bytes(cfg: dict, rows: int, itemsize: int = 2) -> int:
    """Dense: every weight once, however many rows the pass holds."""
    return matmul_params(cfg) * itemsize


def cache_bytes(cfg: dict, depth: int, itemsize: int = 2) -> int:
    """One key and one value row of ``kv_heads * head_dim`` a layer for each
    of the ``depth`` positions a new token attends over."""
    _, _, L, _, G, hd, _ = _sizes(cfg)
    return depth * L * 2 * G * hd * itemsize


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * forward_flops(cfg, seq, 0) / seq


def train_attention(cfg: dict):
    """``(heads, head_dim, layers)``: the kernel runs over the query heads."""
    _, _, L, H, _, hd, _ = _sizes(cfg)
    return H, hd, L
