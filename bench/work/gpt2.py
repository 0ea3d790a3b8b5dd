"""GPT-2's count: the operations and bytes that a configuration of this
family needs, from its ``config.json`` keys alone. The builder hands it to
the traffic modules as ``builder.work``; the sums, the bounds and which of
them binds are ``bench/flops.py``'s and read no configuration.

Every answer counts what the mathematics requires (``bench/flops.py`` says
how): dense blocks of four matrices, ``12 D^2`` weights a layer, a head tied
to the token embedding, every query row reading every key and value row
before it in every layer.
"""
from __future__ import annotations


def _sizes(cfg: dict):
    return (int(cfg["vocab_size"]), int(cfg["n_embd"]), int(cfg["n_layer"]),
            int(cfg["n_head"]), int(cfg["n_positions"]))


def param_count(cfg: dict) -> int:
    """Every stored parameter; the output head is the token embedding."""
    V, D, L, _, P = _sizes(cfg)
    per_layer = (2 * D) + (D * 3 * D + 3 * D) + (D * D + D) + (2 * D) \
        + (D * 4 * D + 4 * D) + (4 * D * D + D)
    return V * D + P * D + L * per_layer + 2 * D


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product for every token: the
    blocks' four matrices and the tied head. The embedding *lookups* and the
    position table cost no multiply."""
    V, D, L, _, _ = _sizes(cfg)
    return L * 12 * D * D + V * D


def max_positions(cfg: dict) -> int:
    """The most positions the configuration declares."""
    return _sizes(cfg)[4]


def forward_flops(cfg: dict, n: int, start: int) -> float:
    """Operations of a forward pass over ``n`` new positions that start at
    cache depth ``start`` (``start = 0, n = P``: a whole prompt; ``n = 1,
    start = P + j - 1``: a decoded token). The new positions read ``n *
    start + n (n + 1) / 2`` key/value rows between them; each row read costs
    a query row 2*D for QK^T and 2*D for PV, per layer."""
    _, D, L, _, _ = _sizes(cfg)
    context_sum = n * start + n * (n + 1) // 2
    return 2.0 * matmul_params(cfg) * n + 4.0 * L * D * context_sum


def weight_bytes(cfg: dict, rows: int, itemsize: int = 2) -> int:
    """Bytes of the weights that one forward pass over ``rows`` token rows
    must read: all of them once, however many rows (a dense family). The
    position table and the embedding rows of the tokens are read by lookup:
    the head reads the whole token table anyway."""
    return matmul_params(cfg) * itemsize


def cache_bytes(cfg: dict, depth: int, itemsize: int = 2) -> int:
    """Bytes of cached state that one new token must read when it attends
    over ``depth`` positions: a key and a value row of D a layer for each."""
    _, D, L, _, _ = _sizes(cfg)
    return depth * L * 2 * D * itemsize


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward) of one token in a causal
    sequence of ``seq``: 6*N_matmul + 6*L*D*(seq+1). The familiar
    12*L*T*D counts the masked upper triangle too; this does not."""
    return 3.0 * forward_flops(cfg, seq, 0) / seq


def train_attention(cfg: dict):
    """``(heads, head_dim, layers)`` of the causal attention kernel that a
    training step runs forward and backward once a layer."""
    _, D, L, H, _ = _sizes(cfg)
    return H, D // H, L
