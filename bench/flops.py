"""Operations and bytes that the algorithms need, from shapes alone.

Kept with the benchmark so that no later PR can change the yardstick. Every
function counts what the mathematics requires: a multiply-add is two
operations, causal attention is counted over the lower triangle only, and
recomputation (a flash backward's second look at QK^T, a rematerialised
forward) is not counted. So a share of a peak computed from these cannot be
flattered by work the program chose to do twice.
"""
from __future__ import annotations


def _sizes(cfg: dict):
    return (int(cfg["vocab_size"]), int(cfg["n_embd"]), int(cfg["n_layer"]),
            int(cfg["n_head"]), int(cfg["n_positions"]))


def gpt2_param_count(cfg: dict) -> int:
    """Every stored parameter; the output head is the token embedding."""
    V, D, L, _, P = _sizes(cfg)
    per_layer = (2 * D) + (D * 3 * D + 3 * D) + (D * D + D) + (2 * D) \
        + (D * 4 * D + 4 * D) + (4 * D * D + D)
    return V * D + P * D + L * per_layer + 2 * D


def gpt2_matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product for every token: the
    blocks' four matrices and the tied head. The embedding *lookups* and the
    position table cost no multiply."""
    V, D, L, _, _ = _sizes(cfg)
    return L * 12 * D * D + V * D


def forward_flops(cfg: dict, new_tokens: int, context_sum: int) -> float:
    """Forward pass over ``new_tokens`` positions whose attention reads
    ``context_sum`` key/value rows in total (for a whole causal sequence of
    length T that is T(T+1)/2; for a decode step, the live lengths summed).
    Each query row pays 2*D for QK^T and 2*D for PV per row read, per layer.
    """
    _, D, L, _, _ = _sizes(cfg)
    return 2.0 * gpt2_matmul_params(cfg) * new_tokens \
        + 4.0 * L * D * context_sum


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward) of one token in a causal
    sequence of ``seq``: 6*N_matmul + 6*L*D*(seq+1). The familiar
    12*L*T*D counts the masked upper triangle too; this does not."""
    return 3.0 * forward_flops(cfg, seq, seq * (seq + 1) // 2) / seq


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    _, D, L, _, _ = _sizes(cfg)
    return L * 2 * D * itemsize


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of the weights a forward pass must read once (the position
    table and the embedding rows of the tokens are read by lookup: the head
    reads the whole token table anyway)."""
    return gpt2_matmul_params(cfg) * itemsize


def decode_step_least_s(cfg: dict, live_lengths, peaks: dict) -> dict:
    """Least time of one decode step over rows at ``live_lengths``: every
    weight once, each row's live keys and values once; against the FLOPs of
    one token per row. Says which bound binds."""
    rows = len(live_lengths)
    ctx = int(sum(live_lengths))
    nbytes = weight_bytes(cfg) + ctx * kv_bytes_per_token(cfg)
    flops = forward_flops(cfg, rows, ctx)
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_flop = flops / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_mem, t_flop), "bytes": nbytes, "flops": flops,
            "binds": "hbm" if t_mem >= t_flop else "flops"}


def prefill_least_s(cfg: dict, chunks, peaks: dict) -> dict:
    """Least time of prefill work given as ``(new_tokens, start)`` chunks:
    each chunk reads every weight once and the keys and values before it,
    and writes its own."""
    flops = nbytes = 0.0
    for n, start in chunks:
        ctx = n * start + n * (n + 1) // 2
        flops += forward_flops(cfg, n, ctx)
        nbytes += weight_bytes(cfg) + (start + n) * kv_bytes_per_token(cfg)
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_flop = flops / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_mem, t_flop), "bytes": nbytes, "flops": flops,
            "binds": "hbm" if t_mem >= t_flop else "flops"}


def flash_attention_train(batch: int, heads: int, seq: int, head_dim: int,
                          peaks: dict, itemsize: int = 2) -> dict:
    """Causal attention forward and backward at (B, H, T, hd): the forward's
    two products and the backward's four (dV, dP, dQ, dK), each over the
    lower triangle, T(T+1)/2 * hd multiply-adds. The backward's recomputed
    QK^T is not counted. Bytes: q, k, v read and o written forward; q, k, v,
    o, do read and dq, dk, dv written backward."""
    tri = seq * (seq + 1) // 2
    flops = 6 * 2.0 * batch * heads * tri * head_dim
    nbytes = 12.0 * batch * heads * seq * head_dim * itemsize
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_flop = flops / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_mem, t_flop), "bytes": nbytes, "flops": flops,
            "binds": "hbm" if t_mem >= t_flop else "flops"}
