"""Operations and bytes that the algorithms need, from shapes alone.

Kept with the benchmark so that no later PR can change the yardstick. Every
function counts what the mathematics requires: a multiply-add is two
operations, causal attention is counted over the lower triangle only, and
recomputation (a flash backward's second look at QK^T, a rematerialised
forward) is not counted. So a share of a peak computed from these cannot be
flattered by work the program chose to do twice.

Nothing here reads a configuration. What depends on the architecture (the
operations of a forward pass, the weights a pass must read, the cached state
a token must read) is the model family's own count, ``bench/work/<family>.py``,
which a builder carries as ``builder.work`` (``bench/README.md`` lists its
functions); the sums over rows and chunks below take its answers.
"""
from __future__ import annotations


def least(nbytes, flops, peaks: dict) -> dict:
    """The least time the chip could take to move ``nbytes`` and to do
    ``flops``, and which of the two bounds binds."""
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_flop = flops / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_mem, t_flop), "bytes": nbytes, "flops": flops,
            "binds": "hbm" if t_mem >= t_flop else "flops"}


def decode_step_least_s(work, cfg: dict, live_lengths, peaks: dict) -> dict:
    """Least time of one decode step over rows that attend over
    ``live_lengths`` positions each: the weights a pass over that many rows
    must read, each row's cached state once; against the operations of one
    token per row."""
    nbytes = work.weight_bytes(cfg, len(live_lengths)) + sum(
        work.cache_bytes(cfg, int(d)) for d in live_lengths)
    flops = sum(work.forward_flops(cfg, 1, int(d) - 1) for d in live_lengths)
    return least(nbytes, flops, peaks)


def prefill_least_s(work, cfg: dict, chunks, peaks: dict) -> dict:
    """Least time of prefill work given as ``(new_tokens, start)`` chunks:
    each chunk reads the weights of a pass over its rows once, and reads the
    cached state before it and writes its own (together what a token at the
    chunk's last position reads)."""
    flops = nbytes = 0.0
    for n, start in chunks:
        flops += work.forward_flops(cfg, n, start)
        nbytes += work.weight_bytes(cfg, n) + work.cache_bytes(cfg, start + n)
    return least(nbytes, flops, peaks)


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
    return least(nbytes, flops, peaks)
