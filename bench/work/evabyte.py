"""The count of EvaByte (``reference/evabyte.py``): operations and bytes from
the configuration's own keys, for ``bench/flops.py``'s sums and the traffic
modules' facts (``bench/README.md``, "Adding a model family"), and the
per-kernel count that ``readers/kernel_roofline.py`` takes.

What differs from a dense decoder's count:

- a query at position ``t`` attends ``(t mod w) + 1`` exact rows of its own
  window and ``(w / c) * (t // w)`` chunk summaries, not ``t + 1`` rows:
  ``w = window_size``, ``c = chunk_size``;
- a window that ends is *summarised*: its ``w`` keys and values are read
  once and ``w / c`` pairs written, a layer (the pooling logits ``phi . k``,
  2 ``hd`` operations a position and head, and the two weighted sums, 4
  ``hd``);
- the cache a request holds is folded: what a new token reads of it is the
  rows it attends, which past the first window grow by ``1 / c`` a position
  on average;
- the model is served by its first prediction head: the head's product is
  ``hidden_size x vocab_size``, not ``x num_pred_heads``.
"""
from __future__ import annotations

import numpy as np

KERNELS = ("eva_attn", "eva_summarize")


def _sizes(cfg: dict):
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(V=int(cfg["vocab_size"]), D=D,
                I=int(cfg["intermediate_size"]), H=H, hd=D // H,
                L=int(cfg["num_hidden_layers"]), c=int(cfg["chunk_size"]),
                w=int(cfg["window_size"]))


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product for every served token:
    q, k, v, o (D x D each) and the MLP (3 x D x I) a layer, and the first
    prediction head (D x V; the embedding is a lookup)."""
    z = _sizes(cfg)
    return z["L"] * (4 * z["D"] ** 2 + 3 * z["D"] * z["I"]) + z["D"] * z["V"]


def max_positions(cfg: dict) -> int:
    return int(cfg["max_position_embeddings"])


def rows_read(cfg: dict, t):
    """Rows a query at position ``t`` (array) attends: its window's exact
    rows as far as itself, and every earlier window's summaries."""
    z = _sizes(cfg)
    t = np.asarray(t, np.int64)
    return t % z["w"] + 1 + (z["w"] // z["c"]) * (t // z["w"])


def windows_ended(cfg: dict, n: int, start: int) -> int:
    """Windows that the positions ``[start, start + n)`` complete."""
    w = _sizes(cfg)["w"]
    return (start + n) // w - start // w


def _attn_flops(cfg: dict, n: int, start: int) -> float:
    """QK^T and PV over the rows read, every layer."""
    z = _sizes(cfg)
    t = np.arange(start, start + n)
    return float(z["L"] * z["H"] * z["hd"] * 4 * rows_read(cfg, t).sum())


def _summarize_flops(cfg: dict, n: int, start: int) -> float:
    """The pooling logits and the two weighted sums of every window ended."""
    z = _sizes(cfg)
    return float(z["L"] * windows_ended(cfg, n, start)
                 * z["w"] * z["H"] * 6 * z["hd"])


def forward_flops(cfg: dict, n: int, start: int) -> float:
    """``n`` new positions from cache depth ``start``: the matrices, the
    attention over the rows each position reads, the summarising of each
    window that ``[start, start + n)`` completes."""
    return (2.0 * matmul_params(cfg) * n + _attn_flops(cfg, n, start)
            + _summarize_flops(cfg, n, start))


def weight_bytes(cfg: dict, rows: int, itemsize: int = 2) -> int:
    """Dense: every weight once, however many rows the pass holds, and the
    one embedding row a token looks up."""
    return (matmul_params(cfg) + _sizes(cfg)["D"]) * itemsize


def cache_bytes(cfg: dict, depth: int, itemsize: int = 2) -> int:
    """What one new token reads of cached state when ``depth`` positions are
    live, itself included: a key and a value row of ``hidden_size`` for every
    row held, every layer."""
    z = _sizes(cfg)
    return int(rows_read(cfg, depth - 1)) * 2 * z["D"] * itemsize * z["L"]


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
    all its layers for ``n`` new positions of one row from depth ``start``,
    without the projections around it.

    ``eva_summarize``: each window ended in ``[start, start + n)`` has its
    ``w`` keys and values read and ``w / c`` pairs written. ``eva_attn``: the
    scores and weighted sums over the rows read (queries read, outputs
    written, the new keys and values written, the rows held at the last
    position read once) and, since the summarising lies inside the
    attention's scope, the summarising too."""
    z = _sizes(cfg)
    row = z["D"] * itemsize
    ended = windows_ended(cfg, n, start)
    s_flops = _summarize_flops(cfg, n, start)
    s_bytes = z["L"] * ended * 2 * row * (z["w"] + z["w"] // z["c"])
    if kernel == "eva_summarize":
        return s_flops, s_bytes
    if kernel == "eva_attn":
        io = 4 * n * row * z["L"]
        held = rows_read(cfg, start + n - 1) * 2 * row * z["L"]
        return _attn_flops(cfg, n, start) + s_flops, io + held + s_bytes
    raise ValueError(f"no kernel {kernel!r} in this family: {KERNELS}")
