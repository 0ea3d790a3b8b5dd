"""The count of MiniCPM-SALA (``reference/minicpm_sala.py``): operations and
bytes from the configuration's own keys, for ``bench/flops.py``'s sums and the
traffic modules' facts (``bench/README.md``, "Adding a model family"), and the
per-kernel count that ``readers/kernel_roofline.py`` takes.

What differs from a dense decoder's count:

- a **lightning layer** reads no keys: one token costs the recurrence,
  ``S = lam S + k^T v`` and ``o = q S`` (5 ``hd ** 2`` operations a head: the
  decay, a multiply-add into the state, a multiply-add out of it), and reads
  and writes the state, ``2 * heads * hd * hd * 4`` bytes a layer whatever
  the depth;
- a **sparse layer** attends the positions of the blocks a query selects,
  not every position before it: all of them while the context is at most
  ``dense_len``, else ``topk`` blocks (the newest one as far as the query),
  and past ``dense_len`` it also scores the compressed keys that are whole
  (``2 * heads * hd`` a compressed key). Its cached state is 2 key/value
  heads, so a position is ``2 * 2 * hd`` values a layer, plus a compressed
  key every ``kernel_stride`` positions.

So ``forward_flops`` grows linearly past the selection's reach and
``cache_bytes`` is constant there but for the compressed keys.
"""
from __future__ import annotations

import numpy as np

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
KERNELS = ("linear_attn", "sparse_attn")


def _sizes(cfg: dict):
    sp = cfg["sparse_config"]
    mixers = list(cfg["mixer_types"])
    return dict(
        V=int(cfg["vocab_size"]), D=int(cfg["hidden_size"]),
        I=int(cfg["intermediate_size"]), H=int(cfg["num_attention_heads"]),
        G=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        LH=int(cfg["lightning_nh"]), lhd=int(cfg["lightning_head_dim"]),
        n_sparse=mixers.count(SPARSE), n_linear=mixers.count(LIGHTNING),
        block=int(sp["block_size"]), kernel=int(sp["kernel_size"]),
        stride=int(sp["kernel_stride"]), topk=int(sp["topk"]),
        dense_len=int(sp["dense_len"]))


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product for every token. Lightning
    layer: q, k, v, gate, o (D x heads*hd each); sparse layer: q, gate, o
    (D x H*hd) and k, v (D x G*hd); each layer's MLP (3 x D x I); the head
    (D x V; the embedding is a lookup)."""
    z = _sizes(cfg)
    D, I = z["D"], z["I"]
    linear = 5 * D * z["LH"] * z["lhd"] + 3 * D * I
    sparse = 3 * D * z["H"] * z["hd"] + 2 * D * z["G"] * z["hd"] + 3 * D * I
    return z["n_linear"] * linear + z["n_sparse"] * sparse + z["V"] * D


def max_positions(cfg: dict) -> int:
    return int(cfg["max_position_embeddings"])


def _attended(z: dict, t):
    """Positions a query at ``t`` (array) attends in a sparse layer."""
    t = np.asarray(t, np.int64)
    blocks = np.minimum(t // z["block"] + 1, z["topk"])
    picked = (blocks - 1) * z["block"] + t % z["block"] + 1
    return np.where(t + 1 <= z["dense_len"], t + 1, picked)


def _scored(z: dict, t):
    """Compressed keys a query at ``t`` scores: none under ``dense_len``."""
    t = np.asarray(t, np.int64)
    whole = np.maximum((t + 1 - z["kernel"]) // z["stride"] + 1, 0)
    return np.where(t + 1 <= z["dense_len"], 0, whole)


def _sparse_flops(z: dict, n: int, start: int) -> float:
    t = np.arange(start, start + n)
    return float(z["n_sparse"] * z["H"] * z["hd"]
                 * (4 * _attended(z, t).sum() + 2 * _scored(z, t).sum()))


def _linear_flops(z: dict, n: int) -> float:
    return float(z["n_linear"] * n * 5 * z["LH"] * z["lhd"] ** 2)


def forward_flops(cfg: dict, n: int, start: int) -> float:
    """``n`` new positions from cache depth ``start``: the matrices, the
    recurrence of the lightning layers, and in the sparse layers QK^T and PV
    over the positions attended plus the scoring."""
    z = _sizes(cfg)
    return (2.0 * matmul_params(cfg) * n + _linear_flops(z, n)
            + _sparse_flops(z, n, start))


def weight_bytes(cfg: dict, rows: int, itemsize: int = 2) -> int:
    """Dense: every weight once, however many rows the pass holds."""
    return matmul_params(cfg) * itemsize


def _state_bytes(z: dict) -> int:
    """The lightning layers' states, read and written: float32."""
    return z["n_linear"] * 2 * z["LH"] * z["lhd"] ** 2 * 4


def _sparse_cache_bytes(z: dict, depth: int, itemsize: int) -> int:
    t = depth - 1
    return int(z["n_sparse"] * z["G"] * z["hd"] * itemsize
               * (2 * _attended(z, t) + _scored(z, t)))


def cache_bytes(cfg: dict, depth: int, itemsize: int = 2) -> int:
    """What one new token reads of cached state when ``depth`` positions are
    live, itself included: the keys and values of the positions it attends
    and the compressed keys it scores in every sparse layer, and every
    lightning layer's state, read and written."""
    z = _sizes(cfg)
    return _sparse_cache_bytes(z, depth, itemsize) + _state_bytes(z)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * forward_flops(cfg, seq, 0) / seq


def train_attention(cfg: dict):
    """``(heads, head_dim, layers)`` of a softmax attention kernel a training
    step would run: the sparse layers' (the benchmark trains no cell of this
    family)."""
    z = _sizes(cfg)
    return z["H"], z["hd"], z["n_sparse"]


def kernel_count(cfg: dict, kernel: str, n: int, start: int,
                 itemsize: int = 2):
    """``(operations, bytes)`` that ``kernel`` (one of ``KERNELS``) needs in
    all its layers for ``n`` new positions of one row from depth ``start``:
    the attention itself, without the projections around it. Bytes: the
    queries read and the outputs written, the new keys and values written
    (lightning: read), and the cached state as ``cache_bytes`` charges it at
    the row's last position."""
    z = _sizes(cfg)
    if kernel == "linear_attn":
        io = 4 * n * z["LH"] * z["lhd"] * itemsize * z["n_linear"]
        return _linear_flops(z, n), io + _state_bytes(z)
    if kernel == "sparse_attn":
        io = (2 * n * z["H"] * z["hd"] + 2 * n * z["G"] * z["hd"]) \
            * itemsize * z["n_sparse"]
        return (_sparse_flops(z, n, start),
                io + _sparse_cache_bytes(z, start + n, itemsize))
    raise ValueError(f"no kernel {kernel!r} in this family: {KERNELS}")
