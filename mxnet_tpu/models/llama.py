"""Llama-family decoder LM (Gluon blocks) — the modern-LLM flagship config
(BASELINE.json config 5: "Llama-3-8B via Gluon nn.Block").

No reference analogue (the reference predates LLMs; its closest artifact is
the fused transformer attention op, reference
src/operator/contrib/transformer.cc:675). Built TPU-first:

- attention via the Pallas flash kernel (mx.ops.attention) or ring/Ulysses
  sequence parallelism (mx.parallel.attention) for long context
- GQA (num_kv_heads < num_heads), RoPE, RMSNorm, SwiGLU
- optional MoE layers (top-k routing with capacity, Mesh-TF style dense
  dispatch) for expert parallelism over the 'ep' mesh axis
- ``llama_shardings`` annotates Megatron-style TP column/row shardings that
  TrainStep/GSPMD compile into ICI collectives
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as onp

from .. import numpy_extension as npx
from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..device import on_tpu
from ..ndarray import NDArray, asarray, invoke_jnp
from ..ops.attention import flash_attention as _flash_attention
from ..ops.paged_prefill import paged_prefill_attention

__all__ = ["LlamaConfig", "LlamaForCausalLM", "LlamaModel", "LlamaStackedDecoder",
           "llama_shardings",
           "LLAMA3_8B", "LLAMA_TINY"]


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    dtype: object = jnp.bfloat16
    tie_embeddings: bool = False
    # attention implementation: 'flash' (Pallas/XLA), 'ring', 'ulysses'
    attn_impl: str = "flash"
    sp_mesh: Optional[object] = None     # jax Mesh for ring/ulysses
    sp_axis: str = "sp"
    # MoE (0 = dense)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_capacity_factor: float = 1.25
    moe_every: int = 1  # every n-th layer is MoE
    # stacked decoder: one set of (num_layers, ...) Parameters applied via
    # lax.scan — O(1) compile time in depth, and the substrate for pipeline
    # parallelism (parallel/pipeline.py). Dense layers only (no MoE).
    stacked: bool = False
    pp_mesh: Optional[object] = None     # jax Mesh enabling GPipe over pp
    pp_axis: str = "pp"
    pp_microbatches: int = 2

    @property
    def hd(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads


LLAMA3_8B = LlamaConfig()
LLAMA_TINY = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                         num_layers=2, num_heads=4, num_kv_heads=2,
                         dtype=jnp.float32)


def _rope(x, positions, theta: float):
    """Rotary embedding, rotate-half convention (lane ``i`` turns against
    lane ``i + D/2``; ``models/cohere2_moe.py`` has the interleaved-pairs
    one); f32 math.

    ``positions`` is [T] (whole batch at the same offsets) or [B, T]
    (per-sequence offsets — the serving engine's continuous batches run
    every slot at its own decode depth)."""
    B, H, T, D = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    positions = jnp.asarray(positions)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., T, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if ang.ndim == 2:
        cos, sin = cos[None, None], sin[None, None]     # [1, 1, T, D/2]
    else:
        cos, sin = cos[:, None], sin[:, None]           # [B, 1, T, D/2]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _decode_positions(pos, T: int):
    """Token positions for an incremental step: scalar ``pos`` (whole
    batch at one offset) -> [T]; per-sequence [B] ``pos`` (continuous
    batching: every slot at its own depth) -> [B, T]."""
    pos = jnp.asarray(pos, jnp.int32)
    steps = jnp.arange(T, dtype=jnp.int32)
    if pos.ndim == 0:
        return pos + steps
    return pos[:, None] + steps[None, :]


class LlamaAttention(HybridBlock):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        hd = cfg.hd
        self.q_proj = nn.Dense(cfg.num_heads * hd, use_bias=False,
                               flatten=False, in_units=cfg.hidden_size,
                               dtype=cfg.dtype)
        self.k_proj = nn.Dense(cfg.num_kv_heads * hd, use_bias=False,
                               flatten=False, in_units=cfg.hidden_size,
                               dtype=cfg.dtype)
        self.v_proj = nn.Dense(cfg.num_kv_heads * hd, use_bias=False,
                               flatten=False, in_units=cfg.hidden_size,
                               dtype=cfg.dtype)
        self.o_proj = nn.Dense(cfg.hidden_size, use_bias=False, flatten=False,
                               in_units=cfg.num_heads * hd, dtype=cfg.dtype)

    def forward(self, x):
        cfg = self.cfg
        B, T, _ = x.shape
        hd = cfg.hd
        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)

        def prep(qv, kv, vv):
            qh = qv.reshape(B, T, cfg.num_heads, hd).transpose(0, 2, 1, 3)
            kh = kv.reshape(B, T, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
            vh = vv.reshape(B, T, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
            pos = jnp.arange(T)
            qh = _rope(qh, pos, cfg.rope_theta)
            kh = _rope(kh, pos, cfg.rope_theta)
            rep = cfg.num_heads // cfg.num_kv_heads
            if rep > 1:  # GQA: repeat kv heads
                kh = jnp.repeat(kh, rep, axis=1)
                vh = jnp.repeat(vh, rep, axis=1)
            if cfg.attn_impl == "ring" and cfg.sp_mesh is not None:
                from ..parallel.attention import ring_attention_sharded
                out = ring_attention_sharded(qh, kh, vh, cfg.sp_mesh,
                                             cfg.sp_axis, causal=True)
            elif cfg.attn_impl == "ulysses" and cfg.sp_mesh is not None:
                from ..parallel.attention import ulysses_attention_sharded
                out = ulysses_attention_sharded(qh, kh, vh, cfg.sp_mesh,
                                                cfg.sp_axis, causal=True)
            else:
                out = _flash_attention(qh, kh, vh, True, None)
            return out.transpose(0, 2, 1, 3).reshape(B, T, cfg.num_heads * hd)

        ctx = invoke_jnp(prep, (q, k, v), {}, name="llama_attention")
        return self.o_proj(ctx)

    def forward_cached(self, x, pos, k_cache, v_cache):
        """Incremental forward: attend ``x`` (positions pos..pos+T-1)
        against the KV cache; returns (out, new_k_cache, new_v_cache)."""
        cfg = self.cfg
        B, T, _ = x.shape
        hd = cfg.hd
        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)

        def fn(qv, kv, vv, kc, vc, posv):
            qh = qv.reshape(B, T, cfg.num_heads, hd).transpose(0, 2, 1, 3)
            kh = kv.reshape(B, T, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
            vh = vv.reshape(B, T, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
            positions = _decode_positions(posv, T)
            qh = _rope(qh, positions, cfg.rope_theta)
            kh = _rope(kh, positions, cfg.rope_theta)
            rep = cfg.num_heads // cfg.num_kv_heads
            out, kc, vc = _cached_attention(qh, kh, vh, kc, vc, posv, rep)
            ctx = out.transpose(0, 2, 1, 3).reshape(B, T, cfg.num_heads * hd)
            return ctx, kc, vc

        ctx, kc, vc = invoke_jnp(fn, (q, k, v, k_cache, v_cache, pos), {},
                                 name="llama_attention_cached")
        return self.o_proj(ctx), kc, vc

    def forward_cached_paged(self, x, pos, block_table, k_pages, v_pages):
        """Incremental forward against the shared PAGED KV pool (see
        :func:`_paged_attention`): attend ``x`` (positions pos..pos+T-1)
        through ``block_table``; returns (out, new_k_pages, new_v_pages)."""
        cfg = self.cfg
        B, T, _ = x.shape
        hd = cfg.hd
        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)

        def fn(qv, kv, vv, bt, kp, vp, posv):
            qh = qv.reshape(B, T, cfg.num_heads, hd).transpose(0, 2, 1, 3)
            kh = kv.reshape(B, T, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
            vh = vv.reshape(B, T, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
            positions = _decode_positions(posv, T)
            qh = _rope(qh, positions, cfg.rope_theta)
            kh = _rope(kh, positions, cfg.rope_theta)
            rep = cfg.num_heads // cfg.num_kv_heads
            out, kp, vp = _paged_attention(qh, kh, vh, kp, vp, bt, posv, rep)
            ctx = out.transpose(0, 2, 1, 3).reshape(B, T, cfg.num_heads * hd)
            return ctx, kp, vp

        ctx, kp, vp = invoke_jnp(fn, (q, k, v, block_table, k_pages,
                                      v_pages, pos), {},
                                 name="llama_attention_paged")
        return self.o_proj(ctx), kp, vp


def _attend(qh, kf, vf, mask3, rep):
    """Masked attention of ``qh`` [B, H, T, hd] against a full-length f32
    KV view ``kf``/``vf`` [B, n_kv, L, hd] with validity mask ``mask3``
    [B|1, T, L]: the contiguous layout's read (:func:`_cached_attention`)
    and the reference that the paged walk (:func:`_paged_attention`) is
    tested against. Masked columns contribute exact zeros regardless of
    what garbage the layout leaves there.

    GQA attends grouped — q reshaped to [B, n_kv, rep, T, hd] and
    contracted straight against the unrepeated cache — so the repeated-KV
    cache is never materialized per step (ADVICE r2 #4)."""
    B, H, T, hd = qh.shape
    if rep > 1:
        G = H // rep
        qg = qh.reshape(B, G, rep, T, hd).astype(jnp.float32)
        scores = jnp.einsum("bgrtd,bgjd->bgrtj", qg, kf) / math.sqrt(hd)
        scores = jnp.where(mask3[:, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bgrtj,bgjd->bgrtd", probs, vf)
        out = out.reshape(B, H, T, hd)
    else:
        scores = jnp.einsum("bhtd,bhjd->bhtj", qh.astype(jnp.float32),
                            kf) / math.sqrt(hd)
        scores = jnp.where(mask3[:, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhtj,bhjd->bhtd", probs, vf)
    return out.astype(qh.dtype)


def _cached_attention(qh, kh, vh, k_cache, v_cache, pos, rep):
    """Attention for incremental decode: write the new K/V rows at ``pos``
    into the [B, n_kv, L, hd] caches, attend the T query rows against the
    full cache with a causality+validity mask (cache column j participates
    iff j <= pos + t for query row t). One code path serves both prefill
    (T = prompt length, pos = 0) and single-token decode (T = 1).

    ``pos`` may be a scalar (the whole batch at one offset — generate())
    or a [B] vector (each row at its own offset — the serving engine's
    continuous batches, where slots join/leave mid-flight and sit at
    heterogeneous depths)."""
    B, H, T, hd = qh.shape
    L = k_cache.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        zero = jnp.int32(0)
        idx = (zero, zero, pos, zero)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, kh.astype(k_cache.dtype), idx)
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, vh.astype(v_cache.dtype), idx)
        mask = jnp.arange(L)[None, :] <= (pos + jnp.arange(T))[:, None]
        mask3 = mask[None]                      # [1, T, L]
    else:
        # per-row offsets: scatter the T new rows at each row's own columns
        cols = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # [B,T]
        b_idx = jnp.arange(B)[:, None]
        k_cache = k_cache.at[b_idx, :, cols, :].set(
            kh.transpose(0, 2, 1, 3).astype(k_cache.dtype))
        v_cache = v_cache.at[b_idx, :, cols, :].set(
            vh.transpose(0, 2, 1, 3).astype(v_cache.dtype))
        mask3 = jnp.arange(L)[None, None, :] <= cols[:, :, None]       # [B,T,L]
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    out = _attend(qh, kf, vf, mask3, rep)
    return out, k_cache, v_cache


# Tokens of K/V that one iteration of the paged read walks: 8 pages of 16,
# one lane tile of scores. Settled on the v5e (PERF.md section 6, PR 27); a
# constant, not a knob.
KV_BLOCK = 128


def kv_block(page_size: int, max_pages: int) -> int:
    """Tokens a block of the paged read holds for a pool geometry:
    ``KV_BLOCK`` in whole pages, at least one, at most the whole table (the
    tiny geometries of the tests)."""
    return min(max(KV_BLOCK // page_size, 1), max_pages) * page_size


# Query columns (heads x new positions) up to which the paged read multiplies
# a block as the pool stores it: one lane tile. The MXU pads a product's
# columns to a tile whatever their number, so up to there the zeros of a
# block-diagonal query cost nothing beside the block's bytes; past it they
# are real FLOPs (a thousandfold at a chunk), and there the compiler already
# feeds the head-split products bf16 blocks (PERF.md section 6, PR 35). A
# constant, not a knob.
WALK_LANES = 128


def walk_form(heads: int, T: int, head_dim: int) -> str:
    """Which form the paged read of ``heads`` query heads of ``head_dim``
    over ``T`` new positions takes (:func:`_walk_pages`): ``"lanes"`` for a
    decode step and a verify of a few tokens; for a prefill chunk or bucket
    ``"kernel"`` on a TPU where a head is whole lane tiles of a pool's row
    (``ops/paged_prefill.py``), else ``"heads"``. Fixed when a program is
    traced; the serving engine asks the same (``form`` on its dispatch
    spans)."""
    if heads * T <= WALK_LANES:
        return "lanes"
    return "kernel" if on_tpu() and head_dim % 128 == 0 else "heads"


# jitted by itself so that a program of many layers traces and lowers the
# walk once: unrolled, the loop's body made a step's lowering a third longer
# and the engine's warm-up with it (PERF.md section 6, PR 27)
@jax.named_scope("mx.paged_attention")
@functools.partial(jax.jit, static_argnames=("rep", "window"))
def _paged_attention(qh, kh, vh, k_pages, v_pages, block_table, pos, rep,
                     window=None):
    """Attention for incremental decode over a PAGED cache: the pool
    carries [num_pages + 1, page_size, n_kv * hd] physical pages shared by
    every request, one row a token: the K (or V) vectors of all its heads
    side by side. ``block_table`` [B, max_pages] maps each row's logical
    page i (token positions [i*ps, (i+1)*ps)) to a physical page (the
    serve/paging.PagePool ledger). The last physical page is the *sink*:
    unleased table entries point there, so pad/speculative writes land
    harmlessly and reads of unleased territory see garbage that the
    validity mask turns into exact zeros.

    The shape is what keeps a pool where it lies. A TPU stores an array
    of this shape as declared (rows of n_kv * hd lanes, padded to whole
    tiles of 128), so a scatter of whole rows updates the caller's buffer
    and nothing else of it moves; with the heads and their width as
    separate trailing dimensions (64 lanes of 128 used) the chip keeps
    the pages minor-most instead, and every program that wrote one row
    relaid the whole pool out and back (PERF.md section 6, PR 31). The
    serving engine donates the pools to each program that writes them, so
    ``k_pages`` in and ``k_pages`` out are one buffer.

    Writes scatter the T new K/V rows through the table, whole rows
    (page = table[col // ps], offset = col % ps). Reads WALK the table in
    blocks of ``KV_BLOCK`` tokens (whole pages, aligned at column 0) under
    a running float32 softmax (max, sum and accumulator), and the trip
    count is data: the deepest ACTIVE row's ``pos + T`` (a row whose table
    starts at the sink is inactive, so a stale ``pos`` there cannot
    lengthen the walk). Nothing of width ``max_len`` is gathered or
    converted: a step costs the live pages, not the table's.

    What multiplies a block has three forms, chosen when the program is
    traced by :func:`walk_form` from the query columns it brings, ``heads x
    T`` against ``WALK_LANES``, and the head's width. A decode step, and a
    verify of a few tokens, multiply the block as the pool stores it, heads
    on the lanes (:func:`_walk_lanes`): left to the head-split form, the
    chip converted every block to float32 and relaid it out for the heads in
    every trip, for a one-row product that stays on its vector unit, and
    that, not the block's bytes, was what a trip cost (PERF.md section 6, PR
    35). A prefill chunk or bucket splits the block into its heads: on a TPU
    where a head is whole lane tiles in one Pallas kernel
    (``ops/paged_prefill.py``), whose running softmax stays in VMEM where
    XLA's loop carried it through HBM in every trip, the size of the whole
    chunk's queries (PERF.md section 6, PR 37); elsewhere in the XLA loop of
    :func:`_walk_pages`. The scores are products of ``q`` and ``k`` as
    stored, summed in float32; on the lanes and in the kernel the softmax
    weights enter the second product in the values' dtype, as they do in
    the flash kernels and in what the chip makes of the XLA loop.

    What is bitwise: a block that the mask hides entirely is an exact
    no-op on (max, sum, accumulator), and column 0 is valid for every row,
    so a row's output depends neither on the trip count (who else is in
    the batch) nor, between programs of one form, on ``T``. T > 1 with
    per-row ``pos`` is the self-speculative VERIFY step (serve engine
    ``speculate=K``): column j's logits are bitwise what the sequential
    decode computes where both take one form (``heads x K <= WALK_LANES``)
    -- rejected drafts leave stale K/V rows past the accepted point that
    the causal mask hides until they are overwritten, exactly like the
    multi-token loop's speculative rows. Between the two forms (a step
    against the same column of a chunk, a verify wider than that) the
    outputs agree to the rounding of the weights and of the output to the
    served dtype (tests/test_paged_walk.py), as they do against the
    CONTIGUOUS layout (:func:`_cached_attention`), whose summation order
    differs: that parity is token identity of greedy decode
    (tests/test_serve_paging.py), not bits.

    **A sliding window.** With ``window`` (static) a query at column ``i``
    sees the keys ``i - window < j <= i`` only. The mask gets its lower
    bound, and the walk its first block: that of the earliest column any
    ACTIVE row's first query still sees, so a decode step deep in a long
    request walks ``window / block + 2`` blocks at the most, not its depth.
    The table is indexed by logical page as ever; the pages behind a row's
    window may have been given back (``serve/paging.PagePool.slide``: their
    entries point at the sink), so column 0 says nothing of a row here and a
    row is active if the page of its first new position is leased. A block
    the mask hides whole is still an exact no-op, also while a row's window
    has not begun (its running maximum is still ``-inf``: the walk starts
    where the shallowest row's window does), and a row that sees no key at
    all (an inactive one) reads zeros. With ``window=None`` none of this is
    traced: the program is letter for letter what it was."""
    B, H, T, hd = qh.shape
    G, ps = kh.shape[1], k_pages.shape[1]
    maxp = block_table.shape[1]
    L = maxp * ps
    sink = k_pages.shape[0] - 1
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    cols = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]      # [B,T]
    # pad columns of a bucketed prefill chunk can run past L: redirect
    # their writes to the sink page explicitly (index clamping would
    # alias them onto the row's LAST real page and corrupt it)
    pg = jnp.take_along_axis(block_table,
                             jnp.minimum(cols // ps, maxp - 1), axis=1)
    pg = jnp.where(cols < L, pg, jnp.int32(sink))                      # [B,T]
    off = cols % ps
    with jax.named_scope("mx.kv_write"):
        k_pages = k_pages.at[pg, off].set(
            kh.transpose(0, 2, 1, 3).reshape(B, T, G * hd)
            .astype(k_pages.dtype))
        v_pages = v_pages.at[pg, off].set(
            vh.transpose(0, 2, 1, 3).reshape(B, T, G * hd)
            .astype(v_pages.dtype))
    with jax.named_scope("mx.kv_walk"):
        out = _walk_pages(qh, k_pages, v_pages, block_table, cols, rep,
                          window)
    return out, k_pages, v_pages


def _softmax_step(m, l, s, windowed: bool):
    """One block's scores ``s`` (masked ones ``-inf``) into the running
    maximum and sum: ``(m_new, l_new, p, scale)``. Under a window a row may
    not have met a visible key yet; its maximum is then ``-inf`` and must not
    be subtracted from itself."""
    m_new = jnp.maximum(m, s.max(axis=-1))
    ref = jnp.where(jnp.isneginf(m_new), 0.0, m_new) if windowed else m_new
    p = jnp.exp(s - ref[..., None])
    scale = jnp.exp(m - ref)
    return m_new, l * scale + p.sum(axis=-1), p, scale


def _walk_pages(qh, k_pages, v_pages, block_table, cols, rep, window=None):
    """The read side of :func:`_paged_attention`: query row t of batch row
    b (at column ``cols[b, t]``) attends the row's logical columns
    ``j <= cols[b, t]``, one block of pages an iteration. A block is
    gathered as the pool lies, ``[B, pages, ps, G * hd]``; the pool itself
    is only indexed. What multiplies the block has three forms, and
    :func:`walk_form` says which a program takes.

    ``"heads"`` (a prefill chunk or bucket off a TPU, or whose head is not
    whole lane tiles): the block is split into its heads, ``[B, block, G,
    hd]``, and each head's queries meet its keys.

    ``"kernel"`` (a prefill chunk or bucket on a TPU, ``hd % 128 == 0``):
    the same, in one Pallas kernel (``ops/paged_prefill.py``) that reads
    each head's lanes of a block page by page and keeps the running softmax
    in VMEM; each query tile walks only the blocks it sees. ``cols`` is
    ``pos + t`` there, as :func:`_paged_attention` makes it.

    ``"lanes"`` (:func:`_walk_lanes`: a decode step, a verify of a few
    tokens): the heads stay on the lanes and the query takes the block's
    shape instead."""
    B, H, T, hd = qh.shape
    G, ps = H // rep, k_pages.shape[1]
    maxp = block_table.shape[1]
    L = maxp * ps
    sink = k_pages.shape[0] - 1
    block = kv_block(ps, maxp)
    bp = block // ps                              # pages a block
    # a table whose width is no multiple of the block ends in sink pages
    # (a clamped slice of the last block would misalign its columns)
    table = jnp.pad(block_table, ((0, 0), (0, -maxp % bp)),
                    constant_values=sink)
    last = jnp.minimum(cols, L - 1)               # deepest column a query sees
    if window is None:
        active = block_table[:, 0] != sink
        first, low = 0, None
    else:
        # the pages behind a window are given back, column 0 among them
        active = jnp.take_along_axis(
            block_table, jnp.minimum(cols[:, :1] // ps, maxp - 1),
            axis=1)[:, 0] != sink
        low = cols - (window - 1)                 # earliest column it sees
        first = jnp.maximum(
            jnp.min(jnp.where(active, low[:, 0], L)), 0) // block
    live = jnp.max(jnp.where(active, last[:, -1], 0)) + 1
    n = (live + block - 1) // block
    form = walk_form(H, T, hd)
    if form == "lanes":
        return _walk_lanes(qh, k_pages, v_pages, table, bp, last, n, rep,
                           first, low)
    if form == "kernel":
        return paged_prefill_attention(qh, k_pages, v_pages, table,
                                       cols[:, 0], L, active, block, rep,
                                       window)
    q = qh.reshape(B, G, rep, T, hd).astype(jnp.float32) / math.sqrt(hd)

    def step(i, carry):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(table, i * bp, bp, axis=1)
        kb = k_pages[pages].reshape(B, block, G, hd).astype(jnp.float32)
        vb = v_pages[pages].reshape(B, block, G, hd).astype(jnp.float32)
        col = i * block + jnp.arange(block, dtype=jnp.int32)
        mask = col[None, None, :] <= last[:, :, None]              # [B,T,blk]
        if low is not None:
            mask = mask & (col[None, None, :] >= low[:, :, None])
        s = jnp.einsum("bgrtd,bjgd->bgrtj", q, kb)
        s = jnp.where(mask[:, None, None], s, -jnp.inf)
        m_new, l, p, scale = _softmax_step(m, l, s, low is not None)
        acc = acc * scale[..., None] + jnp.einsum("bgrtj,bjgd->bgrtd", p, vb)
        return m_new, l, acc

    m0 = jnp.full((B, G, rep, T), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, G, rep, T), jnp.float32)
    acc0 = jnp.zeros((B, G, rep, T, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(first, n, step, (m0, l0, acc0))
    if low is not None:
        l = jnp.where(l == 0.0, 1.0, l)           # a row that saw no key
    return (acc / l[..., None]).reshape(B, H, T, hd).astype(qh.dtype)


def _walk_lanes(qh, k_pages, v_pages, table, bp, last, n, rep, first=0,
                low=None):
    """The walk of :func:`_walk_pages` for few query columns: ``n`` trips
    over ``table`` (padded to whole blocks of ``bp`` pages), query row t of
    batch row b seeing the columns up to ``last[b, t]``.

    A block stays as gathered, ``[B, block, G * hd]`` in the pool's dtype,
    and the query is laid out to meet it: block-diagonal ``[B, G * hd, N]``
    whose column ``(g, r, t)`` holds ``qh[b, g * rep + r, t]`` on head g's
    lanes and zeros on the others'. Scores and weighted values are then two
    plain matrix products over the block as it lies (the zeros add exact
    zeros), the accumulator is ``[B, N, G * hd]`` float32, and head g's
    output is the g-th diagonal block of it, taken once after the loop. No
    value of a block's size is converted or relaid out inside the loop."""
    B, H, T, hd = qh.shape
    G, ps = H // rep, k_pages.shape[1]
    N, block = H * T, bp * ps
    q = qh.reshape(B, G, rep * T, hd).astype(k_pages.dtype)
    heads = jnp.eye(G, dtype=q.dtype)
    q = jnp.einsum("bgnd,hg->bhdgn", q, heads).reshape(B, G * hd, N)
    # made once: left alone, the compiler rebuilds it from qh in every trip
    # (it is G times qh's size, and what inflates is not hoisted)
    q = jax.lax.optimization_barrier(q)
    last = jnp.broadcast_to(last[:, None], (B, H, T)).reshape(B, N)
    if low is not None:
        low = jnp.broadcast_to(low[:, None], (B, H, T)).reshape(B, N)

    def step(i, carry):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(table, i * bp, bp, axis=1)
        kb = k_pages[pages].reshape(B, block, G * hd)
        vb = v_pages[pages].reshape(B, block, G * hd)
        col = i * block + jnp.arange(block, dtype=jnp.int32)
        mask = col[None, None, :] <= last[:, :, None]              # [B,N,blk]
        if low is not None:
            mask = mask & (col[None, None, :] >= low[:, :, None])
        s = jnp.einsum("bjc,bcn->bnj", kb, q,
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        s = jnp.where(mask, s, -jnp.inf)
        m_new, l, p, scale = _softmax_step(m, l, s, low is not None)
        acc = acc * scale[..., None] + jnp.einsum(
            "bnj,bjc->bnc", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((B, N), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, N), jnp.float32)
    acc0 = jnp.zeros((B, N, G * hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(first, n, step, (m0, l0, acc0))
    if low is not None:
        l = jnp.where(l == 0.0, 1.0, l)           # a row that saw no key
    out = jnp.einsum("bgngd->bgnd", acc.reshape(B, G, rep * T, G, hd))
    out = out / l.reshape(B, G, rep * T, 1)
    return out.reshape(B, H, T, hd).astype(qh.dtype)


class LlamaMLP(HybridBlock):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.gate_proj = nn.Dense(cfg.intermediate_size, use_bias=False,
                                  flatten=False, in_units=cfg.hidden_size,
                                  dtype=cfg.dtype)
        self.up_proj = nn.Dense(cfg.intermediate_size, use_bias=False,
                                flatten=False, in_units=cfg.hidden_size,
                                dtype=cfg.dtype)
        self.down_proj = nn.Dense(cfg.hidden_size, use_bias=False,
                                  flatten=False, in_units=cfg.intermediate_size,
                                  dtype=cfg.dtype)

    def forward(self, x):
        return self.down_proj(npx.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaMoE(HybridBlock):
    """Top-k routed MoE with capacity-limited dense dispatch (Mesh-TF /
    Switch style). Expert weights are rank-3 Parameters shardable over 'ep'."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        E, d, f = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
        self.router = nn.Dense(E, use_bias=False, flatten=False, in_units=d,
                               dtype=cfg.dtype)
        from .. import initializer as init_mod
        for name, shape in [("w_gate", (E, d, f)), ("w_up", (E, d, f)),
                            ("w_down", (E, f, d))]:
            setattr(self, name, Parameter(
                name, shape=shape, dtype=cfg.dtype,
                init=init_mod.StackedXavier(factor_type="in", magnitude=2.0)))

    def forward(self, x):
        cfg = self.cfg
        B, T, d = x.shape
        k = cfg.num_experts_per_tok
        E = cfg.num_experts
        N = B * T
        capacity = max(int(math.ceil(k * N / E * cfg.moe_capacity_factor)), 1)
        gates_logits = self.router(x)

        def fn(xv, gl, wg, wu, wd):
            tokens = xv.reshape(N, d)
            gates = jax.nn.softmax(gl.reshape(N, E).astype(jnp.float32), axis=-1)
            dispatch = jnp.zeros((N, E, capacity), jnp.float32)
            combine = jnp.zeros((N, E, capacity), jnp.float32)
            counts = jnp.zeros((E,), jnp.float32)
            remaining = gates
            for _ in range(k):
                idx = jnp.argmax(remaining, axis=1)
                onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
                pos = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]
                pos_tok = jnp.sum(pos * onehot, axis=1)
                keep = (pos_tok < capacity).astype(jnp.float32)
                gate_val = jnp.sum(gates * onehot, axis=1)
                disp = (onehot[:, :, None]
                        * jax.nn.one_hot(
                            jnp.clip(pos_tok, 0, capacity - 1).astype(jnp.int32),
                            capacity, dtype=jnp.float32)[:, None, :]
                        * keep[:, None, None])
                dispatch = dispatch + disp
                combine = combine + disp * gate_val[:, None, None]
                counts = counts + jnp.sum(onehot * keep[:, None], axis=0)
                remaining = remaining * (1.0 - onehot)
            # normalize combine weights over selected experts
            denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
            combine = combine / jnp.maximum(denom, 1e-9)
            xin = tokens.astype(jnp.float32)
            expert_in = jnp.einsum("nec,nd->ecd", dispatch, xin)
            ein = expert_in.astype(wg.dtype)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ein, wg)) * \
                jnp.einsum("ecd,edf->ecf", ein, wu)
            eout = jnp.einsum("ecf,efd->ecd", h, wd).astype(jnp.float32)
            y = jnp.einsum("nec,ecd->nd", combine, eout)
            return y.reshape(B, T, d).astype(xv.dtype)

        return invoke_jnp(fn, (x, gates_logits, self.w_gate.data(),
                               self.w_up.data(), self.w_down.data()), {},
                          name="moe")


class LlamaDecoderLayer(HybridBlock):
    def __init__(self, cfg: LlamaConfig, layer_idx: int):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(epsilon=cfg.rms_eps,
                                          in_channels=cfg.hidden_size,
                                          dtype=cfg.dtype)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(epsilon=cfg.rms_eps,
                                                   in_channels=cfg.hidden_size,
                                                   dtype=cfg.dtype)
        use_moe = cfg.num_experts > 0 and (layer_idx % cfg.moe_every == 0)
        self.mlp = LlamaMoE(cfg) if use_moe else LlamaMLP(cfg)

    def forward(self, x):
        x = x + self.self_attn(self.input_layernorm(x))
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x

    def forward_cached(self, x, pos, k_cache, v_cache):
        attn, kc, vc = self.self_attn.forward_cached(
            self.input_layernorm(x), pos, k_cache, v_cache)
        x = x + attn
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x, kc, vc

    def forward_cached_paged(self, x, pos, block_table, k_pages, v_pages):
        attn, kp, vp = self.self_attn.forward_cached_paged(
            self.input_layernorm(x), pos, block_table, k_pages, v_pages)
        x = x + attn
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x, kp, vp


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _stacked_layer(cfg: LlamaConfig, p, x):
    """One dense decoder layer as a pure fn of its (unstacked) param dict."""
    B, T, _ = x.shape
    hd = cfg.hd
    h = _rms(x, p["ln1"], cfg.rms_eps)
    q = h @ p["wq"].T
    k = h @ p["wk"].T
    v = h @ p["wv"].T
    qh = q.reshape(B, T, cfg.num_heads, hd).transpose(0, 2, 1, 3)
    kh = k.reshape(B, T, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    vh = v.reshape(B, T, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    pos = jnp.arange(T)
    qh = _rope(qh, pos, cfg.rope_theta)
    kh = _rope(kh, pos, cfg.rope_theta)
    rep = cfg.num_heads // cfg.num_kv_heads
    if rep > 1:
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    out = _flash_attention(qh, kh, vh, True, None)
    ctx = out.transpose(0, 2, 1, 3).reshape(B, T, cfg.num_heads * hd)
    x = x + ctx @ p["wo"].T
    h2 = _rms(x, p["ln2"], cfg.rms_eps)
    x = x + (jax.nn.silu(h2 @ p["wg"].T) * (h2 @ p["wu"].T)) @ p["wd"].T
    return x


def _stacked_layer_cached(cfg: LlamaConfig, p, x, pos, k_cache, v_cache):
    """Cached (incremental-decode) variant of ``_stacked_layer``: one dense
    layer against its [B, n_kv, L, hd] KV cache slice."""
    B, T, _ = x.shape
    hd = cfg.hd
    h = _rms(x, p["ln1"], cfg.rms_eps)
    q = h @ p["wq"].T
    k = h @ p["wk"].T
    v = h @ p["wv"].T
    qh = q.reshape(B, T, cfg.num_heads, hd).transpose(0, 2, 1, 3)
    kh = k.reshape(B, T, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    vh = v.reshape(B, T, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    positions = _decode_positions(pos, T)
    qh = _rope(qh, positions, cfg.rope_theta)
    kh = _rope(kh, positions, cfg.rope_theta)
    rep = cfg.num_heads // cfg.num_kv_heads
    out, kc, vc = _cached_attention(qh, kh, vh, k_cache, v_cache, pos, rep)
    ctx = out.transpose(0, 2, 1, 3).reshape(B, T, cfg.num_heads * hd)
    x = x + ctx @ p["wo"].T
    h2 = _rms(x, p["ln2"], cfg.rms_eps)
    x = x + (jax.nn.silu(h2 @ p["wg"].T) * (h2 @ p["wu"].T)) @ p["wd"].T
    return x, kc, vc


def _stacked_layer_paged(cfg: LlamaConfig, p, x, pos, block_table,
                         k_pages, v_pages):
    """Paged-cache variant of ``_stacked_layer_cached``: one dense layer
    against its own [num_pages+1, ps, n_kv * hd] page-pool slice (the
    block table is shared across layers)."""
    B, T, _ = x.shape
    hd = cfg.hd
    h = _rms(x, p["ln1"], cfg.rms_eps)
    q = h @ p["wq"].T
    k = h @ p["wk"].T
    v = h @ p["wv"].T
    qh = q.reshape(B, T, cfg.num_heads, hd).transpose(0, 2, 1, 3)
    kh = k.reshape(B, T, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    vh = v.reshape(B, T, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    positions = _decode_positions(pos, T)
    qh = _rope(qh, positions, cfg.rope_theta)
    kh = _rope(kh, positions, cfg.rope_theta)
    rep = cfg.num_heads // cfg.num_kv_heads
    out, kp, vp = _paged_attention(qh, kh, vh, k_pages, v_pages,
                                   block_table, pos, rep)
    ctx = out.transpose(0, 2, 1, 3).reshape(B, T, cfg.num_heads * hd)
    x = x + ctx @ p["wo"].T
    h2 = _rms(x, p["ln2"], cfg.rms_eps)
    x = x + (jax.nn.silu(h2 @ p["wg"].T) * (h2 @ p["wu"].T)) @ p["wd"].T
    return x, kp, vp


class LlamaStackedDecoder(HybridBlock):
    """All decoder layers as stacked (num_layers, ...) Parameters.

    Dense path: ``lax.scan`` over the layer axis (compile time independent
    of depth). With ``cfg.pp_mesh`` set, layers are grouped into
    mesh.shape[pp_axis] stages and executed by the GPipe schedule
    (parallel/pipeline.py) — PP first-class per SURVEY §2.3.

    KV-cache decode is supported (``forward_cached``): caches are stacked
    [num_layers, B, n_kv, L, hd] arrays scanned alongside the layer
    parameters — closes the r2 limitation where stacked decoders fell back
    to cache-free O(L²) decode."""

    _WEIGHTS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        if cfg.num_experts > 0:
            raise MXNetError("stacked decoder does not support MoE layers")
        if cfg.attn_impl != "flash" or cfg.sp_mesh is not None:
            raise MXNetError(
                "stacked decoder supports flash attention only; ring/ulysses "
                "sequence parallelism requires the per-layer (non-stacked) "
                "decoder")
        self.cfg = cfg
        N, d, f, hd = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size, cfg.hd
        from .. import initializer as init_mod
        shapes = {
            "ln1": (N, d), "ln2": (N, d),
            "wq": (N, cfg.num_heads * hd, d),
            "wk": (N, cfg.num_kv_heads * hd, d),
            "wv": (N, cfg.num_kv_heads * hd, d),
            "wo": (N, d, cfg.num_heads * hd),
            "wg": (N, f, d), "wu": (N, f, d), "wd": (N, d, f),
        }
        for name, shape in shapes.items():
            init = init_mod.Constant(1.0) if name.startswith("ln") \
                else init_mod.StackedXavier()
            setattr(self, name, Parameter(name, shape=shape, dtype=cfg.dtype,
                                          init=init))

    def forward(self, x):
        cfg = self.cfg
        names = ["ln1", "ln2"] + list(self._WEIGHTS)
        arrays = [getattr(self, n).data() for n in names]

        def fn(xv, *pv):
            stacked = dict(zip(names, pv))

            def layer_step(h, p):
                return _stacked_layer(cfg, p, h), None

            if cfg.pp_mesh is not None:
                from ..parallel.pipeline import gpipe
                S = cfg.pp_mesh.shape[cfg.pp_axis]
                if cfg.num_layers % S:
                    raise MXNetError(
                        f"num_layers {cfg.num_layers} not divisible by "
                        f"pp={S}")
                L = cfg.num_layers // S
                staged = jax.tree.map(
                    lambda a: a.reshape(S, L, *a.shape[1:]), stacked)

                def stage_fn(p_loc, h):
                    return jax.lax.scan(layer_step, h, p_loc)[0]

                return gpipe(stage_fn, staged, xv, mesh=cfg.pp_mesh,
                             axis=cfg.pp_axis,
                             num_microbatches=cfg.pp_microbatches)
            return jax.lax.scan(layer_step, xv, stacked)[0]

        return invoke_jnp(fn, (x, *arrays), {}, name="stacked_decoder")

    def forward_cached(self, x, pos, k_caches, v_caches):
        """Incremental forward through all layers: scan consumes each
        layer's parameter slice + cache slice, carries the hidden state,
        and emits the updated cache slices."""
        cfg = self.cfg
        names = ["ln1", "ln2"] + list(self._WEIGHTS)
        arrays = [getattr(self, n).data() for n in names]

        def fn(xv, posv, kcs, vcs, *pv):
            stacked = dict(zip(names, pv))

            def layer_step(h, inputs):
                p, kc, vc = inputs
                h2, kc2, vc2 = _stacked_layer_cached(cfg, p, h, posv, kc, vc)
                return h2, (kc2, vc2)

            h, (new_k, new_v) = jax.lax.scan(layer_step, xv,
                                             (stacked, kcs, vcs))
            return h, new_k, new_v

        return invoke_jnp(fn, (x, pos, k_caches, v_caches, *arrays), {},
                          name="stacked_decoder_cached")

    def forward_cached_paged(self, x, pos, block_table, k_pages, v_pages):
        """Paged incremental forward: scan consumes each layer's parameter
        slice + page-pool slice ([num_layers, num_pages+1, ps, n_kv * hd]);
        the block table is loop-invariant (all layers share one table)."""
        cfg = self.cfg
        names = ["ln1", "ln2"] + list(self._WEIGHTS)
        arrays = [getattr(self, n).data() for n in names]

        def fn(xv, posv, bt, kps, vps, *pv):
            stacked = dict(zip(names, pv))

            def layer_step(h, inputs):
                p, kp, vp = inputs
                h2, kp2, vp2 = _stacked_layer_paged(cfg, p, h, posv, bt,
                                                    kp, vp)
                return h2, (kp2, vp2)

            h, (new_k, new_v) = jax.lax.scan(layer_step, xv,
                                             (stacked, kps, vps))
            return h, new_k, new_v

        return invoke_jnp(fn, (x, pos, block_table, k_pages, v_pages,
                               *arrays), {},
                          name="stacked_decoder_paged")


class LlamaModel(HybridBlock):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         dtype=cfg.dtype)
        if cfg.stacked or cfg.pp_mesh is not None:
            self.layers = LlamaStackedDecoder(cfg)
        else:
            self.layers = nn.HybridSequential()
            for i in range(cfg.num_layers):
                self.layers.add(LlamaDecoderLayer(cfg, i))
        self.norm = nn.RMSNorm(epsilon=cfg.rms_eps, in_channels=cfg.hidden_size,
                               dtype=cfg.dtype)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        x = self.layers(x)
        return self.norm(x)

    def cache_spec(self, batch: int, max_len: int):
        """[(shape, dtype)] for the flat KV cache. Per-layer decoder:
        k0, v0, k1, v1, ...; stacked decoder: one stacked K and one stacked
        V array [num_layers, B, n_kv, L, hd]."""
        cfg = self.cfg
        if cfg.pp_mesh is not None:
            raise MXNetError("KV-cache decode is not supported under "
                             "pipeline parallelism; use the cache-free path")
        if cfg.num_experts > 0:
            # capacity-based MoE routing over B tokens per decode step can
            # route differently from the full-buffer uncached forward
            # (ADVICE r2 #1) — refuse rather than silently diverge
            raise MXNetError("KV-cache decode is not supported for MoE "
                             "configs; use the cache-free path")
        if cfg.sp_mesh is not None:
            # cached decode would silently bypass the configured ring/ulysses
            # sharded attention (ADVICE r2 #2)
            raise MXNetError("KV-cache decode is not supported with "
                             "sequence-parallel attention (sp_mesh); use "
                             "the cache-free path")
        shp = (batch, cfg.num_kv_heads, max_len, cfg.hd)
        if cfg.stacked:
            return [((cfg.num_layers,) + shp, cfg.dtype)] * 2
        return [(shp, cfg.dtype)] * (2 * cfg.num_layers)

    def cache_spec_paged(self, num_pages: int, page_size: int):
        """[(shape, dtype)] for the PAGED KV pool (serve/paging): per-layer
        decoder k0, v0, ... of [num_pages, page_size, n_kv * hd], one row
        a token (:func:`_paged_attention` says why); stacked decoder one
        stacked K and one stacked V of
        [num_layers, num_pages, page_size, n_kv * hd]. The caller passes
        the physical count (the engine adds its sink page, and donates
        the pools to every program that writes them). Same
        unsupported-config refusals as :meth:`cache_spec`."""
        self.cache_spec(1, page_size)        # shared pp/MoE/sp refusals
        cfg = self.cfg
        shp = (num_pages, page_size, cfg.num_kv_heads * cfg.hd)
        if cfg.stacked:
            return [((cfg.num_layers,) + shp, cfg.dtype)] * 2
        return [(shp, cfg.dtype)] * (2 * cfg.num_layers)

    def _embed(self, input_ids, caches):
        """The token embedding of a cached forward; behind its caches (a
        stacked pair, or two a layer) a serving program may bring the table
        to gather from (generation.embed_operand)."""
        from .generation import embed_lookup
        n = 2 if self.cfg.stacked else 2 * self.cfg.num_layers
        return embed_lookup(self.embed_tokens, input_ids, *caches[n:])

    def forward_cached(self, input_ids, pos, *caches):
        x = self._embed(input_ids, caches)
        if self.cfg.stacked:
            x, new_k, new_v = self.layers.forward_cached(
                x, pos, caches[0], caches[1])
            return (self.norm(x), new_k, new_v)
        new_caches = []
        for i, layer in enumerate(self.layers._children.values()):
            x, kc, vc = layer.forward_cached(
                x, pos, caches[2 * i], caches[2 * i + 1])
            new_caches += [kc, vc]
        return (self.norm(x), *new_caches)

    def forward_cached_paged(self, input_ids, pos, block_table, *caches):
        x = self._embed(input_ids, caches)
        if self.cfg.stacked:
            x, new_k, new_v = self.layers.forward_cached_paged(
                x, pos, block_table, caches[0], caches[1])
            return (self.norm(x), new_k, new_v)
        new_caches = []
        for i, layer in enumerate(self.layers._children.values()):
            x, kp, vp = layer.forward_cached_paged(
                x, pos, block_table, caches[2 * i], caches[2 * i + 1])
            new_caches += [kp, vp]
        return (self.norm(x), *new_caches)


class LlamaForCausalLM(HybridBlock):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        if not cfg.tie_embeddings:
            self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                    flatten=False, in_units=cfg.hidden_size,
                                    dtype=cfg.dtype)
        else:
            self.lm_head = None

    def forward(self, input_ids):
        h = self.model(input_ids)
        return self._logits(h)

    def _logits(self, h):
        if self.lm_head is not None:
            return self.lm_head(h)
        from ..ops.int8_gemv import gemv_max_m
        q = getattr(self, "_q_lm_head", None)
        if q is not None and h.shape[0] * h.shape[1] <= gemv_max_m():
            # weight-only int8/int4 tied head (contrib/quantization), vocab
            # dim padded to a 128-lane multiple and sliced back after the GEMV
            w_q, scale, V = q

            def fn(hv):
                import jax.numpy as jnp
                from ..ops.int8_gemv import (int4_weight_matmul,
                                             int8_weight_matmul)
                if w_q.dtype == jnp.uint8:   # packed int4 nibble table
                    y = int4_weight_matmul(hv.reshape(-1, hv.shape[-1]),
                                           w_q, scale)
                else:
                    y = int8_weight_matmul(hv.reshape(-1, hv.shape[-1]),
                                           w_q, scale)
                y = y.reshape(hv.shape[:-1] + (w_q.shape[0],))[..., :V]
                return y.astype(hv.dtype)
            return invoke_jnp(fn, (h,), {}, name="lm_head_int8")
        w = self.model.embed_tokens.weight.data()
        return invoke_jnp(lambda hv, wv: hv @ wv.T, (h, w), {})

    def embed_table(self):
        """The embedding's Parameter (generation.embed_operand)."""
        return self.model.embed_tokens.weight

    def head_weights(self):
        """(int8 table, scales, vocab) for fused LM-head sampling, or None
        (untied heads keep the unfused path — the Dense owns the weight)."""
        if self.lm_head is not None:
            return None
        return getattr(self, "_q_lm_head", None)

    def cache_spec(self, batch: int, max_len: int):
        return self.model.cache_spec(batch, max_len)

    def cache_spec_paged(self, num_pages: int, page_size: int):
        return self.model.cache_spec_paged(num_pages, page_size)

    def walk_form(self, T: int) -> str:
        """The form a layer's paged read takes over ``T`` new positions
        (:func:`walk_form`)."""
        return walk_form(self.cfg.num_heads, T, self.cfg.hd)

    def forward_cached(self, input_ids, pos, *caches):
        h, *new_caches = self.model.forward_cached(input_ids, pos, *caches)
        return (self._logits(h), *new_caches)

    def forward_cached_paged(self, input_ids, pos, block_table, *caches):
        h, *new_caches = self.model.forward_cached_paged(
            input_ids, pos, block_table, *caches)
        return (self._logits(h), *new_caches)

    def forward_cached_hidden(self, input_ids, pos, *caches):
        """Incremental forward returning the final hidden state (no
        logits): the fused LM-head sampling path folds the tied-head GEMV
        into token selection (ops/fused_block_gemv). Works for per-layer
        AND stacked-scan decoders (the cache protocol is shared)."""
        return self.model.forward_cached(input_ids, pos, *caches)

    def forward_cached_paged_hidden(self, input_ids, pos, block_table,
                                    *caches):
        """Paged variant of :meth:`forward_cached_hidden` (fused LM-head
        sampling over the paged pool)."""
        return self.model.forward_cached_paged(input_ids, pos, block_table,
                                               *caches)


def llama_shardings(model: LlamaForCausalLM, tp: Optional[str] = "tp",
                    ep: Optional[str] = "ep", pp: Optional[str] = None,
                    dp_embed: bool = False):
    """Annotate Megatron-style TP shardings (+ EP for MoE experts, + PP
    stage placement for the stacked decoder) on the model's Parameters;
    consumed by parallel.TrainStep. Pass ``tp=None``/``ep=None`` when the
    mesh lacks that axis."""
    from jax.sharding import PartitionSpec as P
    for name, p in model.collect_params().items():
        base = name.rsplit(".", 1)[-1]
        if base in LlamaStackedDecoder._WEIGHTS + ("ln1", "ln2"):
            # stacked decoder params: leading layer axis rides pp stages
            p.sharding = P(pp, *([None] * (len(p.shape) - 1))) \
                if pp is not None else None
            continue
        if tp is None:
            continue
        if name.endswith(("q_proj.weight", "k_proj.weight", "v_proj.weight",
                          "gate_proj.weight", "up_proj.weight")):
            p.sharding = P(tp, None)          # column parallel
        elif name.endswith(("o_proj.weight", "down_proj.weight")):
            p.sharding = P(None, tp)          # row parallel
        elif name.endswith("lm_head.weight"):
            p.sharding = P(tp, None)
        elif name.endswith("embed_tokens.weight"):
            p.sharding = P(None, tp)
        elif ep is not None and (name.endswith("w_gate") or name.endswith("w_up")
                                 or name.endswith("w_down")
                                 or ".w_gate" in name or ".w_up" in name
                                 or ".w_down" in name):
            p.sharding = P(ep, None, None)    # expert parallel
    return model
