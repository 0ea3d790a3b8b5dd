"""EvaByte decoder LM (Gluon blocks): a byte-level model whose attention keeps
the query's own window exactly and everything before it as chunk summaries
(EVA, ``ops/eva_attention.py`` has the equations).

Identical pre-norm blocks: RMSNorm with gain ``1 + w``
(``norm_add_unit_offset``), EVA attention over as many key/value heads as
query heads with rotary positions and, per layer and head, the two learned
vectors ``phi`` (pools a chunk's keys) and ``mu`` (added to a pooled key),
llama's SwiGLU MLP, no bias, residual additions in float32
(``fp32_skip_add``). A vocabulary of 320 bytes and specials, untied; the
output head has ``num_pred_heads * vocab_size`` columns, head ``i`` predicting
the byte ``i + 1`` ahead, and its logits are float32 (``fp32_logits``).

``forward(ids)`` is the definition over a whole sequence, no cache, and
returns every head: ``[B, T, num_pred_heads, vocab]``. **It is served by its
first head**: the paged protocol's logits are ``[B, T, vocab]``, the product
of the hidden state with the head's first ``vocab`` columns only. (Drafting
from heads 1.. and verifying is the release's faster decoding path, and is
not built: ``ROADMAP.md``.)

**The cache is a paged cache that folds.** K and V pools of rows of heads
side by side, ``[pages + 1, page_size, heads * hd]`` as
``models/llama._paged_attention`` lays them out, with ``page_size =
window_size / chunk_size``: the summaries of a finished window are exactly
one page. **A layer's heads are held in groups of at most ``POOL_LANES`` =
2,048 lanes**, a K and a V pool a group (the published 32 heads of 128: two
groups of 16), and each group is attended by a call of its own: from a pool
whose rows are wider, the TPU compiler's gather of a decode batch's pages
first slices the *whole pool* into halves of 2,048 lanes, in every trip of
the walk (a step of 16 rows took 234 ms so, PERF.md section 6, PR 34).
Attention is independent a head, so the grouping changes no number. The paged
protocol here is

- ``cache_spec_paged(num_pages, page_size)``: the pools;
- ``cache_fold(page_size)``: the :class:`~mxnet_tpu.ops.eva_attention.
  FoldedPages` that tells the serving engine how many pages a request of
  some depth holds, how wide a table gets, and which dispatches end a window
  (the engine takes no argument for any of it);
- ``forward_cached_paged(ids, pos, block_table, valid, *pools)``: ``pos``
  ``[B]`` each row's TRUE first new position (rotary positions count from
  it) and ``valid`` ``[B]`` how many of its ``T`` positions are real (the
  padding of a prompt's last chunk must not end a window). The new rows are
  written at the folded columns, the walk that GPT-2 and llama decode with
  (``_paged_attention``) attends summaries and window in one running
  softmax, and a row whose real positions reach a multiple of ``window_size``
  has its window summarised into the table entry behind the window's pages,
  which the host has filled with a fresh page (``ops.eva_attention.
  fold_windows``). The ``T`` positions of a call lie in one window.

Device operations carry the scopes ``mx.embed``, ``mx.attn`` (inside it
``mx.eva_attn`` around the write, the walk and the summarising, which is
``mx.eva_summarize``; the shared walk keeps ``mx.kv_write`` / ``mx.kv_walk``),
``mx.mlp``, ``mx.lm_head``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ndarray import invoke_jnp
from ..ops import eva_attention as _eva
from .llama import (LlamaMLP, _decode_positions, _paged_attention, _rope,
                    walk_form)

__all__ = ["EvaByteConfig", "EvaByteForCausalLM", "EVABYTE_TINY"]

#: the widest row (in lanes) of a pool that a TPU gathers pages from where
#: the pool lies (module docstring); a constant of the chip's compiler, not
#: a knob
POOL_LANES = 2048


@dataclasses.dataclass
class EvaByteConfig:
    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    head_dim: int = 128
    chunk_size: int = 16
    window_size: int = 2048
    num_pred_heads: int = 8
    rope_theta: float = 100000.0
    rms_eps: float = 1e-5
    max_position_embeddings: int = 32768
    dtype: object = jnp.bfloat16

    @property
    def page_size(self) -> int:
        """Rows of a page: the summaries one window leaves."""
        return self.window_size // self.chunk_size

    @property
    def group_heads(self) -> int:
        """Heads whose keys (or values) share a pool's row."""
        return max(1, min(self.num_heads, POOL_LANES // self.head_dim))


EVABYTE_TINY = EvaByteConfig(
    hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
    head_dim=16, chunk_size=4, window_size=32, num_pred_heads=3,
    max_position_embeddings=256, dtype=jnp.float32)


def _dense(units, in_units, dtype):
    return nn.Dense(units, use_bias=False, flatten=False, in_units=in_units,
                    dtype=dtype)


def _heads(x, n, hd):
    B, T, _ = x.shape
    return x.reshape(B, T, n, hd).transpose(0, 2, 1, 3)


class EvaUnitNorm(HybridBlock):
    """RMSNorm whose gain is ``1 + w`` (``norm_add_unit_offset``): a zero
    ``w`` is the identity gain. The gain is applied in float32."""

    def __init__(self, size: int, eps: float, dtype):
        super().__init__()
        self._eps = eps
        self.weight = Parameter("weight", shape=(size,), dtype=dtype,
                                init="zeros")

    def forward(self, x):
        def fn(xv, w):
            xf = xv.astype(jnp.float32)
            var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
            return (xf * jax.lax.rsqrt(var + self._eps)
                    * (1.0 + w.astype(jnp.float32))).astype(xv.dtype)

        return invoke_jnp(fn, (x, self.weight.data()), {}, name="eva_norm")


def _skip_add(x, y):
    """A residual addition in float32 (``fp32_skip_add``)."""
    return invoke_jnp(
        lambda a, b: (a.astype(jnp.float32)
                      + b.astype(jnp.float32)).astype(a.dtype),
        (x, y), {}, name="eva_skip_add")


def _rotated(cfg, q, k, v, positions):
    """The projections as heads ``[B, H, T, hd]``, queries and keys turned
    to their positions."""
    H, hd = cfg.num_heads, cfg.head_dim
    return (_rope(_heads(q, H, hd), positions, cfg.rope_theta),
            _rope(_heads(k, H, hd), positions, cfg.rope_theta),
            _heads(v, H, hd))


class EvaAttention(HybridBlock):
    def __init__(self, cfg: EvaByteConfig):
        super().__init__()
        self.cfg = cfg
        D, H, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
        self.q_proj = _dense(H * hd, D, cfg.dtype)
        self.k_proj = _dense(H * hd, D, cfg.dtype)
        self.v_proj = _dense(H * hd, D, cfg.dtype)
        self.o_proj = _dense(D, H * hd, cfg.dtype)
        self.phi = Parameter("phi", shape=(H, hd), dtype=cfg.dtype,
                             init="zeros")
        self.mu = Parameter("mu", shape=(H, hd), dtype=cfg.dtype,
                            init="zeros")

    def forward(self, x):
        cfg = self.cfg
        B, T, _ = x.shape

        def fn(q, k, v, phi, mu):
            q, k, v = _rotated(cfg, q, k, v, jnp.arange(T))
            o = _eva.eva_attention(q, k, v, phi, mu, cfg.chunk_size,
                                   cfg.window_size)
            return o.transpose(0, 2, 1, 3).reshape(B, T, -1)

        o = invoke_jnp(fn, (self.q_proj(x), self.k_proj(x), self.v_proj(x),
                            self.phi.data(), self.mu.data()), {},
                       name="eva_attention")
        return self.o_proj(o)

    def forward_paged(self, x, pos, block_table, valid, *pools):
        """``pools``: a K and a V pool for each group of heads, in order."""
        cfg = self.cfg
        B, T, _ = x.shape
        fold = _eva.FoldedPages(cfg.window_size, cfg.page_size)
        gh = cfg.group_heads

        def fn(q, k, v, phi, mu, posv, bt, validv, *pools):
            posv = jnp.broadcast_to(jnp.asarray(posv, jnp.int32), (B,))
            q, k, v = _rotated(cfg, q, k, v, _decode_positions(posv, T))
            outs, new = [], []
            with jax.named_scope("mx.eva_attn"):
                for g, (kp, vp) in enumerate(zip(pools[::2], pools[1::2])):
                    hs = slice(g * gh, (g + 1) * gh)
                    # summaries and window in one running softmax: over the
                    # folded columns EVA's read is the causal walk
                    o, kp, vp = _paged_attention(
                        q[:, hs], k[:, hs], v[:, hs], kp, vp, bt,
                        fold.column(posv), 1)
                    with jax.named_scope("mx.eva_summarize"):
                        kp, vp = _eva.fold_windows(
                            kp, vp, bt, posv, validv, phi[hs], mu[hs], fold,
                            cfg.chunk_size)
                    outs.append(o)
                    new += [kp, vp]
            o = jnp.concatenate(outs, axis=1)
            return (o.transpose(0, 2, 1, 3).reshape(B, T, -1), *new)

        o, *new = invoke_jnp(
            fn, (self.q_proj(x), self.k_proj(x), self.v_proj(x),
                 self.phi.data(), self.mu.data(), pos, block_table, valid,
                 *pools), {}, name="eva_attention_paged")
        return (self.o_proj(o), *new)


class EvaDecoderLayer(HybridBlock):
    def __init__(self, cfg: EvaByteConfig):
        super().__init__()
        self.input_layernorm = EvaUnitNorm(cfg.hidden_size, cfg.rms_eps,
                                           cfg.dtype)
        self.self_attn = EvaAttention(cfg)
        self.post_attention_layernorm = EvaUnitNorm(cfg.hidden_size,
                                                    cfg.rms_eps, cfg.dtype)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x):
        with jax.named_scope("mx.attn"):
            x = _skip_add(x, self.self_attn(self.input_layernorm(x)))
        with jax.named_scope("mx.mlp"):
            return _skip_add(x, self.mlp(self.post_attention_layernorm(x)))

    def forward_cached_paged(self, x, pos, block_table, valid, *pools):
        with jax.named_scope("mx.attn"):
            y, *pools = self.self_attn.forward_paged(
                self.input_layernorm(x), pos, block_table, valid, *pools)
            x = _skip_add(x, y)
        with jax.named_scope("mx.mlp"):
            x = _skip_add(x, self.mlp(self.post_attention_layernorm(x)))
        return (x, *pools)


class EvaByteModel(HybridBlock):
    def __init__(self, cfg: EvaByteConfig):
        super().__init__()
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         dtype=cfg.dtype)
        self.layers = nn.HybridSequential()
        for _ in range(cfg.num_layers):
            self.layers.add(EvaDecoderLayer(cfg))
        self.norm = EvaUnitNorm(cfg.hidden_size, cfg.rms_eps, cfg.dtype)

    def forward(self, input_ids):
        with jax.named_scope("mx.embed"):
            x = self.embed_tokens(input_ids)
        for layer in self.layers._children.values():
            x = layer(x)
        return self.norm(x)

    def forward_cached_paged(self, input_ids, pos, block_table, valid,
                             *caches):
        with jax.named_scope("mx.embed"):
            x = self.embed_tokens(input_ids)
        new = []
        n = len(caches) // len(self.layers._children)      # pools a layer
        for i, layer in enumerate(self.layers._children.values()):
            x, *pools = layer.forward_cached_paged(
                x, pos, block_table, valid, *caches[n * i:n * (i + 1)])
            new += pools
        return (self.norm(x), *new)


class EvaByteForCausalLM(HybridBlock):
    def __init__(self, cfg: EvaByteConfig):
        super().__init__()
        if cfg.window_size % cfg.chunk_size:
            raise MXNetError("EvaByte: window_size must be a multiple of "
                             "chunk_size")
        if cfg.num_heads % cfg.group_heads:
            raise MXNetError(
                f"EvaByte: {cfg.num_heads} heads of {cfg.head_dim} do not "
                f"divide into pools of {cfg.group_heads} heads")
        self.cfg = cfg
        self.model = EvaByteModel(cfg)
        self.lm_head = _dense(cfg.num_pred_heads * cfg.vocab_size,
                              cfg.hidden_size, cfg.dtype)

    @jax.named_scope("mx.lm_head")
    def _logits(self, h, heads: int):
        """Float32 logits (``fp32_logits``) of the first ``heads`` prediction
        heads, ``[B, T, heads * vocab]``: the head's first columns only."""
        n = heads * self.cfg.vocab_size
        return invoke_jnp(
            lambda hv, w: jnp.einsum("btd,vd->btv", hv, w[:n],
                                     preferred_element_type=jnp.float32),
            (h, self.lm_head.weight.data()), {}, name="eva_lm_head")

    def forward(self, input_ids):
        """Every prediction head over a whole sequence, no cache: ``[B, T,
        num_pred_heads, vocab]`` float32."""
        cfg = self.cfg
        B, T = input_ids.shape
        return self._logits(self.model(input_ids), cfg.num_pred_heads) \
            .reshape(B, T, cfg.num_pred_heads, cfg.vocab_size)

    # ------------------------------------------------------ cache protocol
    def cache_fold(self, page_size: int) -> _eva.FoldedPages:
        """What the serving engine needs to know of a cache that folds
        (module docstring). A window's summaries are one page, so
        ``page_size`` is not free."""
        cfg = self.cfg
        if page_size != cfg.page_size:
            raise MXNetError(
                f"EvaByte folds a window of {cfg.window_size} positions "
                f"into one page of its {cfg.page_size} chunk summaries: "
                f"page_size must be {cfg.page_size}, not {page_size}")
        return _eva.FoldedPages(cfg.window_size, page_size)

    def cache_spec_paged(self, num_pages: int, page_size: int):
        """[(shape, dtype)] of the pools, in layer order a K and a V pool
        for each group of heads: ``[num_pages, page_size, group_heads *
        hd]``."""
        cfg = self.cfg
        self.cache_fold(page_size)
        kv = ((num_pages, page_size, cfg.group_heads * cfg.head_dim),
              cfg.dtype)
        return [kv, kv] * (cfg.num_heads // cfg.group_heads) * cfg.num_layers

    def walk_form(self, T: int) -> str:
        """The form the shared walk takes over ``T`` new positions, a group
        of heads a call (``models/llama.walk_form``)."""
        return walk_form(self.cfg.group_heads, T, self.cfg.head_dim)

    def forward_cached_paged(self, input_ids, pos, block_table, valid,
                             *caches):
        h, *new = self.model.forward_cached_paged(
            input_ids, pos, block_table, valid, *caches)
        return (self._logits(h, 1), *new)
