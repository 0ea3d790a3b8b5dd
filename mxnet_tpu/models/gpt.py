"""GPT-2-family decoder LM (Gluon blocks): learned positions, pre-LN,
GELU MLP, causal fused attention.

Device operations carry the program's names (``jax.named_scope``: metadata
only, read back from a profiler trace by the operation's scope path):
``mx.embed``, ``mx.attn`` (flash kernels included; on the paged path
``mx.paged_attention`` nests inside it), ``mx.mlp``, ``mx.lm_head``.
Backward operations keep the name inside ``transpose(jvp(...))``."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .. import numpy_extension as npx
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import invoke_jnp
from ..ops.attention import flash_attention as _flash_attention

__all__ = ["GPTConfig", "GPTModel", "GPT2_SMALL", "GPT_TINY"]


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position_embeddings: int = 1024
    dropout: float = 0.1
    layer_norm_eps: float = 1e-5
    dtype: object = jnp.float32


GPT2_SMALL = GPTConfig()
GPT_TINY = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                     max_position_embeddings=128)


class GPTBlock(HybridBlock):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        d = cfg.hidden_size
        self.ln_1 = nn.LayerNorm(epsilon=cfg.layer_norm_eps, in_channels=d)
        self.attn_qkv = nn.Dense(3 * d, flatten=False, in_units=d, dtype=cfg.dtype)
        self.attn_out = nn.Dense(d, flatten=False, in_units=d, dtype=cfg.dtype)
        self.ln_2 = nn.LayerNorm(epsilon=cfg.layer_norm_eps, in_channels=d)
        self.mlp_fc = nn.Dense(4 * d, flatten=False, in_units=d, dtype=cfg.dtype)
        self.mlp_proj = nn.Dense(d, flatten=False, in_units=4 * d, dtype=cfg.dtype)
        self.dropout = nn.Dropout(cfg.dropout)
        self._heads = cfg.num_heads

    def forward(self, x):
        B, T, d = x.shape
        H = self._heads
        hd = d // H

        def fn(qkv_v):
            q, k, v = jnp.split(qkv_v, 3, axis=-1)
            qh = q.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            kh = k.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            vh = v.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            o = _flash_attention(qh, kh, vh, True, None)
            return o.transpose(0, 2, 1, 3).reshape(B, T, d)

        with jax.named_scope("mx.attn"):
            qkv = self.attn_qkv(self.ln_1(x))
            x = x + self.dropout(self.attn_out(
                invoke_jnp(fn, (qkv,), {}, name="gpt_attention")))
        return self._mlp(x)

    def _mlp(self, x):
        with jax.named_scope("mx.mlp"):
            h = npx.gelu(self.mlp_fc(self.ln_2(x)))
            return x + self.dropout(self.mlp_proj(h))

    def forward_cached(self, x, pos, k_cache, v_cache):
        """Incremental forward against the [B, H, L, hd] KV caches."""
        from .llama import _cached_attention
        B, T, d = x.shape
        H = self._heads
        hd = d // H

        def fn(qkv_v, kc, vc, posv):
            q, k, v = jnp.split(qkv_v, 3, axis=-1)
            qh = q.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            kh = k.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            vh = v.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            out, kc, vc = _cached_attention(qh, kh, vh, kc, vc, posv, 1)
            return out.transpose(0, 2, 1, 3).reshape(B, T, d), kc, vc

        with jax.named_scope("mx.attn"):
            qkv = self.attn_qkv(self.ln_1(x))
            ctx, kc, vc = invoke_jnp(fn, (qkv, k_cache, v_cache, pos), {},
                                     name="gpt_attention_cached")
            x = x + self.dropout(self.attn_out(ctx))
        return self._mlp(x), kc, vc

    def forward_cached_paged(self, x, pos, block_table, k_pages, v_pages):
        """Incremental forward against the shared PAGED KV pool
        (models/llama._paged_attention)."""
        from .llama import _paged_attention
        B, T, d = x.shape
        H = self._heads
        hd = d // H

        def fn(qkv_v, bt, kp, vp, posv):
            q, k, v = jnp.split(qkv_v, 3, axis=-1)
            qh = q.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            kh = k.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            vh = v.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
            out, kp, vp = _paged_attention(qh, kh, vh, kp, vp, bt, posv, 1)
            return out.transpose(0, 2, 1, 3).reshape(B, T, d), kp, vp

        with jax.named_scope("mx.attn"):
            qkv = self.attn_qkv(self.ln_1(x))
            ctx, kp, vp = invoke_jnp(fn, (qkv, block_table, k_pages,
                                          v_pages, pos), {},
                                     name="gpt_attention_paged")
            x = x + self.dropout(self.attn_out(ctx))
        return self._mlp(x), kp, vp


class GPTModel(HybridBlock):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype)
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                                dtype=cfg.dtype)
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.HybridSequential()
        for _ in range(cfg.num_layers):
            self.blocks.add(GPTBlock(cfg))
        self.ln_f = nn.LayerNorm(epsilon=cfg.layer_norm_eps,
                                 in_channels=cfg.hidden_size)

    def forward(self, input_ids):
        from .. import numpy as np
        B, T = input_ids.shape
        pos = np.arange(T, dtype="int32")
        with jax.named_scope("mx.embed"):
            x = self.drop(self.wte(input_ids) + self.wpe(pos))
        x = self.blocks(x)
        x = self.ln_f(x)
        return self._lm_head(x)  # tied; int8-streamed at decode if quantized

    def cache_spec(self, batch: int, max_len: int):
        """[(shape, dtype)] for the flat KV cache: k0, v0, k1, v1, ..."""
        cfg = self.cfg
        shp = (batch, cfg.num_heads, max_len, cfg.hidden_size // cfg.num_heads)
        return [(shp, cfg.dtype)] * (2 * cfg.num_layers)

    def cache_spec_paged(self, num_pages: int, page_size: int):
        """[(shape, dtype)] for the PAGED KV pool (serve/paging): k0, v0,
        ... of [num_pages, page_size, hidden_size]: one row a token, its
        heads side by side, the shape a TPU keeps as declared and updates
        in place (models/llama._paged_attention). The caller passes the
        physical page count (the engine adds its sink page, and donates
        the pools to every program that writes them)."""
        cfg = self.cfg
        shp = (num_pages, page_size, cfg.hidden_size)
        return [(shp, cfg.dtype)] * (2 * cfg.num_layers)

    def walk_form(self, T: int) -> str:
        """The form a layer's paged read takes over ``T`` new positions
        (``models/llama.walk_form``)."""
        from .llama import walk_form
        return walk_form(self.cfg.num_heads, T,
                         self.cfg.hidden_size // self.cfg.num_heads)

    def forward_cached(self, input_ids, pos, *caches):
        hidden, *new_caches = self.forward_cached_hidden(input_ids, pos,
                                                         *caches)
        logits = self._lm_head(hidden)
        return (logits, *new_caches)

    def forward_cached_paged(self, input_ids, pos, block_table, *caches):
        hidden, *new_caches = self.forward_cached_paged_hidden(
            input_ids, pos, block_table, *caches)
        logits = self._lm_head(hidden)
        return (logits, *new_caches)

    def forward_cached_hidden(self, input_ids, pos, *caches):
        """Incremental forward returning the FINAL HIDDEN STATE instead of
        logits: the fused LM-head sampling path (ops/fused_block_gemv.
        fused_lm_head_sample) folds the head GEMV into token selection, so
        the [B, V] logits are never materialized."""
        B, T = input_ids.shape

        def _positions(posv):
            # scalar pos: whole batch at one offset; [B] pos: per-sequence
            # offsets (serving engine continuous batches)
            from .llama import _decode_positions
            p = _decode_positions(posv, T)
            return p[None, :].repeat(B, axis=0) if p.ndim == 1 else p

        with jax.named_scope("mx.embed"):
            positions = invoke_jnp(_positions, (pos,), {})
            x = self.drop(self._embed(input_ids, caches) + self.wpe(positions))
        new_caches = []
        for i, blk in enumerate(self.blocks):
            x, kc, vc = blk.forward_cached(
                x, pos, caches[2 * i], caches[2 * i + 1])
            new_caches += [kc, vc]
        x = self.ln_f(x)
        return (x, *new_caches)

    def forward_cached_paged_hidden(self, input_ids, pos, block_table,
                                    *caches):
        """Paged variant of :meth:`forward_cached_hidden`: the per-layer
        page pools replace the per-slot contiguous caches; positions flow
        exactly as in the contiguous path."""
        B, T = input_ids.shape

        def _positions(posv):
            from .llama import _decode_positions
            p = _decode_positions(posv, T)
            return p[None, :].repeat(B, axis=0) if p.ndim == 1 else p

        with jax.named_scope("mx.embed"):
            positions = invoke_jnp(_positions, (pos,), {})
            x = self.drop(self._embed(input_ids, caches) + self.wpe(positions))
        new_caches = []
        for i, blk in enumerate(self.blocks):
            x, kp, vp = blk.forward_cached_paged(
                x, pos, block_table, caches[2 * i], caches[2 * i + 1])
            new_caches += [kp, vp]
        x = self.ln_f(x)
        return (x, *new_caches)

    def embed_table(self):
        """The embedding's Parameter: what generation.embed_operand makes a
        serving program's gather table from."""
        return self.wte.weight

    def _embed(self, input_ids, caches):
        """The token embedding of a cached forward; behind its two caches a
        layer a serving program may bring the table to gather from."""
        from .generation import embed_lookup
        return embed_lookup(self.wte, input_ids,
                            *caches[2 * len(self.blocks):])

    def head_weights(self):
        """(int8 table [Vp, D], scales [Vp], vocab) for the fused LM-head
        sampling path, or None when the tied head is not int8-quantized."""
        return getattr(self, "_q_lm_head", None)

    @jax.named_scope("mx.lm_head")
    def _lm_head(self, x):
        """Tied LM head. When quantize_net stored a weight-only int8 table
        (contrib/quantization._quantize_tied_lm_head) and the row count is
        decode-sized, stream the table as int8 — half the HBM bytes of the
        bf16 read that dominates per-token cost. The table's vocab dim is
        padded to a 128-lane multiple; logits are sliced back to V (free —
        XLA folds the slice into the consumer)."""
        from ..ops.int8_gemv import gemv_max_m
        q = getattr(self, "_q_lm_head", None)
        B, T = x.shape[0], x.shape[1]
        if q is not None and B * T <= gemv_max_m():
            w_q, scale, V = q

            def fn(h):
                import jax.numpy as jnp
                from ..ops.int8_gemv import (int4_weight_matmul,
                                             int8_weight_matmul)
                D = h.shape[-1]
                if w_q.dtype == jnp.uint8:   # packed int4 nibble table
                    y = int4_weight_matmul(h.reshape(-1, D), w_q, scale)
                else:
                    y = int8_weight_matmul(h.reshape(-1, D), w_q, scale)
                y = y.reshape(h.shape[:-1] + (w_q.shape[0],))[..., :V]
                return y.astype(h.dtype)
            return invoke_jnp(fn, (x,), {}, name="lm_head_int8")
        w = self.wte.weight.data()
        return invoke_jnp(lambda h, wv: h @ wv.T, (x, w), {}, name="lm_head")
